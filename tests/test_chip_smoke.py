"""chip_smoke.py and the start-up rules it rests on (CPU, tier-1).

The smoke itself runs on the chip; here: it refuses this sandbox, its
rehearsal still runs end to end, a model whose invokes fail ends the run
non-zero (the drop-and-carry-on path is SEEN), the compile cache lands
where the environment says, and a request for a TPU that is not there
raises.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, env_extra=None, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


def test_smoke_refuses_a_machine_without_a_chip():
    r = _run([SMOKE])
    assert r.returncode != 0
    assert "refusing to run" in r.stderr
    assert "ok" not in r.stdout       # before any phase, no result line


def test_rehearsal_passes_with_every_line_marked(tmp_path):
    """All five phases at tiny sizes, four virtual devices so mesh4 runs
    too; nothing it prints can be taken for a pass on the chip."""
    r = _run([SMOKE, "--rehearsal"], {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.splitlines()
    assert lines and all(ln.startswith("REHEARSAL ") for ln in lines), lines
    for phase in ("label", "serve", "decode", "kernels", "mesh4"):
        assert any(ln.startswith(f"REHEARSAL {phase}: ok") for ln in lines), \
            (phase, lines)
    assert lines[-1].startswith('REHEARSAL {"ok": true')
    # the cache went where the environment said, and nowhere else
    assert os.listdir(tmp_path / "xla")
    assert f"compile cache: {tmp_path / 'xla'}" in r.stdout


def test_rehearsal_with_a_failing_model_exits_nonzero(tmp_path):
    """A model whose input does not match the caps: every invoke raises,
    tensor_filter drops the frame and keeps the pipeline alive — and the
    smoke, reading the counters, does not pass."""
    bad = tmp_path / "bad_model.py"
    bad.write_text(
        "import jax.numpy as jnp\n"
        "from nnstreamer_tpu.tensors.info import TensorsInfo\n"
        "def get_model():\n"
        "    # no declared input (taken from the caps), then wants 7x7\n"
        "    def apply_fn(params, x):\n"
        "        return x.reshape(7, 7).astype(jnp.float32).sum(0)\n"
        "    return apply_fn, None, None, TensorsInfo.make('float32', '7')\n")
    r = _run([SMOKE, f"--rehearsal={bad}"])
    assert r.returncode != 0
    assert "label: FAILED" in r.stdout and "invoke_errors=1" in r.stdout
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_is_placed_from_outside(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR set: opening a filter leaves the config
    equal to it. Unset: <root>/.jax_cache — never a temporary name."""
    code = (
        "import jax\n"
        "from nnstreamer_tpu.filters import FilterProperties, find_filter\n"
        "fw = find_filter('jax')()\n"
        "fw.open(FilterProperties(framework='jax', "
        "model_files=('zoo://mlp',)))\n"
        "print('CACHE', jax.config.jax_compilation_cache_dir)\n")
    want = str(tmp_path / "placed") if placed \
        else os.path.join(ROOT, ".jax_cache")
    r = _run(["-c", code],
             {"JAX_COMPILATION_CACHE_DIR": want} if placed else {})
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"CACHE {want}" in r.stdout


def test_one_writer_of_the_cache_dir():
    """`jax_compilation_cache_dir` is written in exactly one place."""
    hits = []
    for base in ("nnstreamer_tpu", "tools"):
        for dirpath, _, files in os.walk(os.path.join(ROOT, base)):
            hits += [os.path.join(dirpath, f) for f in files
                     if f.endswith(".py") and '"jax_compilation_cache_dir"'
                     in open(os.path.join(dirpath, f)).read()]
    if '"jax_compilation_cache_dir"' in open(
            os.path.join(ROOT, "chip_smoke.py")).read():
        hits.append("chip_smoke.py")
    assert [os.path.relpath(h, ROOT) for h in hits] == \
        ["nnstreamer_tpu/utils/xla_cache.py"]


def test_tpu_request_without_a_tpu_raises():
    """accelerator=true:tpu on a CPU-only process is an error, not a
    filter that logs 'opened on TFRT_CPU_0' and serves."""
    from nnstreamer_tpu.filters import FilterProperties, find_filter
    from nnstreamer_tpu.filters.base import Accelerator
    fw = find_filter("jax")()
    with pytest.raises(RuntimeError, match="accelerator tpu requested"):
        fw.open(FilterProperties(
            framework="jax", model_files=("zoo://mlp",),
            accelerators=tuple(Accelerator.parse("true:tpu"))))
    # a preference list still falls through to what exists
    fw.open(FilterProperties(
        framework="jax", model_files=("zoo://mlp",),
        accelerators=tuple(Accelerator.parse("true:tpu.cpu"))))
    fw.close()


def test_unknown_device_kind_has_no_peaks():
    """utils/hw.py: a device that is not in the table is an error, not a
    default (the CPU here; an unrecognised v5 kind on a chip)."""
    import jax
    from nnstreamer_tpu.utils import hw
    with pytest.raises(ValueError, match="no peak figures"):
        hw.peak_flops(jax.devices()[0])

    class FakeTpu:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert hw.peak_flops(FakeTpu()) == 197e12
    assert hw.peak_membw(FakeTpu()) == 819e9
    FakeTpu.device_kind = "TPU v5"          # no catch-all row any more
    with pytest.raises(ValueError):
        hw.peak_membw(FakeTpu())


def test_cli_exits_nonzero_when_frames_were_dropped(tmp_path):
    """`python -m nnstreamer_tpu` used to return 1 only for bus errors;
    a run that dropped every frame on invoke errors did not succeed."""
    bad = tmp_path / "bad_model.py"
    bad.write_text(
        "from nnstreamer_tpu.tensors.info import TensorsInfo\n"
        "def get_model():\n"
        "    return (lambda p, x: x.reshape(7, 7), None, None,\n"
        "            TensorsInfo.make('float32', '7:7'))\n")
    r = _run(["-m", "nnstreamer_tpu",
              'tensortestsrc caps="other/tensors,format=static,'
              'num_tensors=1,types=(string)float32,dimensions=(string)8" '
              f"num-buffers=3 ! tensor_filter framework=jax model={bad} "
              "! fakesink"])
    assert r.returncode == 1
    assert "3 invoke error(s)" in r.stderr
