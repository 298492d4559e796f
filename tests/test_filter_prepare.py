"""The jax filter converts a parameter leaf to its compute dtype once
per load when every use of it in the traced program is that conversion
(filters/prepare.py): same bits out, one shared copy, redone whenever
the parameters are replaced, and nothing at all where no leaf qualifies.
"""
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.filters import FilterProperties, find_filter, prepare
from nnstreamer_tpu.filters.base import FilterEvent
from nnstreamer_tpu.obs.spans import named_program

VIT = "zoo://vit?size=32&patch=8&d_model=64&layers=2&heads=4&classes=10"
VIT_CAPS = ("other/tensors,format=static,num_tensors=1,"
            "types=(string)uint8,dimensions=(string)3:32:32:4,framerate=0/1")


def _open(model, custom=""):
    fw = find_filter("jax")()
    fw.open(FilterProperties(framework="jax", model_files=(model,),
                             custom_properties=custom))
    return fw


def _frames(batch, seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, (batch, 32, 32, 3), np.uint8, endpoint=True)


@pytest.fixture
def conversions(monkeypatch):
    """Counts the runs of the converting program."""
    calls = []
    real = prepare.convert

    def convert(leaves, dtypes):
        calls.append(len(leaves))
        return real(leaves, dtypes)

    monkeypatch.setattr(prepare, "convert", convert)
    return calls


def _model_file(tmp_path, body):
    path = tmp_path / "model.py"
    path.write_text(textwrap.dedent(body))
    return str(path)


# a float32 tree in which `k`'s every use is the conversion, and `w` is
# also read in float32
TWO_USES = """
    import jax.numpy as jnp
    import numpy as np
    from nnstreamer_tpu.tensors.info import TensorsInfo

    def get_model():
        rng = np.random.default_rng(0)
        params = {"k": rng.standard_normal((16, 8)).astype(np.float32),
                  "w": rng.standard_normal((8, 8)).astype(np.float32)}

        def apply_fn(p, x):
            h = x.astype(jnp.bfloat16) @ p["k"].astype(jnp.bfloat16)
            h = h @ p["w"].astype(jnp.bfloat16)
            return h.astype(jnp.float32) + p["w"].sum()

        return (apply_fn, params, TensorsInfo.make("float32", "16"),
                TensorsInfo.make("float32", "8"))
"""

# names the `gpt` rule table shards over the model axis
SHARDED = """
    import jax.numpy as jnp
    import numpy as np
    from nnstreamer_tpu.tensors.info import TensorsInfo

    def get_model():
        rng = np.random.default_rng(0)
        params = {"w1": rng.standard_normal((64, 128)).astype(np.float32),
                  "w2": rng.standard_normal((128, 16)).astype(np.float32),
                  "scale": np.ones((16,), np.float32)}

        def apply_fn(p, x):
            h = x.astype(jnp.bfloat16) @ p["w1"].astype(jnp.bfloat16)
            h = h @ p["w2"].astype(jnp.bfloat16)
            return h.astype(jnp.float32) * p["scale"]

        return (apply_fn, params, TensorsInfo.make("float32", "64"),
                TensorsInfo.make("float32", "16"))
"""


@pytest.mark.parametrize("window", ["", "in-flight=2 prefetch-host=true"],
                         ids=["sync", "windowed"])
def test_pipeline_logits_bit_identical(monkeypatch, window):
    """(a) a float32-leaves model through a real pipeline: the same
    bytes with the leaves converted once and with the leaves left alone,
    and the copy is the kernels' half."""
    def run():
        p = parse_launch(
            f'appsrc name=in caps="{VIT_CAPS}" ! tensor_filter name=f '
            f'framework=jax model={VIT} {window} ! appsink name=out')
        p.start()
        for i in range(3):
            p["in"].push_buffer(Buffer.from_arrays([_frames(4, i)]))
        p["in"].end_stream()
        assert p.wait_eos(timeout=120)
        rep = p["f"].transfer_report()
        kernels = sum(x.nbytes for path, x in
                      jax.tree_util.tree_leaves_with_path(p["f"].fw._params)
                      if "kernel" in jax.tree_util.keystr(path)
                      and "EncoderBlock" in jax.tree_util.keystr(path))
        out = [np.asarray(b.chunks[0].host()).tobytes()
               for b in p["out"].buffers]
        p.stop()
        return out, rep, kernels

    got, rep, kernels = run()
    assert rep["prepared_leaves"] > 0
    assert rep["prepared_bytes"] > 0.95 * kernels / 2
    monkeypatch.setattr(prepare, "narrowable", lambda closed, n: {})
    want, rep0, _ = run()
    assert rep0.get("prepared_leaves", 0) == 0
    assert rep0.get("prepared_bytes", 0) == 0
    assert len(got) == 3 and got == want


@pytest.mark.parametrize("model,shape", [
    ("zoo://mlp?dtype=float32", (4, 64)),     # used in float32
    ("zoo://mlp", (4, 64)),                   # already bfloat16
    ("zoo://toyseg", (8, 8)),                 # no conversion at all
])
def test_nothing_to_convert_same_program_same_arrays(conversions, model,
                                                     shape):
    """(b) no leaf qualifies: the program is ``jax.jit`` of ``apply_fn``
    as before and it is handed the very arrays that were loaded."""
    fw = _open(model)
    loaded = jax.tree.leaves(fw._params)
    x = np.random.default_rng(0).random(shape, np.float32)
    fw.invoke([x])
    assert fw.prepared_report() == {"prepared_leaves": 0,
                                    "prepared_bytes": 0,
                                    "kernel_calls": {}}
    assert conversions == [] and fw._prepared is None
    exe, = fw._jit_cache.values()
    assert not fw._on_prepared
    assert all(a is b for a, b in zip(jax.tree.leaves(fw._params), loaded))
    plain = jax.jit(named_program("nns_filter_" + fw._model_stem, fw._apply))
    assert exe.lower(fw._params, x).as_text() \
        == plain.lower(fw._params, x).as_text()
    fw.close()


@pytest.mark.parametrize("model,custom", [
    (VIT, ""), (VIT, "mesh:8x1x1"),
    ("zoo://mlp?dtype=float32", ""),
    ("zoo://mlp?dtype=float32", "mesh:4x1x2,rules:gpt"),
], ids=["narrowed", "narrowed-mesh", "plain", "plain-mesh"])
def test_one_python_trace_of_the_model_per_program(model, custom):
    """Reading the leaf set costs no second run of the model's Python:
    the narrowed program is built from the jaxpr, the plain one is the
    ``jax.jit`` that was traced."""
    fw = _open(model, custom)
    runs = []
    apply_fn = fw._apply

    def counting(p, *xs):
        runs.append(1)
        return apply_fn(p, *xs)

    fw._apply = counting
    x = _frames(8) if model is VIT \
        else np.random.default_rng(0).random((8, 64), np.float32)
    fw.invoke([x])
    fw.invoke([x])
    jax.block_until_ready(fw.dispatch([x]))
    assert len(runs) == 1 and len(fw._jit_cache) == 1
    fw.close()


def test_leaf_with_a_float32_use_is_not_converted(tmp_path):
    """(c) one float32 use anywhere keeps the leaf as loaded."""
    fw = _open(_model_file(tmp_path, TWO_USES))
    x = np.random.default_rng(1).random((4, 16), np.float32)
    got = np.asarray(fw.invoke([x])[0])
    leaves = jax.tree_util.tree_leaves_with_path(fw._params)
    names = [jax.tree_util.keystr(p) for p, _ in leaves]
    assert {names[i]: d for i, d in fw._narrow.items()} \
        == {"['k']": jnp.dtype(jnp.bfloat16)}
    assert fw._prepared["w"] is fw._params["w"]
    assert fw._prepared["k"].dtype == jnp.bfloat16
    assert fw.prepared_report() == {"prepared_leaves": 1,
                                    "prepared_bytes": 16 * 8 * 2,
                                    "kernel_calls": {}}
    want = np.asarray(jax.jit(fw._apply)(fw._params, x))
    assert got.tobytes() == want.tobytes()
    fw.close()


@pytest.mark.parametrize("use", ["returned", "sub_program", "two_dtypes",
                                 "wider", "integer"])
def test_narrowable_rejects(use):
    """The rule itself, on programs that must keep their leaf."""
    def f(w, x):
        if use == "returned":
            return x @ w.astype(jnp.bfloat16), w
        if use == "sub_program":
            return jax.jit(lambda a: a.astype(jnp.bfloat16))(w) @ x
        if use == "two_dtypes":
            return x @ w.astype(jnp.bfloat16) \
                + (x @ w.astype(jnp.float16)).astype(jnp.bfloat16)
        return x.astype(w.dtype) @ w.astype(
            jnp.float32 if use == "wider" else jnp.int8)

    w = jnp.ones((4, 4), jnp.bfloat16 if use == "wider" else jnp.float32)
    closed = jax.make_jaxpr(f)(w, jnp.ones((4, 4), jnp.bfloat16))
    assert prepare.narrowable(closed, 1) == {}


def test_signatures_share_one_converted_tree(conversions):
    """(d) the set is the model's, not the input shape's."""
    fw = _open(VIT)
    fw.invoke([_frames(1)])
    tree = fw._prepared
    held = jax.tree.leaves(tree)
    out4 = np.asarray(fw.invoke([_frames(4)])[0])
    assert len(fw._jit_cache) == 2
    assert fw._on_prepared == set(fw._jit_cache)
    assert fw._prepared is tree
    assert all(a is b for a, b in zip(jax.tree.leaves(fw._prepared), held))
    assert conversions == [fw.prepared_report()["prepared_leaves"]]
    want = np.asarray(jax.jit(fw._apply)(fw._params, _frames(4)))
    assert out4.tobytes() == want.tobytes()
    fw.close()


def test_signature_that_disagrees_runs_on_loaded_leaves(monkeypatch,
                                                        conversions):
    """A later program whose trace finds another set must not be handed
    leaves it would read in float32."""
    fw = _open(VIT)
    fw.invoke([_frames(1)])
    real = prepare.narrowable

    def fewer(closed, n):
        out = real(closed, n)
        out.pop(min(out))
        return out

    monkeypatch.setattr(prepare, "narrowable", fewer)
    got = np.asarray(fw.invoke([_frames(4)])[0])
    sig1, = fw._on_prepared
    assert len(fw._jit_cache) == 2 and sig1[0][0][0] == 1
    assert len(conversions) == 1
    want = np.asarray(jax.jit(fw._apply)(fw._params, _frames(4)))
    assert got.tobytes() == want.tobytes()
    fw.close()


@pytest.mark.parametrize("how", ["reload", "suspend"])
def test_replaced_parameters_are_converted_again(conversions, how):
    """(e) a reload serves the new weights and a resume the reloaded
    tree: never the copy made from the parameters that went."""
    fw = _open(VIT)
    x = _frames(4)
    first = np.asarray(fw.invoke([x])[0])
    old = fw._prepared
    if how == "reload":
        assert fw.handle_event(FilterEvent.RELOAD_MODEL,
                               {"model_files": (VIT + "&seed=1",)})
        ref = _open(VIT + "&seed=1")
        want = np.asarray(jax.jit(ref._apply)(ref._params, x))
        ref.close()
        assert want.tobytes() != first.tobytes()
    else:
        assert fw.handle_event(FilterEvent.SUSPEND)
        want = first
    assert fw._prepared is None and fw._jit_cache == {}
    assert fw._on_prepared == set()
    assert fw.prepared_report()["prepared_bytes"] == 0
    got = np.asarray(fw.invoke([x])[0])
    assert got.tobytes() == want.tobytes()
    assert fw._prepared is not old and len(conversions) == 2
    assert fw.prepared_report()["prepared_leaves"] == conversions[0]
    fw.close()
    assert fw._prepared is None and fw._narrow is None


def test_mesh_converted_leaves_keep_their_sharding(tmp_path):
    """(f) on the 8-device mesh a converted leaf lies where its source
    leaf lies, and the sharded program reads the converted tree."""
    model = _model_file(tmp_path, SHARDED)
    x = np.random.default_rng(2).random((8, 64), np.float32)
    one = _open(model)
    want = np.asarray(one.invoke([x])[0])
    one.close()
    fw = _open(model, "mesh:4x1x2,rules:gpt")
    out = fw.invoke([x])[0]
    assert len(out.sharding.device_set) == 8
    assert fw.prepared_report()["prepared_leaves"] == 2
    for name in ("w1", "w2"):
        src, conv = fw._params[name], fw._prepared[name]
        assert conv.dtype == jnp.bfloat16
        assert conv.sharding == src.sharding
        assert not src.sharding.is_fully_replicated
    assert fw._prepared["scale"] is fw._params["scale"]
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-2, atol=2e-2)
    fw.close()


def test_fused_segment_closure_reads_the_converted_tree(conversions):
    """``traceable_fn`` inlines the narrowed program from one trace of
    the model, on the tree the filter's own programs share; planned
    before a reload it keeps serving the parameters it was planned
    with."""
    fw = _open(VIT)
    traces = []
    apply_fn = fw._apply

    def counting(p, *xs):
        traces.append(1)
        return apply_fn(p, *xs)

    fw._apply = counting
    x = _frames(4)
    fn = fw.traceable_fn()
    fused = jax.jit(fn)
    got = np.asarray(fused(x))
    assert len(traces) == 1 and len(conversions) == 1
    consts = jax.make_jaxpr(fn)(x).consts
    assert sum(c.dtype == jnp.bfloat16 for c in consts) \
        == fw.prepared_report()["prepared_leaves"]
    assert np.asarray(fw.invoke([x])[0]).tobytes() == got.tobytes()
    assert len(conversions) == 1          # the filter's program shares it
    assert fw.handle_event(FilterEvent.RELOAD_MODEL,
                           {"model_files": (VIT + "&seed=1",)})
    again = np.asarray(jax.jit(fn)(x))    # a new trace, after the reload
    assert again.tobytes() == got.tobytes()
    assert len(conversions) == 1
    fw.close()


def test_prepare_span_is_recorded_once_per_load():
    from nnstreamer_tpu.obs import spans
    spans.clear()
    fw = _open(VIT)
    fw.invoke([_frames(1)])
    fw.invoke([_frames(4)])
    rows = [s for _, s in spans.snapshot() if s[0] == "nns.filter.prepare"]
    assert len(rows) == 1 and rows[0][1] == "filter"
    fw.close()


# -- tools/aot_estimate.py ------------------------------------------------

@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    try:
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return "v5e:2x2"


def test_aot_estimate_smoke(topology):
    """The TPU compiler takes the narrowed program, and it holds no
    conversion of a float32 kernel that the program as loaded has."""
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "aot_estimate.py"
    spec = importlib.util.spec_from_file_location("aot_estimate", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    model = "zoo://vit?size=32&patch=8&d_model=128&layers=2&heads=4&classes=10"
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        narrowed = tool.compile_text(model, 4, topology)
        loaded = tool.compile_text(model, 4, topology, as_loaded=True)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "f32[128,512]" in loaded and "f32[128,512]" not in narrowed
    rows = tool.fusion_cycles(narrowed)
    assert rows and all(n > 0 and c > 0 for n, c in rows.values())
    assert sum(c for _, c in rows.values()) \
        < sum(c for _, c in tool.fusion_cycles(loaded).values())


@pytest.mark.parametrize("lo,hi,masked,dv", [(3584, 4096, True, 256),
                                             (1536, 2048, False, 256),
                                             (3584, 4096, False, 128)],
                         ids=["masked", "causal", "causal_v128"])
def test_mosaic_takes_the_masked_attention_kernel(topology, lo, hi, masked,
                                                  dv):
    """What the interpreter cannot show: the TPU's compiler lays out
    ``nns_masked_attention`` at the lm cells' widths (64 heads, S =
    4096, the module's own tiles; keys of 256 lanes with values of 256,
    GLM-5's, or of 128, LongCat's 192 | 128 padded) within the VMEM it
    asks for."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from nnstreamer_tpu.ops import sparse_attention as sa
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices[0])

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    def block(q, k, v, keep, out):
        return sa._attend_block(q, k, v, keep if masked else None, out,
                                lo=lo, hi=hi, tq=sa.TILE_Q, tk=sa.TILE_K,
                                scale=1 / 16, interpret=False)

    heads, values = spec((64, 4096, 256)), spec((64, 4096, dv))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        # conftest pins float32 products for the CPU's sake; the chip
        # multiplies bfloat16 operands as they are
        with jax.default_matmul_precision("default"):
            text = jax.jit(block).lower(
                heads, heads, values, spec((hi - lo, hi), jnp.int8), values
            ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "tpu_custom_call" in text and "nns_masked_attention" in text
