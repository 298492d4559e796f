#!/usr/bin/env python
"""Sharded-serving parity gate: mesh output must equal single-chip.

Runs every runnable pipeline in the repo's corpus (tests/*.py string
literals + README.md code blocks, extracted by tools/lint_corpus.py)
that declares a ``mesh:DxSxT`` tensor_filter twice — once as authored
(the batch laid out batch-major across the mesh) and once with the mesh
spec stripped from every filter (the single-chip path) — and compares
every sink's output per buffer, per chunk: dtype, shape and raw bytes,
floats that differ held to ``SHARD_RTOL`` (below). A built-in representative suite (batch-major zoo invoke,
elementwise chain, fused mesh segment) always runs, so the gate tests
something even if the extracted corpus yields no mesh pipelines.

Corpus descriptions compare with fusion DISABLED on both sides: XLA's
fusion decisions are float-order-sensitive for matmul chains, so fused
matmul parity is only approximate even without a mesh. The explicit
fused-mesh case in the built-in suite uses the elementwise
toyseg!toyscale oracle chain, which is bit-exact across XLA fusion AND
mesh partitioning. Exit status is nonzero iff any mesh pipeline's
output did not match its single-chip twin's — or if nothing was
compared at all (a vacuous gate is a failing gate).
"""
from __future__ import annotations

import os

# the mesh half needs the 8-virtual-device CPU mesh BEFORE jax loads
# (tests inherit this from conftest.py; this gate runs standalone)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional, Tuple  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from tools.fuse_parity import _bound_sources, _capture_sinks, \
    _runnable  # noqa: E402
from tools.lint_corpus import collect  # noqa: E402

_CAPS_MLP = ("other/tensors,format=static,num_tensors=1,"
             "types=(string)float32,dimensions=(string)64:8,"
             "framerate=(fraction)0/1")
_CAPS_SEG = ("other/tensors,format=static,num_tensors=1,"
             "types=(string)float32,dimensions=(string)8:8,"
             "framerate=(fraction)0/1")

# the always-on representative suite (kept in sync with
# tests/test_mesh_filter.py's parity cases); entries are
# (name, description-with-mesh, fuse)
BUILTIN = [
    ("builtin:mlp-batch-major",
     f"tensortestsrc caps={_CAPS_MLP} num-buffers=4 ! "
     "tensor_filter framework=jax model=zoo://mlp?dtype=float32 "
     "custom=mesh:8x1x1 ! appsink name=out", False),
    ("builtin:elementwise",
     f"tensortestsrc caps={_CAPS_SEG} num-buffers=4 ! "
     "tensor_filter framework=jax model=zoo://toyseg "
     "custom=mesh:8x1x1 ! appsink name=out", False),
    ("builtin:fused-mesh-segment",
     f"tensortestsrc caps={_CAPS_SEG} num-buffers=4 ! "
     "tensor_filter framework=jax model=zoo://toyseg "
     "custom=mesh:8x1x1 ! "
     "tensor_filter framework=jax model=zoo://toyscale "
     "custom=mesh:8x1x1 ! appsink name=out", True),
]


# Across shardings XLA promises the same mathematics, not the same
# bytes: a device that holds one row of the batch multiplies a [1, K]
# row where the single chip multiplies [B, K], and the CPU backend sums
# the K products of the two shapes in different orders (read: 3 float32
# ulps on zoo://mlp?dtype=float32 at one row a device; byte-equal at
# eight rows a device, and always for integer and elementwise outputs).
# So float chunks may differ by this share of the single chip's largest
# value: 84 float32 ulps, room for a sum of 128 products, and 800 times
# tighter than one bfloat16 ulp, so a lower precision, a dropped or a
# shifted row still fails. tests/test_mesh_filter.py
# (test_mesh_filter_window_reports_mesh_devices) holds the same rule.
SHARD_RTOL = 1e-5


def _chunks_match(mesh_chunk: Tuple, chip_chunk: Tuple) -> bool:
    """Two ``(dtype, shape, bytes)`` records of ``_capture_sinks``."""
    if mesh_chunk == chip_chunk:
        return True
    dtype, shape, raw = chip_chunk
    if mesh_chunk[:2] != (dtype, shape) or np.dtype(dtype).kind != "f":
        return False
    got, want = np.frombuffer(mesh_chunk[2], dtype), np.frombuffer(raw, dtype)
    return bool(np.abs(got - want).max() <= SHARD_RTOL * np.abs(want).max())


def _sinks_match(mesh_bufs: Optional[List[Tuple]],
                 chip_bufs: List[Tuple]) -> bool:
    return (mesh_bufs is not None and len(mesh_bufs) == len(chip_bufs)
            and all(len(m) == len(c)
                    and all(map(_chunks_match, m, c))
                    for m, c in zip(mesh_bufs, chip_bufs)))


def _mesh_filters(pipe) -> List:
    from nnstreamer_tpu.analysis.rules import kind_of
    return [e for e in pipe.elements.values()
            if kind_of(e) == "tensor_filter"
            and "mesh:" in str(getattr(e, "custom", "") or "")]


def _strip_mesh(custom: str) -> str:
    return ",".join(p for p in str(custom or "").split(",")
                    if p.strip() and not p.strip().startswith("mesh:"))


def _mesh_devices_needed(pipe) -> int:
    from nnstreamer_tpu.parallel.mesh import spec_dims
    need = 1
    for e in _mesh_filters(pipe):
        for part in str(e.custom).split(","):
            if part.strip().startswith("mesh:"):
                dims = spec_dims(part.strip()[len("mesh:"):])
                if dims:
                    need = max(need, dims[0] * dims[1] * dims[2])
    return need


def _run_variant(desc: str, mesh: bool, fuse: bool, timeout: float):
    """Run the description as authored (mesh=True) or with the mesh
    spec stripped from every filter (mesh=False = single chip). Sinks
    are keyed by parse position + kind: auto-generated names come from
    a process-global counter and would never match across runs."""
    from nnstreamer_tpu.analysis.rules import kind_of
    from nnstreamer_tpu.pipeline.element import SinkElement
    from nnstreamer_tpu.pipeline.parser import parse_launch
    pipe = parse_launch(desc)
    pipe.fuse = fuse
    if not mesh:
        for e in _mesh_filters(pipe):
            e.set_property("custom", _strip_mesh(e.custom))
    _bound_sources(pipe)
    got = _capture_sinks(pipe)
    keys = {name: f"#{i}:{kind_of(e)}" for i, (name, e) in enumerate(
        (n, e) for n, e in pipe.elements.items()
        if isinstance(e, SinkElement))}
    pipe.run(timeout=timeout)
    fused = [e.name for e in pipe.elements.values()
             if getattr(e, "IS_FUSED_SEGMENT", False)]
    return {keys[n]: recs for n, recs in got.items()}, fused


def check_shard_parity(where: str, desc: str, fuse: bool = False,
                       timeout: float = 60.0) -> Tuple[str, str]:
    """-> (status, detail); status in {mesh-ok, no-mesh, skipped, FAIL}."""
    import jax

    from nnstreamer_tpu.analysis import analyze
    from nnstreamer_tpu.pipeline.parser import parse_launch
    try:
        probe = parse_launch(desc)
    except ValueError as exc:
        return "skipped", f"not a pipeline: {exc}"
    reason = _runnable(probe)
    if reason is not None:
        return "skipped", reason
    if not _mesh_filters(probe):
        return "no-mesh", "no tensor_filter declares a mesh spec"
    need = _mesh_devices_needed(probe)
    if jax.device_count() < need:
        # the sharded run would silently degrade to single-chip and the
        # compare would be vacuous — don't count it as coverage
        return "skipped", (f"host has {jax.device_count()} devices, "
                           f"mesh needs {need}")
    if analyze(probe).errors:
        return "skipped", "pipelint rejects it (validation gate)"
    try:
        chip_out, _ = _run_variant(desc, mesh=False, fuse=fuse,
                                   timeout=timeout)
    except Exception as exc:  # noqa: BLE001
        # the pipeline can't run even WITHOUT a mesh: not a sharding
        # defect, no coverage
        return "skipped", f"baseline (single-chip) run crashed: {exc!r}"
    try:
        mesh_out, fused = _run_variant(desc, mesh=True, fuse=fuse,
                                       timeout=timeout)
    except Exception as exc:  # noqa: BLE001
        return "FAIL", f"sharded run crashed: {exc!r}"
    if fuse and not fused:
        return "FAIL", "fused-mesh case did not fuse in the live run"
    for sink in chip_out:
        if not _sinks_match(mesh_out.get(sink), chip_out[sink]):
            na, nb = len(mesh_out.get(sink, [])), len(chip_out[sink])
            return "FAIL", (f"sink {sink!r}: sharded output differs from "
                            f"the single-chip path ({na} vs {nb} buffers)")
    nbuf = sum(len(v) for v in chip_out.values())
    return "mesh-ok", (f"{need} devices"
                       + (f", {len(fused)} fused segment(s)" if fused
                          else "")
                       + f", {nbuf} buffers match")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", help="files to scan (default: "
                    "tests/*.py and README.md)")
    ap.add_argument("-v", "--verbose", action="store_true")
    ap.add_argument("--timeout", type=float, default=60.0)
    opts = ap.parse_args(argv)

    paths = ([Path(p) for p in opts.paths] if opts.paths else
             sorted(ROOT.glob("tests/*.py")) + [ROOT / "README.md"])
    candidates = [(w, d, f) for w, d, f in BUILTIN] + \
        [(w, d, False) for w, d in collect(paths)]

    counts = {"mesh-ok": 0, "no-mesh": 0, "skipped": 0, "FAIL": 0}
    failures: List[str] = []
    seen = set()
    for where, desc, fuse in candidates:
        if desc in seen:
            continue
        seen.add(desc)
        status, detail = check_shard_parity(where, desc, fuse=fuse,
                                            timeout=opts.timeout)
        counts[status] += 1
        if status == "FAIL":
            failures.append(f"{where}: {detail}\n    {desc}")
        if opts.verbose or status == "FAIL":
            print(f"[{status}] {where}: {detail}")
    print(f"shard-parity: {counts['mesh-ok']} pipelines match "
          f"sharded vs single-chip, {counts['no-mesh']} had no mesh, "
          f"{counts['skipped']} skipped, {counts['FAIL']} failures")
    if counts["mesh-ok"] == 0:
        print("shard-parity: BUILTIN suite yielded no coverage — "
              "the gate is vacuous", file=sys.stderr)
        return 1
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
