#!/usr/bin/env python
"""Compile-stability gate: a warmed process must never compile again.

jitcheck's static passes prove the hot path CAN stay on-device; this
gate proves the compile cache actually HOLDS: every builtin corpus
entry runs twice with one shared persistent CompileCache — pass 1 is
the learning pass (signatures recorded, compiles expected), pass 2
builds fresh pipelines against the now-warm registry, and any
frame-path compilation in pass 2 (a filter's ``jit_recompiles`` or a
fused segment's ``jit_misses``) fails the gate. On top of the per-run
check, ``check_against_static`` closes the static↔runtime contract:
observed CompileCache kinds must be a subset of the statically
predicted jit-site kinds, and the vacuous-coverage guard fails the run
if the corpus recorded no signatures at all (a gate that compiled
nothing proved nothing).

Exit status: nonzero on any second-pass compilation, contract breach,
or vacuous coverage.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# env before ANY jax import (transitively via nnstreamer_tpu)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_CAPS_SEG = ("other/tensors,format=static,num_tensors=1,"
             "types=(string)float32,dimensions=(string)8:8,"
             "framerate=(fraction)0/1")
_CAPS_MLP = ("other/tensors,format=static,num_tensors=1,"
             "types=(string)float32,dimensions=(string)64:8,"
             "framerate=(fraction)0/1")

# Elements are NAMED: a fused segment's compile-cache key is built from
# its member names, and auto-generated names come from a process-global
# counter — unnamed, pass 2 could never find pass 1's signatures.
CORPUS = [
    # (label, description, fuse, in_flight)
    ("stability:filter",
     f"tensortestsrc caps={_CAPS_MLP} num-buffers=6 ! "
     "tensor_filter framework=jax model=zoo://mlp?dtype=float32 "
     "name=stab_f0 ! appsink name=stab_out0",
     False, 1),
    ("stability:fused-chain",
     f"tensortestsrc caps={_CAPS_SEG} num-buffers=6 ! "
     "tensor_filter framework=jax model=zoo://toyseg name=stab_f1 ! "
     "tensor_decoder mode=image_segment name=stab_d1 ! "
     "appsink name=stab_out1",
     True, 1),
    ("stability:windowed",
     f"tensortestsrc caps={_CAPS_MLP} num-buffers=6 ! "
     "tensor_filter framework=jax model=zoo://mlp?dtype=float32 "
     "name=stab_f2 ! appsink name=stab_out2",
     False, 4),
]


def _run_once(desc: str, fuse: bool, in_flight: int, timeout: float):
    """Build a FRESH pipeline (cold jit caches — only the installed
    CompileCache persists between passes), run it, snapshot jit stats."""
    from nnstreamer_tpu.analysis.jit.runtime import jit_stat_snapshot
    from nnstreamer_tpu.analysis.rules import kind_of
    from nnstreamer_tpu.pipeline.parser import parse_launch
    pipe = parse_launch(desc)
    pipe.fuse = fuse
    if in_flight > 1:
        for e in pipe.elements.values():
            if kind_of(e) == "tensor_filter":
                e.set_property("in-flight", in_flight)
                e.set_property("reorder", True)
    pipe.run(timeout=timeout)
    return jit_stat_snapshot(pipe)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="per-pipeline-run timeout (s)")
    ap.add_argument("--cache-dir", default="",
                    help="compile-cache root (default: fresh tempdir)")
    opts = ap.parse_args(argv)

    from nnstreamer_tpu.analysis.jit import (CompileEventMonitor,
                                             analyze_paths,
                                             check_against_static,
                                             steady_recompiles)
    from nnstreamer_tpu.fleet import cache as compile_cache

    root = opts.cache_dir or tempfile.mkdtemp(prefix="nns-jitstab-")
    compile_cache.deactivate()
    cc = compile_cache.install(root, export_env=False)
    monitor = CompileEventMonitor().install()

    static = analyze_paths([str(ROOT / "nnstreamer_tpu")])
    print(f"static: {static.jit_sites} jit site(s) in kinds "
          f"{sorted(static.jit_site_kinds)}; {static.hot_sites} hot "
          f"bodies walked")

    failures = []
    total_steady = 0
    for label, desc, fuse, in_flight in CORPUS:
        snap1 = _run_once(desc, fuse, in_flight, opts.timeout)
        monitor.reset()
        snap2 = _run_once(desc, fuse, in_flight, opts.timeout)
        s1, s2 = steady_recompiles(snap1), steady_recompiles(snap2)
        total_steady += s2
        print(f"{label}: pass1 compiles={s1}, pass2 compiles={s2}, "
              f"{monitor.count} compile event(s)")
        if s2:
            detail = {k: v for k, v in snap2.items()
                      if v.get("jit_recompiles") or v.get("jit_misses")}
            failures.append(f"{label}: {s2} second-pass compilation(s) "
                            f"on the frame path: {detail}")

    observed = cc.kinds()
    entries = cc.entry_count()
    print(f"cache: {entries} signature(s) recorded, kinds {observed}")
    if len(CORPUS) < 2 or entries == 0:
        failures.append("vacuous coverage: the corpus recorded no "
                        "compile signatures — the gate proved nothing")
    try:
        check_against_static(static, observed, total_steady)
    except AssertionError as exc:
        failures.append(str(exc))

    if failures:
        print("JIT-STABILITY FAIL")
        for f in failures:
            print(f"  {f}")
        return 1
    print("JIT-STABILITY OK: zero steady-state recompiles; observed "
          f"kinds {observed} ⊆ static {sorted(static.jit_site_kinds)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
