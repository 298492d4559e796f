#!/usr/bin/env python3
"""What the TPU compiler thinks a jax-filter program costs, with no chip.

    JAX_PLATFORMS=cpu python tools/aot_estimate.py \\
        'zoo://vit?size=224&patch=14&d_model=1280&layers=32&heads=16' --batch 32

compiles the program ``tensor_filter framework=jax`` would build for
that model (filters/prepare.py's narrowing included; ``--as-loaded``
leaves the leaves as the model hands them over) for a described
topology, and prints the scheduled HLO's fusions by name with the
compiler's ``estimated_cycles``. An estimate sizes a change to the
device program before a chip run; it is not a measurement (PERF.md §6,
PR 27, holds one estimate beside its trace).
"""
from __future__ import annotations

import argparse
import collections
import os
import re

_FUSION = re.compile(r"^\s*%?([\w.-]+?)(?:\.\d+)? = .*\bfusion\(.*"
                     r'"estimated_cycles":"(\d+)"', re.M)


def compile_text(model: str, batch: int, topology: str,
                 as_loaded: bool = False) -> str:
    """The compiled (scheduled) HLO text of the filter's program for
    ``model`` on one device of ``topology``. Nothing is initialised or
    placed: parameters and inputs are shapes."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from nnstreamer_tpu.filters import prepare
    from nnstreamer_tpu.filters.jax_backend import JaxFilter
    from nnstreamer_tpu.obs.spans import named_program

    fw = JaxFilter()
    # the loader runs under eval_shape, so a zoo model's random init is
    # traced, never computed; apply_fn and the tensor info land on fw
    shapes = jax.eval_shape(lambda: fw._load_model(model, None) or fw._params)
    xs = [jax.ShapeDtypeStruct(((batch,) if batch else ()) + tuple(i.shape),
                               i.type.np_dtype) for i in fw._in_info]
    closed, out_tree, narrow = prepare.trace(jax.jit(fw._apply), shapes, xs)
    if as_loaded:
        narrow = {}
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices[0])
    leaves, treedef = jax.tree.flatten(shapes)
    tree = treedef.unflatten([jax.ShapeDtypeStruct(
        x.shape, narrow.get(i, x.dtype), sharding=dev)
        for i, x in enumerate(leaves)])
    xs = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev) for x in xs]
    fn = named_program("nns_filter_" + fw._model_stem,
                       prepare.program(closed, out_tree, narrow))
    return jax.jit(fn).lower(tree, *xs).compile().as_text()


def fusion_cycles(text: str) -> dict:
    """``{fusion name without its number: (count, summed cycles)}``."""
    out = collections.defaultdict(lambda: [0, 0])
    for name, cycles in _FUSION.findall(text):
        out[name][0] += 1
        out[name][1] += int(cycles)
    return {k: tuple(v) for k, v in out.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("model", help="zoo://... or a get_model() file")
    ap.add_argument("--batch", type=int, default=0,
                    help="leading batch dimension (0: per-frame shapes)")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--as-loaded", action="store_true",
                    help="do not narrow the leaves (filters/prepare.py)")
    ap.add_argument("--text", help="also write the compiled HLO here")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    text = compile_text(args.model, args.batch, args.topology,
                        args.as_loaded)
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)
    rows = fusion_cycles(text)
    total = sum(c for _, c in rows.values())
    for name, (n, c) in sorted(rows.items(), key=lambda r: -r[1][1]):
        print(f"{name:40s} x{n:<5d} {c:>14d} cycles {100 * c / total:6.2f} %")
    print(f"{'all fusions':40s} x{sum(n for n, _ in rows.values()):<5d} "
          f"{total:>14d} cycles")
    return 0


if __name__ == "__main__":
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    raise SystemExit(main())
