#!/usr/bin/env python3
"""What the TPU compiler thinks a jax-filter program costs, with no chip.

    JAX_PLATFORMS=cpu python tools/aot_estimate.py \\
        'zoo://vit?size=224&patch=14&d_model=1280&layers=32&heads=16' --batch 32

compiles the program ``tensor_filter framework=jax`` would build for
that model (the per-buffer step of filters/prepare.py, reading what the
load computed; ``--as-loaded`` compiles the whole trace on the leaves
as the model hands them over) for a described topology, and prints the
scheduled HLO's fusions by name with the compiler's
``estimated_cycles``, then its stand-alone ``copy`` and ``pad``
instructions with their shapes (the compiler estimates no cycles for
those). A model's Pallas kernels are compiled by Mosaic, as on the
chip, not through the interpreter this process' CPU backend would
pick. An estimate sizes a change to the device program before a chip
run; it is not a measurement (PERF.md §6, PR 27, holds one estimate
beside its trace).
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import functools
import os
import re

_FUSION = re.compile(r"^\s*%?([\w.-]+?)(?:\.\d+)? = .*\bfusion\(.*"
                     r'"estimated_cycles":"(\d+)"', re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = (\S+) ([\w-]+)\("
                          r"%?([\w.-]+)?")


@contextlib.contextmanager
def mosaic_kernels():
    """While a model is traced here, its attention kernel is built for
    Mosaic, and so is the power retention's: ``blocked_causal_attention``
    and ``power_retention`` ask ``jax.default_backend()``, which is
    ``cpu`` in this process whatever topology is described."""
    from nnstreamer_tpu.ops import power_retention, sparse_attention
    sites = [(sparse_attention, "_attend_block"), (power_retention, "_call")]
    real = [getattr(mod, name) for mod, name in sites]
    for (mod, name), fn in zip(sites, real):
        setattr(mod, name, functools.wraps(fn)(
            lambda *args, interpret, _fn=fn, **kw: _fn(
                *args, interpret=False, **kw)))
    try:
        yield
    finally:
        for (mod, name), fn in zip(sites, real):
            setattr(mod, name, fn)


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
    leaves = jax.tree.leaves(shapes)
    apply_fn = fw._apply
    if fw._state0 is not None:
        # a model that carries a state: its leaves are inputs, before xs
        apply_fn = fw.flat_apply()
        xs = [jax.ShapeDtypeStruct(x.shape, x.dtype)
              for x in jax.tree.leaves(fw._state0)] + xs
    with mosaic_kernels():
        closed, out_tree, cut = prepare.trace(jax.jit(apply_fn), shapes, xs)
    if as_loaded:
        cut = prepare.split(closed, 0)      # no leaf: nothing to the load
        held = leaves
    else:
        held = [leaves[i] for i in cut.kept] \
            + [v.aval for v in cut.load.outvars]
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices[0])
    held, xs = ([jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev)
                 for x in part] for part in (held, xs))
    fn = named_program("nns_filter_" + fw._model_stem,
                       prepare.program(closed, out_tree, cut))
    return jax.jit(fn).lower(held, *xs).compile().as_text()


def fusion_cycles(text: str) -> dict:
    """``{fusion name without its number: (count, summed cycles)}``."""
    out = collections.defaultdict(lambda: [0, 0])
    for name, cycles in _FUSION.findall(text):
        out[name][0] += 1
        out[name][1] += int(cycles)
    return {k: tuple(v) for k, v in out.items()}


def plain_moves(text: str) -> dict:
    """``{(copy | pad, operand shape, result shape): count}`` of the
    instructions that stand alone in the schedule: in the entry
    computation or a loop's body, not inside a fusion. Each is a pass
    over HBM, and the compiler estimates no cycles for them."""
    fused = set(re.findall(r"\bcalls=%?([\w.-]+)", text))
    moves, shapes, inside = collections.Counter(), {}, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside, shapes = head.group(1), {}
            continue
        found = _INSTRUCTION.match(line)
        if not found or inside in fused:
            continue
        name, shape, op, operand = found.groups()
        shapes[name] = shape
        if op in ("copy", "pad"):
            moves[op, shapes.get(operand, "?"), shape] += 1
    return dict(moves)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("model", help="zoo://... or a get_model() file")
    ap.add_argument("--batch", type=int, default=0,
                    help="leading batch dimension (0: per-frame shapes)")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--as-loaded", action="store_true",
                    help="the whole trace, nothing computed at the load "
                    "(filters/prepare.py)")
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
    for (op, arg, out), n in sorted(plain_moves(text).items()):
        print(f"{op:5s} x{n:<4d} {arg} -> {out}")
    return 0


if __name__ == "__main__":
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    raise SystemExit(main())
