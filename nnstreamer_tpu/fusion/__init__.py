"""Device-resident pipeline compiler: fuse element chains into one
XLA program.

Every element boundary that leaves the device costs a D2H, a host hop
and an H2D per frame, whatever the compute between them. This
package promotes pipelint's static transfer pass into a placement IR:
after parse and validation, but before start, the planner walks the
graph, marks maximal runs of device-capable elements (those whose
:meth:`Element.device_fn` yields a pure traceable program), and
replaces each run's dataflow with a single :class:`FusedSegment` whose
body composes the member programs into one cached ``jax.jit`` — so
activations stay HBM-resident and each frame crosses the link once in,
once out.

The per-element chain path stays intact: it is the opt-out fallback
(``fuse=false`` pipeline prop, ``pipeline.fuse = False``) and the
parity oracle — a fused pipeline must produce byte-identical tensors
to the unfused chain on the CPU backend (``make fuse-parity``).

See Documentation/fusion.md for the planner rules and the ``device_fn``
contract.
"""
from .planner import (FusionCtx, FusionPlan, PlannedSegment,  # noqa: F401
                      fuse_pipeline, plan_fusion, static_veto)
from .segment import FusedSegment  # noqa: F401

__all__ = [
    "FusionCtx", "FusionPlan", "PlannedSegment", "FusedSegment",
    "fuse_pipeline", "plan_fusion", "static_veto",
]
