"""Roofline share of the routed experts' grouped SwiGLU kernel
``nns_grouped_swiglu`` (``nnstreamer_tpu/ops/grouped.py`` when a chip
holds the whole router): the least time the chip could take over the
routed experts of the whole programs in the traced stretch
(``nnsbench/costs_<family>.py::grouped_floor_s(sizes, tokens, peaks)``,
one sequence's seconds: an expert layer's larger of the served pairs'
three products over the peak bf16 rate and of each held expert's
weights once plus a row in and a row out a pair over the memory's rate,
the floor ``kernel.ragged_dot.roofline_pct`` divides by) over the
device time of the events named or scoped ``nns_grouped_swiglu*``
inside those programs. It counts served pairs, not the rows of the
tiles a step multiplies, so a kernel that multiplies rows it masks
reads lower, and none can read over 100. The rows' way in and out
(gathers, the weighted sum) is not in the events' time: the kernel's
share, not the layer's. None where the trace holds no such event (the
tile-loop form, ``ragged_dot``, any other program) or the family's cost
module has no such floor.

Entry in BENCHMARK.json: unit %, better higher, source device_trace,
layer "kernels", moves ``frames_per_s``."""
import importlib

from nnsbench import progtrace
from nnsbench.traceread import op_kind

KERNEL = "nns_grouped_swiglu"


def read(run):
    prog = progtrace.of_run(run)
    if prog is None or run["peaks"] is None:
        return None
    try:
        floor_s = importlib.import_module(
            "nnsbench.costs_" + run["config"]["family"]).grouped_floor_s
    except (ImportError, AttributeError):
        return None
    whole = [(lo, hi) for name, lo, hi in prog.modules
             if name.startswith("jit_nns_filter_") and prog.inside(lo, hi)]
    spent = sum(b - a for name, a, b, scope in prog.ops
                if (op_kind(name).startswith(KERNEL) or KERNEL in scope)
                and any(lo <= a and b <= hi for lo, hi in whole))
    if len(whole) < progtrace.MIN_SPANS or not spent:
        return None
    floor = floor_s({**run["config"], **run["sizes"]},
                    int(run["traffic"]["tokens_per_buffer"]), run["peaks"])
    return 100.0 * len(whole) * floor * 1e9 / spent
