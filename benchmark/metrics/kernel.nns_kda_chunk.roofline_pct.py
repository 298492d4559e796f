"""Roofline share of the chunked gated delta rule ``nns_kda_chunk``
(``nnstreamer_tpu/ops/kda.py``): the least time the chip could take over
the state recurrences of the whole programs in the traced stretch
(``nnsbench/costs_kimi_linear.py::kda_floor_s(sizes, tokens, peaks)``,
one sequence's seconds: a KDA layer's larger of the recurrence's
operations as the token-by-token form states them over the peak bf16
rate and of q, k, v, the float32 log-decays and beta in and o out once
over the memory's rate; the memory's is the larger at the cell's sizes)
over the device time of the events named or scoped ``nns_kda_chunk*``
inside those programs (the operations of the loop over the chunks carry
the scope; the loop's own unscoped ``while`` wrapper, which spans them,
does not and is not counted twice). It counts what the recurrence has to
move, not what a chunked form materialises between its products, so a
form that writes its intermediates to memory reads lower, and none can
read over 100. None where the trace holds no such event or the family's
cost module has no such floor.

Entry in BENCHMARK.json: unit %, better higher, source device_trace,
layer "kernels", moves ``frames_per_s``."""
import importlib

from nnsbench import progtrace
from nnsbench.traceread import op_kind

KERNEL = "nns_kda_chunk"


def read(run):
    prog = progtrace.of_run(run)
    if prog is None or run["peaks"] is None:
        return None
    try:
        floor_s = importlib.import_module(
            "nnsbench.costs_" + run["config"]["family"]).kda_floor_s
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
