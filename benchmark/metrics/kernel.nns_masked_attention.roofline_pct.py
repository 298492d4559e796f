"""Roofline share of the attention kernel ``nns_masked_attention``
(``nnstreamer_tpu/ops/sparse_attention.py``): the least time the chip
could take over the attention of the whole programs in the traced
stretch over the device time of the kernel's events inside those
programs. The floor is the configuration's family's:
``nnsbench/costs_<family>.py::attention_floor_s(sizes, tokens, peaks)``,
one sequence's seconds (for ``afmoe`` a layer of each kind at a time:
the larger of the kept pairs' ``q.k`` and ``p.v`` operations over the
peak bf16 rate and of q, o and each key/value head's k and v bytes once
over the memory's rate). It counts kept pairs, not visited tiles, so a
kernel that computes pairs it drops reads lower, and none can read over
100. None where the trace holds no such event or the family's cost
module has no such floor (a cell gains this metric by that function and
its name in the entry's ``workloads``).

Entry in BENCHMARK.json: unit %, better higher, source device_trace,
layer "kernels", moves ``frames_per_s``."""
import importlib

from nnsbench import progtrace

KERNEL = "nns_masked_attention"


def read(run):
    prog = progtrace.of_run(run)
    if prog is None or run["peaks"] is None:
        return None
    try:
        floor_s = importlib.import_module(
            "nnsbench.costs_" + run["config"]["family"]).attention_floor_s
    except (ImportError, AttributeError):
        return None
    whole = [(lo, hi) for name, lo, hi in prog.modules
             if name.startswith("jit_nns_filter_") and prog.inside(lo, hi)]
    spent = sum(b - a for name, a, b, scope in prog.ops
                if (name.startswith(KERNEL) or KERNEL in scope)
                and any(lo <= a and b <= hi for lo, hi in whole))
    if len(whole) < progtrace.MIN_SPANS or not spent:
        return None
    floor = floor_s({**run["config"], **run["sizes"]},
                    int(run["traffic"]["tokens_per_buffer"]), run["peaks"])
    return 100.0 * len(whole) * floor * 1e9 / spent
