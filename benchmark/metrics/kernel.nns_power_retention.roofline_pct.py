"""Roofline share of the chunked power retention ``nns_power_retention``
(``nnstreamer_tpu/ops/power_retention.py``): the least time the chip
could take over the retentions of the whole programs in the traced
stretch (``nnsbench/costs_brumby.py::retention_floor_s(sizes, tokens,
peaks)``, one buffer's seconds: a layer's larger of the retention's
operations as the token-by-token form states them at ``phi``'s 8256
rows over the peak bf16 rate and of q, k, v and the log-gates in, o out
and the carried state read and written once over the memory's rate; the
operations' is the larger at the cell's sizes) over the device time of
the events named or scoped ``nns_power_retention*`` inside those
programs. It counts what the retention has to do, not what a chunked
form or a padded layout multiplies besides, so none can read over 100.
None where the trace holds no such event or the family's cost module
has no such floor.

Entry in BENCHMARK.json: unit %, better higher, source device_trace,
layer "kernels", moves ``frames_per_s``."""
import importlib

from nnsbench import progtrace
from nnsbench.traceread import op_kind

KERNEL = "nns_power_retention"


def read(run):
    prog = progtrace.of_run(run)
    if prog is None or run["peaks"] is None:
        return None
    try:
        floor_s = importlib.import_module(
            "nnsbench.costs_" + run["config"]["family"]).retention_floor_s
    except (ImportError, AttributeError):
        return None
    whole = [(lo, hi) for name, lo, hi in prog.modules
             if name.startswith("jit_nns_filter_") and prog.inside(lo, hi)]
    spent = sum(b - a for name, a, b, scope in prog.ops
                if (op_kind(name).startswith(KERNEL) or KERNEL in scope)
                and any(lo <= a and b <= hi for lo, hi in whole))
    if len(whole) < progtrace.MIN_SPANS or not spent:
        return None
    floor = floor_s(run["sizes"], int(run["traffic"]["tokens_per_buffer"]),
                    run["peaks"])
    return 100.0 * len(whole) * floor * 1e9 / spent
