"""Share of the traced stretch of the window in which no operation ran
on the device: 1 - union of the device-op intervals of the profiler's
trace over the traced seconds (``nnsbench/traceread.py``).

Entries in BENCHMARK.json, one for each end-to-end metric it moves:
``device.idle_pct.vision`` (moves ``frames_per_s``) and
``device.idle_pct.gen`` (moves ``tokens_per_s``); unit %, better lower,
source device_trace, layer "device"."""
from nnsbench import traceread


def read(run):
    return traceread.idle_pct(run["trace"])
