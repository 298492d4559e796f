"""Time a buffer's chain thread is blocked for a slot of the filter's
in-flight window, milliseconds: mean of the program's
``nns.filter.window_wait`` spans that ended in the traced stretch (one a
buffer, zero-length where a slot was free).

Entry in BENCHMARK.json: unit ms, better lower, source
program_counter, layer "tensor_filter + in-flight window", moves
``latency_p95_ms``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    return progtrace.mean_ms(
        s.hi - s.lo for s in prog.waits("nns.filter.window_wait"))
