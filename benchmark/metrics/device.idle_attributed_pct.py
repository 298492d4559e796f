"""Share of the first device's idle time in the traced stretch that is
charged to one of the program's own ``nns.*`` spans
(``progtrace.idle_gaps``: each gap between device operations goes to the
innermost such span covering at least half of it, else to a harness
``bench.*`` span, else to ``unattributed``). Says how much of the idle
time the trace can name; ``info``-level detail is ``progtrace.idle_gaps``.

Entry for BENCHMARK.json (the generation cell is not listed yet): unit
%, better higher, source device_trace, layer "device", moves
``tokens_per_s``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    gaps = prog.idle_gaps()
    idle = sum(gaps.values())
    ours = sum(v for k, v in gaps.items()
               if k.startswith(progtrace.PROGRAM_PREFIX))
    if idle <= 0 or not ours:
        return None
    return 100.0 * ours / idle
