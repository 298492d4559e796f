"""Host time inside d2h transfer calls for each buffer completed,
milliseconds: the time of the traced stretch covered by the program's
``nns.transfer.fetch`` spans (the fetch coalescer's batched
``device_get``), over the buffers the filter completed in the stretch
(its ``nns.filter.complete`` spans). It is the calling thread's time in the
call, not the bytes' time on the link: the runtime's own transfer
threads work on after the call returns.

Entry in BENCHMARK.json: unit ms, better lower, source
program_counter, layer "transfers", moves ``frames_per_s``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    return prog.busy_ms_per("nns.transfer.fetch", "nns.filter.complete")
