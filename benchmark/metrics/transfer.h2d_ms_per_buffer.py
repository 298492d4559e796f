"""Host time inside h2d transfer calls for each buffer completed,
milliseconds: the time of the traced stretch covered by the program's
``nns.transfer.upload`` spans (the jax backend's staging ``device_put``
in the filter's dispatch, and the upload coalescer's batched call), over
the buffers the filter completed in the stretch (its
``nns.filter.complete`` spans). It is the calling thread's time in the
call, not the bytes' time on the link: the runtime's own transfer
threads work on after the call returns.

Entry in BENCHMARK.json: unit ms, better lower, source
program_counter, layer "transfers", moves ``frames_per_s``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    return prog.busy_ms_per("nns.transfer.upload", "nns.filter.complete")
