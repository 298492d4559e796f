"""Device time of one buffer's program, milliseconds: mean duration of
the ``XLA Modules`` events named ``jit_nns_filter_*`` (the jax filter's
jitted model, ``filters/jax_backend.py``) that ran wholly inside the
traced stretch, on the first device.

Entry in BENCHMARK.json: unit ms, better lower, source device_trace,
layer "model step", moves ``frames_per_s``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    return progtrace.mean_ms(prog.module_ns("jit_nns_filter_"))
