"""Share of the device-operation time of the traced stretch spent in the
power retention itself (``ops/power_retention.py``: the kernel
``nns_power_retention`` and the few operations around it, the gates'
running sum and a ragged buffer's padding): the ``XLA Ops`` events of
the first device whose scope (the stat ``tf_op``, from
``jax.named_scope``) holds ``nns_power_retention``, over all of them.
``model_step.attn_device_pct`` holds the mixers whole (projections,
head norms, rotation, gate and output projection beside it). None where
no operation carries the scope (a program without such layers).

Entry in BENCHMARK.json: unit %, better lower, source device_trace,
layer "model step", moves ``frames_per_s``."""
from nnsbench import progtrace

SCOPE = "nns_power_retention"


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    share = prog.scope_share(SCOPE)
    return None if share is None else 100.0 * share
