"""Share of the device-operation time of the traced stretch spent in the
gated delta-rule (KDA) mixers whole (norm, projections, convolutions,
gates, the chunked recurrence, the gated norm, the output projection):
the ``XLA Ops`` events of the first device whose scope (the stat
``tf_op``, from the model's ``jax.named_scope``s) holds
``block/attn/kda``, over all of them. ``model_step.attn_device_pct``
holds these layers and the latent-attention ones together. None where
no operation carries the scope (a program without such layers).

Entry in BENCHMARK.json: unit %, better lower, source device_trace,
layer "model step", moves ``frames_per_s``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    share = prog.scope_share("block/attn/kda")
    return None if share is None else 100.0 * share
