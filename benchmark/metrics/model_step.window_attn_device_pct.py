"""Share of the device-operation time of the traced stretch spent in the
sliding-window attention layers (norms, projections, rotation, the
kernel over the window's key tiles, gate, output projection): the ``XLA
Ops`` events of the first device whose scope (the stat ``tf_op``, from
the model's ``jax.named_scope``s) holds ``block/attn/window``, over all
of them. ``model_step.attn_device_pct`` holds these layers and the full
ones together. None where no operation carries the scope (a program
without window layers).

Entry in BENCHMARK.json: unit %, better lower, source device_trace,
layer "model step", moves ``frames_per_s``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    share = prog.scope_share("block/attn/window")
    return None if share is None else 100.0 * share
