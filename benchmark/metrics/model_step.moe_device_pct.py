"""Share of the device-operation time of the traced stretch spent in the
expert layers (router, shared expert, the grouped product over the
routed experts held: ``block/moe/route``, ``block/moe/shared``,
``block/moe/experts``): the ``XLA Ops`` events of the first device whose
scope (the stat ``tf_op``, from the models' ``jax.named_scope``s) holds
``block/moe``, over all of them. Operations the compiler leaves without
a scope count in the whole only. None where no operation carries the
scope (a program without it).

Entry in BENCHMARK.json: unit %, better lower, source device_trace,
layer "model step", moves ``frames_per_s``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    share = prog.scope_share("block/moe")
    return None if share is None else 100.0 * share
