"""Share of the device-operation time of the traced stretch spent in the
sparse-attention indexer (its projections, the index scores, and the
exact top-k selection, ``block/indexer/select`` inside it): the ``XLA
Ops`` events of the first device whose scope (the stat ``tf_op``, from
the models' ``jax.named_scope``s) holds ``block/indexer``, over all of
them. Operations the compiler leaves without a scope count in the whole
only. None where no operation carries the scope (a program without it).

Entry in BENCHMARK.json: unit %, better lower, source device_trace,
layer "model step", moves ``frames_per_s``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    share = prog.scope_share("block/indexer")
    return None if share is None else 100.0 * share
