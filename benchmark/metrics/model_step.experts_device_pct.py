"""Share of the device-operation time of the traced stretch spent in the
expert layers, the compiler's unscoped grouped-product kernel included:
the ``XLA Ops`` events of the first device whose scope (the stat
``tf_op``, from the models' ``jax.named_scope``s) holds ``block/moe``
(router, shared expert, the rows' way in and out of the routed experts'
product), and the events named ``ragged-dot*`` inside the programs
``jit_nns_filter_*`` (what the TPU compiler makes of ``jax.lax.
ragged_dot``: it names the kernel itself and hands no scope on, so
``model_step.moe_device_pct`` leaves those events out), over all
operations. Where a program has no such kernel this is
``model_step.moe_device_pct``. None where no operation carries the
scope.

Entry in BENCHMARK.json: unit %, better lower, source device_trace,
layer "model step", moves ``frames_per_s``."""
from nnsbench import progtrace
from nnsbench.traceread import op_kind

SCOPE, KERNEL = "block/moe", "ragged-dot"


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    lo, hi = prog.window
    programs = [(a, b) for name, a, b in prog.modules
                if name.startswith("jit_nns_filter_")]
    total = scoped = hit = 0
    for name, a, b, scope in prog.ops:
        d = min(b, hi) - max(a, lo)
        if d <= 0:
            continue
        total += d
        if SCOPE in scope:
            scoped += d
            hit += d
        elif op_kind(name).startswith(KERNEL) and any(
                p <= a and b <= q for p, q in programs):
            hit += d
    return 100.0 * hit / total if scoped else None
