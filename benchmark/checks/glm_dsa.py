"""The ``glm_dsa`` family's comparison (a configuration names it under
``family``; run.py loads ``checks/<family>.py`` and calls ``check``)."""
from __future__ import annotations

import math

import numpy as np

from nnsbench.compare import Rows


class _L1:
    """``sum |got - ref|`` over ``sum |ref|``, gathered tensor by tensor."""

    def __init__(self):
        self.err = self.ref = 0.0

    def add(self, got, ref):
        if np.shape(got) != ref.shape or not np.isfinite(got).all():
            self.err = math.inf
            return
        self.err += float(np.abs(np.asarray(got, np.float64) - ref).sum())
        self.ref += float(np.abs(ref).sum())

    @property
    def value(self):
        return self.err / self.ref if self.ref else math.inf


def check(driver, results, ctx, limits, control=None):
    """Every buffer of the window that carries one of the sampled
    sequences, each of its three tensors against the reference's for
    that sequence: ``logit_rms`` / ``logit_gap`` on the last position's
    logits (as the ``vit`` family's), ``logprob_rms`` on the per-token
    log-probabilities (rms of the difference over the rms of the
    reference about its mean; the last position is 0 on both sides),
    ``load_l1`` on the expert layers' load (sum |served - ref| over sum
    ref). A control (``fp8``, ``fp8_e5m2``; ``bf16_select``: the
    reference with only its discrete choices made from bfloat16 scores,
    a diagnostic) stands in the program's place."""
    from refs import glm_dsa
    picked, seqs = driver.check_inputs()
    ref = {i: glm_dsa.forward(ctx.session.weights, seq, ctx.sizes, "f32")
           for i, seq in zip(picked, seqs)}
    logits, logprobs, load = Rows(), Rows(), _L1()

    def add(out, i):
        if len(out) != 3 or np.ndim(out[0]) != 1:
            logits.bad()
            return
        logits.add(out[0], ref[i][0])
        logprobs.add(out[1][:-1], ref[i][1][:-1])
        load.add(out[2], ref[i][2])

    if control:
        how = {"precision": control}
        if control == "bf16_select":
            import jax.numpy as jnp
            how = {"select_dtype": jnp.bfloat16}
        for i, seq in zip(picked, seqs):
            add(glm_dsa.forward(ctx.session.weights, seq, ctx.sizes, **how), i)
    else:
        for i, out in results["answers"]:
            if i in ref:
                add(out, i)
    read = {"logit_rms": logits.rms,
            "logit_gap": logits.gap if logits.rows else math.inf,
            "logprob_rms": logprobs.rms, "load_l1": load.value}
    return ({k: {"value": read[k], "limit": limits[k]} for k in limits},
            {"buffers_compared": logits.rows,
             "sequences_referenced": len(picked), "read": read})
