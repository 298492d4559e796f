"""The ``kimi_linear`` family's comparison (a configuration names it
under ``family``; run.py loads ``checks/<family>.py`` and calls
``check``)."""
from __future__ import annotations

import math

import numpy as np

from checks.glm_dsa import _L1
from nnsbench.compare import Rows


def check(driver, results, ctx, limits, control=None):
    """Every buffer of the window that carries one of the sampled
    sequences, each of its three tensors against the reference's for
    that sequence, as the ``glm_dsa`` and ``afmoe`` families compare
    them: ``logit_rms`` / ``logit_gap`` on the last position's logits,
    ``logprob_rms`` on the per-token log-probabilities, ``load_l1`` on
    the expert layers' load over every held expert (sum |served - ref|
    over sum ref). The reference runs the KDA layers token by token
    (``refs/kimi_linear.py``). A control (``fp8``, ``fp8_e5m2``) stands
    in the program's place."""
    from refs import kimi_linear
    # Ctx.sizes keeps numbers only; the rest of the configuration file
    # goes along (the reference reads numbers alone)
    sizes = {**ctx.config, **ctx.sizes}
    picked, seqs = driver.check_inputs()
    ref = {i: kimi_linear.forward(ctx.session.weights, seq, sizes, "f32")
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
        for i, seq in zip(picked, seqs):
            add(kimi_linear.forward(ctx.session.weights, seq, sizes,
                                    control), i)
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
