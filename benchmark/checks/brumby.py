"""The ``brumby`` family's comparison (a configuration names it under
``family``; run.py loads ``checks/<family>.py`` and calls ``check``)."""
from __future__ import annotations

import math

from nnsbench.compare import Rows


def check(driver, results, ctx, limits, control=None):
    """One whole document of the window: every pass of it all of whose
    buffers were pushed in the window and arrived
    (``Driver.check_document``), each buffer's two tensors against the
    reference's for the whole document at that buffer's positions:
    ``logprob_rms`` on the per-token log-probabilities (a buffer's last
    position has none: the token after it lies in the next buffer),
    ``logit_rms`` / ``logit_gap`` on the buffers' last rows of logits.
    The reference is the quadratic definition over the document
    (``refs/brumby.py``): it has no state, so a state that the program
    lost, reset or carried wrongly between two buffers shows in every
    buffer after the first. A control (``fp8``, ``fp8_e5m2``) stands in
    the program's place."""
    from refs import brumby
    sizes = {**ctx.config, **ctx.sizes}
    seq = int(ctx.traffic["tokens_per_buffer"])
    doc, tokens, passes = driver.check_document(results["answers"])
    logits, logprobs = Rows(), Rows()
    if doc is not None:
        ref_last, ref_lp = brumby.forward(ctx.session.weights, tokens, sizes,
                                          "f32", buffer=seq)
        if control:
            last, lp = brumby.forward(ctx.session.weights, tokens, sizes,
                                      control, buffer=seq)
            passes = {"control": [(last[k], lp[k * seq:(k + 1) * seq])
                                  for k in range(len(last))]}
        for buffers in passes.values():
            for k, out in enumerate(buffers):
                if len(out) != 2 or out[0].ndim != 1:
                    logits.bad()
                    continue
                logits.add(out[0], ref_last[k])
                logprobs.add(out[1][:-1], ref_lp[k * seq:(k + 1) * seq - 1])
    read = {"logit_rms": logits.rms,
            "logit_gap": logits.gap if logits.rows else math.inf,
            "logprob_rms": logprobs.rms}
    return ({k: {"value": read[k], "limit": limits[k]} for k in limits},
            {"buffers_compared": logits.rows, "document": doc,
             "passes_compared": len(passes), "read": read})
