"""The ``gpt`` family's comparison (a configuration names it under
``family``; run.py loads ``checks/<family>.py`` and calls ``check``)."""
from __future__ import annotations

import math

import numpy as np


def check(driver, results, ctx, limits, control=None):
    """A seeded sample of the requests the window finished, the longest
    among them: the reference runs once over each prompt with its served
    tokens, and the number is the widest gap by which a served token's
    logit lies below the reference's best, over the logits' range at that
    position. The control reads the gap of the token the lower precision
    puts first at the same positions."""
    from refs import gpt
    sample = driver.check_sample(results)
    worst, tokens = 0.0, 0
    # one padded length for every run, so the reference's programs are
    # found in the compile cache whatever the sample holds
    pad = max(ctx.traffic["prompt_lens"]) + int(ctx.traffic["max_tokens"])
    gaps = []
    for prompt, served in sample:
        seq = np.zeros(pad, np.int32)
        n = len(prompt) + len(served) - 1
        seq[:len(prompt)] = prompt
        seq[len(prompt):n] = served[:-1]
        at = np.arange(len(prompt) - 1, n)
        ref = gpt.logits_at(ctx.session.weights, seq, at, ctx.sizes, "f32")
        if control:
            low = gpt.logits_at(ctx.session.weights, seq, at, ctx.sizes,
                                control)
            chosen = low.argmax(-1)
        else:
            chosen = np.asarray(served)
            if chosen.min() < 0 or chosen.max() >= ref.shape[1]:
                worst = math.inf
                continue
        rows = np.arange(len(chosen))
        gap = (ref.max(-1) - ref[rows, chosen]) / (ref.max(-1) - ref.min(-1))
        if not np.isfinite(gap).all():
            worst = math.inf
            continue
        worst = max(worst, float(gap.max()))
        gaps += [float(g) for g in gap]
        tokens += len(chosen)
    if not tokens:
        worst = math.inf
    read = {"token_gap": worst,
            "token_gap_mean": float(np.mean(gaps)) if gaps and
            math.isfinite(worst) else math.inf}
    return ({k: {"value": read[k], "limit": limits[k]} for k in limits},
            {"requests_compared": len(sample), "tokens_compared": tokens,
             "read": read,
             "off_best": sum(1 for g in gaps if g > 0)})
