"""The ``vit`` family's comparison (a configuration names it under
``family``; run.py loads ``checks/<family>.py`` and calls ``check``)."""
from __future__ import annotations

import math

from nnsbench.compare import Rows


def check(driver, results, ctx, limits, control=None):
    """Every answer of the window that carries one of the sampled frames
    is compared with the reference's logits for that frame."""
    from refs import vit
    pairs, frames = driver.check_inputs()
    ref = vit.forward(ctx.session.weights, frames, ctx.sizes, "f32")
    where = {}
    for j, (b, r) in enumerate(pairs):
        where.setdefault(b, []).append((r, j))
    acc = Rows()
    if control:
        low = vit.forward(ctx.session.weights, frames, ctx.sizes, control)
        for j in range(len(pairs)):
            acc.add(low[j], ref[j])
    else:
        for b, logits in results["answers"]:
            for r, j in where.get(b, ()):
                if logits.ndim != 2 or r >= logits.shape[0]:
                    acc.bad()
                    continue
                acc.add(logits[r], ref[j])
    read = {"logit_rms": acc.rms,
            "logit_gap": acc.gap if acc.rows else math.inf}
    return ({k: {"value": read[k], "limit": limits[k]} for k in limits},
            {"rows_compared": acc.rows, "frames_referenced": len(pairs),
             "read": read})
