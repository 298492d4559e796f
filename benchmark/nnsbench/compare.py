"""The comparisons that decide ``correct``: what the timed path produced
in the window, at the timed sizes, against the plain reference run once
the window has closed. Each returns ``{name: {"value": v, "limit": l}}``;
a run is correct when every value is finite and within its limit.

``control`` names the precision below the configuration's (``fp8``, or
the coarser ``fp8_e5m2``): the reference's stand-in is computed in it and
the same numbers are read for it: the control has
to come out as not correct (tests/ and tools/calibrate.py run it; a
benchmark run never does).

The comparison of one family of configurations is ``checks/<family>.py``,
a file of its own found by the configuration's ``family``: ``check(driver,
results, ctx, limits, control=None)``. This module holds what they
share."""
from __future__ import annotations

import math

import numpy as np


def verdict(numbers: dict) -> bool:
    return all(math.isfinite(n["value"]) and n["value"] <= n["limit"]
               for n in numbers.values())


def row_gap(got, ref):
    """Largest |got - ref| of one row of logits over the reference row's
    range."""
    if got.shape != ref.shape or not np.isfinite(got).all():
        return math.inf
    return float(np.abs(got.astype(np.float64) - ref).max()
                 / (ref.max() - ref.min()))


class Rows:
    """Error of rows of logits against the reference's, gathered row by
    row: the widest single entry (``gap``, over the row's range) and the
    root mean square over all entries of all rows, over the reference
    logits' own spread about each row's mean (``rms``)."""

    def __init__(self):
        self.gap, self.err2, self.ref2, self.rows = 0.0, 0.0, 0.0, 0

    def add(self, got, ref):
        self.rows += 1
        gap = row_gap(got, ref)
        self.gap = max(self.gap, gap)
        if math.isinf(gap):
            self.err2 = math.inf
            return
        ref = ref.astype(np.float64)
        self.err2 += float(np.square(got.astype(np.float64) - ref).sum())
        self.ref2 += float(np.square(ref - ref.mean()).sum())

    def bad(self):
        self.gap = self.err2 = math.inf

    @property
    def rms(self):
        if not self.rows or not self.ref2:
            return math.inf           # nothing came back to compare
        return math.sqrt(self.err2 / self.ref2)
