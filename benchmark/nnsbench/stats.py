"""Percentile, rate and spread arithmetic of the benchmark (the yardstick:
later PRs may not edit it). Checked on fixed samples by ``--selftest``."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100), linear between closest ranks
    (numpy's default). None for an empty sample: a metric with nothing
    to read is left out, never reported as 0."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def rate(count: float, seconds: float) -> Optional[float]:
    """All the work over all the time of the window."""
    if seconds <= 0:
        return None
    return count / seconds


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)`` (the rule the
    bounds are set by)."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None
