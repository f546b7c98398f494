"""Percentiles, spreads and failure accounting for the benchmark."""
from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between
    order statistics; None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def with_failures(values, n_failed, penalty):
    """A failed request misses any percentile: it enters the sample
    with `penalty` (the window's length) as its time."""
    return list(values) + [float(penalty)] * int(n_failed)


def summary(values):
    """Median, p95 and count of a timing, for the earlier lines."""
    return {"n": len(values), "median": percentile(values, 50),
            "p95": percentile(values, 95)}


def quartile_spread(values):
    """(Q3 - Q1) / median, quartiles as statistics.quantiles(n=4)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else float("inf")
