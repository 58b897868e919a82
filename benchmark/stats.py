"""Percentiles and means, the same way in every cell."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; a missing sample (inf) stays inf once q reaches it."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if lo == hi or math.isinf(xs[hi]):
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def mean(values) -> float:
    values = list(values)
    return sum(values) / len(values) if values else math.nan
