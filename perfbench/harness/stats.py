"""Order statistics the metric readers share."""

from __future__ import annotations

import math


def percentile(values, q: float):
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics; ``None`` for no values."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values):
    return percentile(values, 50.0)
