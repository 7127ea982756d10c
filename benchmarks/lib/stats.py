"""Percentile arithmetic, in one place."""
from __future__ import annotations

from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """q in [0, 1], linear interpolation between order statistics (numpy's
    default). None for no values."""
    if not values:
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * q
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 0.5)
