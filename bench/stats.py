"""Arithmetic over a whole window's samples: one percentile over every
request of the window, never a statistic of per-chunk statistics."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """The ``p``-th percentile with linear interpolation between closest
    ranks (numpy's default ``linear`` method); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * p / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def share(part: int, whole: int) -> Optional[float]:
    """``part`` of ``whole`` in percent; None when there is no whole."""
    return None if whole <= 0 else 100.0 * part / whole
