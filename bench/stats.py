"""Percentiles and rates over every sample of a window.

A percentile is taken over all samples, never over a subset or a
per-interval summary: ``q`` percent of the way from the smallest to the
largest sample in sorted order, interpolated linearly between the two
neighbouring ranks (numpy's default ``linear`` method).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    if not values:
        return None
    v = sorted(values)
    pos = q / 100.0 * (len(v) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def rate(count: int, seconds: float) -> float:
    """Work per second over the whole window."""
    return count / seconds
