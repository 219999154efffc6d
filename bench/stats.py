"""Rates and percentiles, written out so every PR computes them alike."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it. A missing value (a failed or refused request)
    is +inf, so it lands in the tail."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def rate(done: int, seconds: float) -> float:
    """Work per second over the whole window, from its start to the
    completion of the last unit of work."""
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0")
    return done / seconds
