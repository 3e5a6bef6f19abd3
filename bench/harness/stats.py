"""End-to-end arithmetic shared by every cell: rates over the whole
window, percentiles over all requests."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) of ``values`` by the nearest-rank
    rule: the smallest value with at least q% of the values at or below
    it.  ``inf`` stands for a request that never completed, so a tail that
    reaches the missing ones is infinite."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def rate(count: int, seconds: float) -> float:
    """Work per second over the whole window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds
