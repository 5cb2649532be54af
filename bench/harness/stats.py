"""Exact order statistics: every value is kept, nothing is bucketed."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile ``q`` (0 < q <= 100) of all ``values``:
    the smallest value with at least q% of the values at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]
