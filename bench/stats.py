"""Metric arithmetic shared by the metric readers."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of every value.

    No interpolation and no sampling: the value at rank ``ceil(q/100 * n)``
    of the sorted values.  Raises on an empty list.
    """
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[rank - 1])

