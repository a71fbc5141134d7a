"""Order statistics as the benchmark reports them."""
from __future__ import annotations

import math


def quantile(values, q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least a share
    ``q`` of the values at or below it. Empty input is NaN, never 0."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    return float(xs[max(0, math.ceil(q * len(xs)) - 1)])
