"""Order statistics the benchmark reports, computed over every sample.

A percentile interpolates linearly between the two nearest ranks (the
method numpy calls ``linear``). ``spread`` is the distance between the first
and third quartile, as ``statistics.quantiles(values, n=4)`` gives them, as a
share of the median: the measure the benchmark's bounds are set from.
"""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) of ``values``; ``inf`` entries sort
    last, so a sample that is missing counts above every finished one."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == xs[lo]:
        return float(xs[lo])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median (``statistics.quantiles``'
    default, exclusive method)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
