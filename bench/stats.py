"""Exact order statistics for the benchmark's tails and spreads."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """The nearest-rank ``p``-th percentile: the smallest value with at
    least ``p`` percent of the values at or below it.  Exact (no buckets, no
    interpolation); ``inf`` entries stand for requests that never finished
    and count as missing any limit."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0.0 < p <= 100.0:
        raise ValueError(f"percentile {p} outside (0, 100]")
    ordered = sorted(values)
    rank = math.ceil(p / 100.0 * len(ordered))
    return ordered[rank - 1]


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(med)
