"""Order statistics used by the benchmark's metrics and its bounds."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(sorted_values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile of an ascending-sorted sequence.

    p in (0, 100]; rank = ceil(p/100 * n), so ``percentile(v, 100)`` is the
    maximum and every result is an observed value (no interpolation).
    """
    n = len(sorted_values)
    if n == 0:
        raise ValueError("percentile of empty sequence")
    if not 0 < p <= 100:
        raise ValueError(f"p={p} out of (0, 100]")
    rank = max(1, math.ceil(p * n / 100 - 1e-9))
    return float(sorted_values[min(rank, n) - 1])


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``): the spread that the
    benchmark's bounds are set from."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
