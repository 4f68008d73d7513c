"""Percentile and spread arithmetic, kept with the benchmark so that
every PR computes the same number in the same way."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest sample with at least a
    share `p` (0 < p <= 1) of the sample at or below it. None for an
    empty sample. No interpolation: the answer is always a value that
    was measured."""
    if not values:
        return None
    if not 0.0 < p <= 1.0:
        raise ValueError(f"percentile share {p} outside (0, 1]")
    xs = sorted(values)
    rank = max(1, math.ceil(p * len(xs) - 1e-9))
    return xs[rank - 1]


def quartile_spread(values: Sequence[float]) -> Optional[float]:
    """The builder's spread: distance between the first and the third
    quartile as `statistics.quantiles(values, n=4)` gives them, as a
    share of the median."""
    if len(values) < 2:
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def share(part: float, whole: float) -> Optional[float]:
    """part / whole as a percentage; None where there is no whole."""
    return 100.0 * part / whole if whole > 0 else None
