"""Percentiles and spreads, as the benchmark's contract defines them."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0 < q < 100) by linear interpolation between
    order statistics (numpy's default rule), or None for no samples."""
    if not values:
        return None
    xs = sorted(float(v) for v in values)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile."""
    return int(math.floor(n * (1.0 - q / 100.0)))


def highest_supported_percentile(n: int, beyond: int = 10,
                                 ladder=(50, 90, 95, 99, 99.9)) -> float:
    """The highest percentile of the ladder with at least ``beyond`` samples
    beyond it (choosing-metrics guide, section 1); 50 when none has."""
    best = ladder[0]
    for q in ladder:
        if samples_beyond(n, q) >= beyond:
            best = q
    return best


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
