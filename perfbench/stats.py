"""Percentiles, the benchmark's own (linear interpolation between the
closest ranks, as ``numpy.percentile`` does by default)."""

from __future__ import annotations

import math
from typing import Optional, Sequence

# A tail is reported only where at least this many samples lie beyond
# it: the 95th percentile of 60 requests is the third-worst request.
MIN_BEYOND = 10


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) of a non-empty sequence."""
    data = sorted(values)
    if not data:
        raise ValueError("percentile of no samples")
    rank = (len(data) - 1) * q / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    return data[lo] + (data[hi] - data[lo]) * (rank - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0) if values else None


def tail(values: Sequence[float], q: float = 95.0) -> Optional[float]:
    """The ``q``-th percentile, or None where fewer than MIN_BEYOND
    samples lie beyond it."""
    if len(values) * (100.0 - q) / 100.0 < MIN_BEYOND:
        return None
    return percentile(values, q)


def mean(values: Sequence[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None
