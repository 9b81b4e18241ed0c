"""Metric arithmetic over request records: percentiles, time per output
token, rates.  Plain Python and numpy, no JAX."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default); None for no values."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def tpot(first_s: float, last_s: float, tokens: int) -> Optional[float]:
    """Time per output token of one request after its first: (last
    delivery - first delivery) / (tokens - 1); None below two tokens."""
    if tokens < 2:
        return None
    return (last_s - first_s) / (tokens - 1)


def rate(count: float, seconds: float) -> float:
    return count / seconds


def merged(intervals: Iterable[Sequence[float]]) -> List[List[float]]:
    """The union of ``[start, end)`` intervals as sorted disjoint ones."""
    out: List[List[float]] = []
    for s, e in sorted((float(a), float(b)) for a, b in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_length(intervals: Iterable[Sequence[float]]) -> float:
    """Length of the union of ``[start, end)`` intervals."""
    return sum(e - s for s, e in merged(intervals))
