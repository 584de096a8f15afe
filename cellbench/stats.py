"""Arithmetic on rows: percentiles, pooled token gaps.  Kept
with the benchmark so that every PR computes a metric the same way."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) with linear interpolation between
    the two nearest ranks; None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def pooled_gaps(rows: Iterable[Sequence[float]], start: float,
                end: float) -> List[float]:
    """Every gap, over all requests, whose later token was delivered
    in ``[start, end)``: the pool a gap percentile is taken over."""
    out = []
    for times in rows:
        for a, b in zip(times, times[1:]):
            if start <= b < end:
                out.append(b - a)
    return out


def count_in(rows: Iterable[Sequence[float]], start: float,
             end: float) -> int:
    """Tokens delivered in ``[start, end)``."""
    return sum(1 for times in rows for t in times if start <= t < end)

