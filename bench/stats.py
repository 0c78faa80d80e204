"""Exact statistics over per-request client timestamps."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Exact ``p``-th percentile of every value, interpolated linearly
    between the two nearest ranks (numpy's default ``linear`` method)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * p / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: int, start: float, end: float) -> float:
    """Completions per second over the whole window ``[start, end]``."""
    if end <= start:
        raise ValueError("empty window")
    return count / (end - start)
