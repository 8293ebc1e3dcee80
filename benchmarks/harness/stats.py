"""Percentiles and spreads, in plain Python so that a number means the same
in every PR."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default). Raises on an empty input: a cell
    that produced no sample has no percentile."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def summary(values: Sequence[float], qs=(50, 90, 99)) -> dict:
    """Sample count, min, max and the percentiles ``qs``; and for each
    percentile how many samples lie beyond it (a tail wants ten)."""
    if not values:
        return {"n": 0}
    out = {"n": len(values), "min": float(min(values)),
           "max": float(max(values))}
    for q in qs:
        out[f"p{q:g}"] = percentile(values, q)
        out[f"beyond_p{q:g}"] = int(len(values) * (100 - q) / 100)
    return out


def spread(values: Sequence[float]) -> float:
    """The distance between the quartiles over the median: what the driver
    reads as a metric's run-to-run spread."""
    m = median(values)
    return (percentile(values, 75) - percentile(values, 25)) / abs(m)
