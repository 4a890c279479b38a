"""Percentile arithmetic of the benchmark (pure Python, no numpy)."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default), of a non-empty sequence."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def summary(values: Sequence[float]) -> dict:
    """Count, median, p95 and max, for the lines a run prints before
    its result."""
    if not values:
        return {"n": 0}
    return {"n": len(values), "p50": percentile(values, 50),
            "p95": percentile(values, 95), "max": max(values)}
