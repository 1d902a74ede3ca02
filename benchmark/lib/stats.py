"""Percentile and window arithmetic.  Stdlib only, no device, no clock."""

from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default rule).  None for an empty sample: a
    metric with nothing to read is left out, never reported as 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, t0: float, t1: float) -> float:
    """``count`` per second over the window ``[t0, t1]``."""
    if not t1 > t0:
        raise ValueError(f"empty window: t0={t0} t1={t1}")
    return count / (t1 - t0)


def in_window(stamp: float, t0: float, t1: float) -> bool:
    """Closed at both ends: a request that completes at the very instant
    the window closes still counts."""
    return t0 <= stamp <= t1


def delta(after: dict, before: dict, key: str) -> float:
    """Difference of a cumulative counter between two snapshots; a key
    that was never touched reads as 0 on either side."""
    return float(after.get(key, 0.0)) - float(before.get(key, 0.0))


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median, the driver's
    measure of run-to-run spread."""
    med = percentile(values, 50)
    if med is None or med == 0:
        return None
    return (percentile(values, 75) - percentile(values, 25)) / abs(med)
