"""Rate, tail and spread arithmetic, kept with the benchmark.

:func:`percentile` is a copy of the system's ``serve.stats.percentile``
(linear interpolation between closest ranks, numpy's default method).
"""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (``0 <= q <= 100``) of ``values`` by linear
    interpolation; raises on an empty sample."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q!r}")
    vals = sorted(float(v) for v in values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    rank = (len(vals) - 1) * (q / 100.0)
    lo = math.floor(rank)
    hi = min(lo + 1, len(vals) - 1)
    frac = rank - lo
    return vals[lo] * (1.0 - frac) + vals[hi] * frac


def rate(work: float, seconds: float) -> float:
    """All the work of a window over all of its time."""
    if seconds <= 0:
        raise ValueError(f"a window of {seconds!r} s has no rate")
    return float(work) / float(seconds)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
