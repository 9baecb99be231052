"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Every reader returns ``None`` where the run holds nothing to read (no
trace, no span, no matching op), and the harness then leaves the metric
out of the result line: a share is never reported as 0 for want of data.
"""
from __future__ import annotations

from typing import Iterable, Optional

from kgbench import devtrace, kernel_bytes


def mean_ms(values: Iterable[float]) -> Optional[float]:
    vals = list(values)
    return 1e3 * sum(vals) / len(vals) if vals else None


def mean(values: Iterable[float]) -> Optional[float]:
    vals = list(values)
    return sum(vals) / len(vals) if vals else None


def idle_share_pct(run) -> Optional[float]:
    if run.profile is None:
        return None
    return 100.0 * run.profile.idle_share


def is_sort(event) -> bool:
    """An XLA sort operation (the δ's and the ⋈'s sorts)."""
    return devtrace.hlo_parts(event[0])[2] == "sort"


def sort_share_pct(run) -> Optional[float]:
    p = run.profile
    if p is None or not p.events(is_sort):
        return None
    return 100.0 * p.op_seconds(is_sort) / p.busy_s


def kernel_roofline_pct(run, kernels) -> Optional[float]:
    """Least time for the kernels' HBM bytes at the chip's bandwidth over
    their summed event time, in %: the kernels are memory-bound."""
    p = run.profile
    if p is None:
        return None
    events = p.events(lambda ev: kernel_bytes.kernel_of(ev) in kernels)
    if not events:
        return None
    nbytes = [kernel_bytes.hbm_bytes(ev) for ev in events]
    if any(b is None for b in nbytes):
        return None
    seconds = sum(ev[2] for ev in events) * 1e-9
    return 100.0 * (sum(nbytes) / run.peaks.hbm_bw) / seconds
