"""Shared by the benchmark's CPU tests: cells cut to a size a test run
holds, and a stand-in for the chip check (the tests run on the CPU)."""
from __future__ import annotations

import time
import types

from kgbench import harness, registry

#: per shape: the size keys a test shrinks, and their test values
TINY = {"group_a": {"rows_per_source_at_volume_1": 8000, "redundancy": 0.25}}


def tiny_cell(workload: str, batch_rows: int = 50) -> registry.Cell:
    cell = registry.resolve(workload)
    cell.config.update(TINY[cell.config["shape"]])
    if "batch_rows" in cell.traffic:
        cell.traffic["batch_rows"] = batch_rows
    return cell


def fake_chips(n: int = 1):
    """Devices that pass for v5e chips in the harness's arithmetic; the
    run itself is on the CPU."""
    return [types.SimpleNamespace(
        platform="cpu", device_kind="TPU v5 lite",
        memory_stats=lambda: {"peak_bytes_in_use": 1})] * n


def run(cell: registry.Cell, seed: int = 2**33 + 7, seconds: float = 0.5):
    """One run of ``cell`` past the harness's look for a chip."""
    out, checks = harness.run_cell(cell, seed, seconds, False, fake_chips(),
                                   time.perf_counter())
    return out, {c.name: c for c in checks}
