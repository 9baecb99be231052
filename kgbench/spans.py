"""Host spans the benchmark records around its calls into the system.

Each span is kept in memory (name, start, end on ``perf_counter_ns``) and
also written into the profiler's trace as ``kgbench.<name>``, so a traced
run can say what the host was doing while the device sat idle.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterator, List, Tuple

import jax

PREFIX = "kgbench."


class Spans:
    def __init__(self) -> None:
        self.done: List[Tuple[str, int, int]] = []

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            try:
                yield
            finally:
                self.done.append((name, t0, time.perf_counter_ns()))

    def durations(self, name: str) -> List[float]:
        """Seconds of every finished span called ``name``."""
        return [(t1 - t0) * 1e-9 for n, t0, t1 in self.done if n == name]
