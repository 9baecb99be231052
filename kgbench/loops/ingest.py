"""Closed-loop streaming ingest: one producer hands micro-batches of new
records to a live session, one at a time.

Set-up generates the base sources from the seed, opens a ``KGEngine``
session over them and calls ``create_kg`` (which compiles the plan, or
loads it from JAX's cache), then ingests the first
``traffic["warmup_batches"]`` batches, which warms the ingest path's
shapes, and generates as many more as a window of ``--seconds`` takes at
the fastest warm-up batch's pace, twice over, within the room of the capacity
bucket the sources sit in. The window then takes the next batch, encodes
its records with the session's vocabulary (``Table.from_records``, span
``encode``) and ingests them (``KGEngine.ingest``, span ``ingest``, ended
when the KG is on the device), counting the host syncs of each ingest. A
batch's latency runs from handing its records to the system until the KG
is current. The window never crosses a capacity bucket: it stops early,
and says so, when the batches run out.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List, Tuple

import numpy as np

from kgbench import arith, session
from kgbench.refkg import (reference_kg, rows_diff, rows_of_triples,
                           triples_diff, triples_of_rows)


class State:
    def __init__(self, ctx) -> None:
        from repro.api import KGEngine
        from repro.relalg import Table
        shape = ctx.cell.shape()
        self.rows = int(ctx.traffic["batch_rows"])
        with ctx.spans.span("generate"):
            self.dep = shape.deployment(ctx.cfg, [ctx.seed])
            self.vocab = session.vocabulary(self.dep)
        dis = session.mapping(self.dep, self.vocab)
        with ctx.spans.span("upload"):
            dis.sources = {name: Table.from_codes(codes, self.dep.attrs[name])
                           for name, codes in self.dep.datasets[0].items()}
        with ctx.spans.span("create_kg"):
            self.engine = KGEngine(dis,
                                   config=session.engine_config(ctx.cfg))
            self.kg, _ = self.engine.create_kg()
            self.kg.data.block_until_ready()
        # the micro-batches that fit the capacity bucket the sources sit in
        self.room = min(
            (t.capacity - len(self.dep.datasets[0][name])) // self.rows
            for name, t in self.engine.sources.items())
        self.batches: List = []
        self.applied = 0

    def generate(self, ctx, count: int) -> None:
        """Generate the stream's next batches, up to ``count`` in all."""
        count = min(count, self.room)
        with ctx.spans.span("generate"):
            self.batches += ctx.cell.shape().stream(
                ctx.cfg, ctx.seed, len(self.batches),
                count - len(self.batches), self.rows)


def ingest_one(ctx, state: State) -> float:
    """Encode and ingest the next batch; its latency in seconds."""
    from repro.relalg import Table, count_transfers
    batch = state.batches[state.applied]
    t0 = time.perf_counter()
    with ctx.spans.span("encode"):
        deltas = {name: Table.from_records(recs, state.dep.attrs[name],
                                           state.vocab)
                  for name, recs in batch.items()}
    with ctx.spans.span("ingest"), count_transfers() as ledger:
        state.kg, _ = state.engine.ingest(deltas)
        state.kg.data.block_until_ready()
    latency = time.perf_counter() - t0
    state.applied += 1
    ctx.count("host_syncs", ledger.device_to_host)
    ctx.count("rows", sum(len(recs) for recs in batch.values()))
    return latency


def setup(ctx) -> State:
    state = State(ctx)
    warmup = int(ctx.traffic["warmup_batches"])
    state.generate(ctx, warmup)
    pace = min(ingest_one(ctx, state) for _ in range(warmup))
    state.generate(ctx, warmup + 2 + 2 * math.ceil(ctx.seconds / pace))
    return state


def recompiles(state: State) -> int:
    return state.engine.recompiles


def window(ctx, state: State, seconds: float) -> Dict:
    latencies = []
    t0 = time.perf_counter()
    end = t0 + seconds
    while time.perf_counter() < end and state.applied < len(state.batches):
        latencies.append(ingest_one(ctx, state))
    window_s = time.perf_counter() - t0
    rows = sum(ctx.counters.get("rows", ()))
    notes = {"batches": len(latencies), "room": state.room,
             "applied": state.applied,
             "slowest_ms": [1e3 * t for t in sorted(latencies)[-3:]]}
    if state.applied == len(state.batches) and window_s < seconds:
        notes["stopped"] = ("the capacity bucket's room was used up"
                            if state.applied == state.room else
                            "the generated batches ran out")
    return {"window_s": window_s, "attempted": len(latencies), "failed": 0,
            "metrics": {"ingest_rows_per_s": arith.rate(rows, window_s),
                        "ingest_p95_ms": 1e3 * arith.percentile(latencies, 95)},
            "notes": notes}


def program_output(state: State) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """The session's sources and KG on the host, then the program's state
    freed."""
    from repro.api import clear_plan_cache
    got = ({name: t.to_codes() for name, t in state.engine.sources.items()},
           state.kg.to_codes())
    state.engine = state.kg = None
    clear_plan_cache()
    return got


def reference_sources(state: State, applied: int) -> Dict[str, np.ndarray]:
    """The base sources plus the first ``applied`` batches, encoded the
    way a vocabulary that numbers values in order of arrival does:
    record by record, attribute by attribute."""
    dep = state.dep
    code = {v: i for i, v in enumerate(dep.values)}
    parts = {name: [codes] for name, codes in dep.datasets[0].items()}
    for batch in state.batches[:applied]:
        for name, recs in batch.items():
            attrs = dep.attrs[name]
            rows = [code.setdefault(rec[a], len(code))
                    for rec in recs for a in attrs]
            parts[name].append(np.asarray(rows, np.int32).reshape(
                -1, len(attrs)))
    return {name: np.concatenate(p) for name, p in parts.items()}


def _reference_kg(state: State, sources: Dict[str, np.ndarray]):
    dep = state.dep
    return reference_kg(dep.maps, sources, dep.attrs, dep.constant_codes)


def control_output(state: State):
    """The control, put in the program's place: the reference state one
    acknowledged batch behind (a session whose ingest returns before it
    applies the batch)."""
    stale = reference_sources(state, state.applied - 1)
    return stale, rows_of_triples(_reference_kg(state, stale))


def check(ctx, state: State, result: Dict, output=None):
    """Compares ``output`` (by default the program's) with the
    reference."""
    from kgbench.harness import Check
    sources, kg_rows = program_output(state) if output is None else output
    want = reference_sources(state, state.applied)
    src_diff = sum(rows_diff(sources[name], want[name]) for name in want)
    triples, repeats = triples_of_rows(kg_rows)
    kg_diff = triples_diff(triples, _reference_kg(state, want)) + repeats
    return [Check("source_rows_diff", src_diff, 0),
            Check("kg_triples_diff", kg_diff, 0)]
