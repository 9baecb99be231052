"""Closed-loop batch rebuilds: one ``create_kg`` at a time, each from a
fresh dump.

Set-up generates ``traffic["datasets"]`` datasets (seeds ``seed``,
``seed + 1``, ...) coded against one vocabulary, and rebuilds once from
each, which compiles the plan (or loads it from JAX's cache) and warms
every shape. Each rebuild of the window takes the next dataset in turn,
uploads its code matrices with ``Table.from_codes`` (span ``upload``,
ended when the device holds them), opens a new ``KGEngine`` session over
them and calls ``create_kg`` (span ``create_kg``, ended when the KG is on
the device): what a user rebuilding a knowledge graph from a new dump
does. A sample of the rebuilds, drawn from the seed, keeps its KG for the
check.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax

from kgbench import arith, session
from kgbench.data import rng
from kgbench.refkg import (hash_only_distinct, reference_kg, rows_of_triples,
                           triples_diff, triples_of_rows)


class State:
    def __init__(self, ctx) -> None:
        n_sets = int(ctx.traffic["datasets"])
        with ctx.spans.span("generate"):
            self.dep = ctx.cell.shape().deployment(
                ctx.cfg, [ctx.seed + i for i in range(n_sets)])
            self.vocab = session.vocabulary(self.dep)
        self.config = session.engine_config(ctx.cfg)
        # the mapping, parsed once; each rebuild copies it with new sources
        self.dis = session.mapping(self.dep, self.vocab)
        self.records = [self.dep.records(i) for i in range(n_sets)]
        self.next = 0
        self.engine = None
        self.kept: List = []          # (dataset index, KG table)
        self.last: Dict[int, object] = {}   # dataset -> its latest KG


def rebuild(ctx, state: State, d: int):
    """Upload dataset ``d`` and build its KG in a new session."""
    from repro.api import KGEngine
    from repro.relalg import Table
    state.engine = None               # drop the previous session's sources
    with ctx.spans.span("upload"):
        sources = {name: Table.from_codes(codes, state.dep.attrs[name])
                   for name, codes in state.dep.datasets[d].items()}
        jax.block_until_ready([t.data for t in sources.values()])
    dis = state.dis.copy()
    dis.sources = sources
    with ctx.spans.span("create_kg"):
        state.engine = KGEngine(dis, config=state.config)
        kg, _ = state.engine.create_kg()
        kg.data.block_until_ready()
    return kg


def setup(ctx) -> State:
    state = State(ctx)
    for d in range(len(state.dep.datasets)):
        rebuild(ctx, state, d)
    return state


def recompiles(state: State) -> int:
    from repro.api import plan_cache_stats
    return int(plan_cache_stats().get("misses", 0)) + (
        state.engine.recompiles if state.engine is not None else 0)


def window(ctx, state: State, seconds: float) -> Dict:
    sample = rng(ctx.seed, 2)
    keep_share = float(ctx.traffic["check_share"])
    n_sets = len(state.dep.datasets)
    records, times = 0, []
    t0 = time.perf_counter()
    end = t0 + seconds
    while time.perf_counter() < end:
        d = state.next % n_sets
        t = time.perf_counter()
        kg = rebuild(ctx, state, d)
        times.append(time.perf_counter() - t)
        records += state.records[d]
        if sample.random() < keep_share and len(state.kept) < 2:
            state.kept.append((d, kg))
        state.last[d] = kg
        state.next += 1
    window_s = time.perf_counter() - t0
    # the last rebuild of each dataset is checked too
    state.kept += [(d, kg) for d, kg in sorted(state.last.items())
                   if all(kg is not k for _, k in state.kept)]
    return {"window_s": window_s, "attempted": len(times), "failed": 0,
            "metrics": {"create_records_per_s": arith.rate(records, window_s)},
            "notes": {"creates": len(times), "records": records,
                      "checked": len(state.kept)}}


def program_output(state: State):
    """The kept KGs on the host, then the program's state freed."""
    from repro.api import clear_plan_cache
    got = [(d, kg.to_codes()) for d, kg in state.kept]
    state.kept, state.last, state.engine = [], {}, None
    clear_plan_cache()
    return got


def reference(state: State, d: int):
    dep = state.dep
    return reference_kg(dep.maps, dep.datasets[d], dep.attrs,
                        dep.constant_codes)


def control_output(state: State):
    """The control, put in the program's place: each kept KG's reference
    through a δ that trusts a 32-bit hash of the whole triple (what
    dropping the hash δ's full-row check would do)."""
    return [(d, rows_of_triples(hash_only_distinct(reference(state, d))))
            for d, _ in state.kept]


def check(ctx, state: State, result: Dict, output=None):
    """Compares ``output`` (by default the program's) with the
    reference."""
    from kgbench.harness import Check
    got = program_output(state) if output is None else output
    refs = {}
    diff = 0
    for d, rows in got:
        if d not in refs:
            refs[d] = reference(state, d)
        triples, repeats = triples_of_rows(rows)
        diff += triples_diff(triples, refs[d]) + repeats
    return [Check("kg_triples_diff", diff, 0)]
