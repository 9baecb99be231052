"""KGEngine session benchmark: cold vs cached vs ingest steady state.

Paper mapping: MapSDI's value proposition is *amortization* — extract
knowledge from the mapping rules once, then semantify large and growing
sources cheaply. This group measures the session API that makes the
amortization literal:

* ``cold``    — ``mapsdi_create_kg`` with an empty plan cache: symbolic
                fixpoint + annotation + jit compile + execute.
* ``cached``  — a structurally-identical DIS in a fresh session: the plan
                cache returns the compiled closure, only execution remains.
                The acceptance bar is cached ≥ 10× faster than cold.
* ``ingest``  — steady-state micro-batches through ``engine.ingest``:
                within-bucket appends re-execute the cached closure with
                zero re-trace (triples/sec + recompile counts reported).

Hard correctness gates run in every invocation (including
``--smoke``): an out-of-capacity extension (16× the seed) must produce the
bit-exact KG of a fresh run over the accumulated sources with exactly one
recompile; the distributed shard_map δ path must reuse the session's
cached collective closure (trace-count guard); the fused mesh closure
(``config="distributed_fused"``, over ALL available devices — 8 on the CI
multi-device leg) must run with zero host gathers of intermediate triples
(``forbid_transfers`` passes around the closure) while producing the
bit-identical KG of the single-device planned path; and a fresh process
against a populated persistent plan store
(``config="warm_process_cold_start"``, see ``docs/plan_store.md``) must
reach its first KG ≥ 10× faster than the cold process that populated it,
bit-identically. The static verification layer (``docs/analysis.md``)
is gated too: ``config="verifier_overhead"`` asserts ``verify="plan"``
adds <5% to cold plan-build time, so the default stays on.

Run: ``PYTHONPATH=src python -m benchmarks.engine [--smoke]``
Artifacts: ``experiments/bench/engine.json``.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import numpy as np

from repro.api import (EngineConfig, KGEngine, clear_plan_cache,
                       plan_cache_stats)
from repro.core import parse_dis
from repro.core.distributed import repartition_trace_count
from repro.core.pipeline import mapsdi_create_kg
from repro.core.rdfizer import RDFizer
from repro.data.synthetic import (make_group_b_dis,
                                  make_group_b_extension_records)
from repro.launch.mesh import make_mesh
from repro.relalg import Table, forbid_transfers, host_int

from .common import print_csv, save_rows, timeit


def _gene_records(n: int, seed: int) -> List[Dict]:
    """Extension rows shaped like the group-B ``gene`` source (new samples
    over the same entity pools, so joins keep matching)."""
    return make_group_b_extension_records(n, seed, sources=("gene",))["gene"]


def _delta(engine: KGEngine, name: str, records: List[Dict]) -> Table:
    attrs = engine.sources[name].attrs
    return Table.from_records(records, attrs, engine.vocab)


def bench_cold_vs_cached(n_rows: int, engine: str, dedup: str,
                         repeats: int) -> Dict[str, object]:
    mk = lambda: make_group_b_dis(n_rows, 0.6, seed=0)  # noqa: E731
    clear_plan_cache()
    t0 = time.perf_counter()
    kg_cold, _stats = mapsdi_create_kg(mk(), engine=engine, dedup=dedup)
    kg_cold.data.block_until_ready()
    cold_s = time.perf_counter() - t0

    # fresh session, structurally identical DIS -> plan-cache hit
    t0 = time.perf_counter()
    kg_c, stats_c = mapsdi_create_kg(mk(), engine=engine, dedup=dedup)
    kg_c.data.block_until_ready()
    cached_s = time.perf_counter() - t0
    assert stats_c["plan_cache_hit"], "second one-shot call missed the cache"
    assert np.array_equal(kg_c.to_codes(), kg_cold.to_codes())

    # steady state: re-execution of one session's cached closure (best-of-N
    # even in --smoke — the regression gate keys on this, and a single
    # measurement of a millisecond-scale call is too noisy to gate on)
    session = KGEngine(mk(), config=EngineConfig(engine=engine, dedup=dedup))
    session.create_kg()
    steady_s = timeit(lambda: session.run(), repeats=max(3, repeats),
                      inner=10)

    kg_triples = int(host_int(kg_cold.count))
    row = {
        "config": "group_b", "rows": 2 * n_rows, "engine": engine,
        "dedup": dedup, "kg_triples": kg_triples,
        "cold_s": round(cold_s, 5),
        "cached_s": round(cached_s, 5),
        "steady_s": round(steady_s, 5),
        "speedup_cached": round(cold_s / max(cached_s, 1e-9), 2),
        "speedup_steady": round(cold_s / max(steady_s, 1e-9), 2),
        "cold_triples_per_s": round(kg_triples / max(cold_s, 1e-9)),
        "cached_triples_per_s": round(kg_triples / max(cached_s, 1e-9)),
        "steady_triples_per_s": round(kg_triples / max(steady_s, 1e-9)),
    }
    # acceptance gate: cached re-execution >= 10x faster than cold
    assert cached_s * 10 <= cold_s, \
        f"cached path only {cold_s / cached_s:.1f}x faster than cold"
    return row


def bench_ingest(n_rows: int, engine: str, dedup: str, batches: int,
                 batch_rows: int) -> Dict[str, object]:
    session = KGEngine(make_group_b_dis(n_rows, 0.6, seed=0),
                       config=EngineConfig(engine=engine, dedup=dedup))
    session.create_kg()
    # warm batch: absorbs the (at most one) bucket-crossing recompile so
    # the loop below times the cached steady state
    session.ingest({"gene": _delta(session, "gene",
                                   _gene_records(batch_rows, seed=99))})
    base_recompiles = session.stats()["recompiles"]
    t0 = time.perf_counter()
    triples = 0
    for b in range(batches):
        kg, stats = session.ingest(
            {"gene": _delta(session, "gene",
                            _gene_records(batch_rows, seed=100 + b))})
        triples = stats["kg_triples"]
    dt = time.perf_counter() - t0
    st = session.stats()
    return {
        "config": "ingest", "rows": 2 * n_rows, "engine": engine,
        "dedup": dedup, "batches": batches, "batch_rows": batch_rows,
        "kg_triples": triples,
        "ingest_s_per_batch": round(dt / max(batches, 1), 5),
        "ingest_triples_per_s": round(triples * batches / max(dt, 1e-9)),
        "recompiles": st["recompiles"] - base_recompiles,
        "plan_cache_hits": st["plan_cache_hits"],
    }


def check_overflow_recompile(n_rows: int, engine: str, dedup: str
                             ) -> Dict[str, object]:
    """Acceptance gate: a 16× out-of-capacity extension succeeds — the KG
    is bit-exact vs a fresh run over the accumulated sources — with exactly
    one recompile."""
    dis = make_group_b_dis(n_rows, 0.6, seed=0)
    session = KGEngine(dis, config=EngineConfig(engine=engine, dedup=dedup))
    session.create_kg()
    assert session.stats()["recompiles"] == 0
    kg, stats = session.ingest(
        {"gene": _delta(session, "gene",
                        _gene_records(16 * n_rows, seed=7))})
    assert stats["recompiles"] == 1, \
        f"expected exactly one recompile, got {stats['recompiles']}"
    acc = dis.copy()
    acc.sources = dict(session.sources)
    kg_ref, _ = RDFizer(acc, engine, dedup=dedup)()
    assert np.array_equal(kg.to_codes(), kg_ref.to_codes()), \
        "ingested KG differs from fresh run over accumulated sources"
    return {"config": "overflow_16x", "rows": 2 * n_rows, "engine": engine,
            "dedup": dedup, "kg_triples": stats["kg_triples"],
            "recompiles": stats["recompiles"], "bitwise_equal": True}


def check_distributed_closure_reuse(n_rows: int, dedup: str
                                    ) -> Dict[str, object]:
    """Acceptance gate: the shard_map δ path reuses the session's cached
    collective closure — the shard body is traced at most once across
    repeated ingests (trace-count guard)."""
    mesh = make_mesh((1,), ("data",))
    session = KGEngine(make_group_b_dis(n_rows, 0.6, seed=0),
                       config=EngineConfig(mesh=mesh, dedup=dedup))
    session.create_kg()
    t0 = repartition_trace_count()
    for b in range(2):
        kg, stats = session.ingest(
            {"gene": _delta(session, "gene",
                            _gene_records(max(4, n_rows // 16),
                                          seed=200 + b))})
    traces = repartition_trace_count() - t0
    assert traces == 0, \
        f"distributed δ re-traced {traces}x across same-bucket ingests"
    return {"config": "distributed_reuse", "rows": 2 * n_rows,
            "engine": "sdm", "dedup": dedup,
            "kg_triples": stats["kg_triples"], "sink_traces": traces}


def check_fused_mesh_device_resident(n_rows: int, engine: str, dedup: str,
                                     repeats: int) -> Dict[str, object]:
    """Acceptance gate: the fused mesh closure never gathers intermediate
    triples to host — ``forbid_transfers`` passes around the closure (input
    shard blocks and the final-KG read happen outside it) — and the KG it
    produces is bit-identical to the single-device planned path. Runs over
    ALL available devices, so the CI multi-device leg exercises the real
    collectives."""
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("data",))
    mk = lambda: make_group_b_dis(n_rows, 0.6, seed=0)  # noqa: E731
    kg_single, _ = KGEngine(mk(), config=EngineConfig(
        engine=engine, dedup=dedup)).create_kg()
    session = KGEngine(mk(), config=EngineConfig(engine=engine, dedup=dedup,
                                                 mesh=mesh))
    kg_mesh, stats = session.create_kg()
    assert np.array_equal(kg_mesh.to_codes(), kg_single.to_codes()), \
        "fused mesh KG differs from the single-device planned path"
    entry = session._last["entry"]
    datas, counts = session._shard_sources(session.sources, entry.cap_locals)
    with forbid_transfers():   # zero host gathers of intermediate triples
        jax.block_until_ready(entry.fn(datas, counts))
    steady_s = timeit(lambda: jax.block_until_ready(entry.fn(datas, counts)),
                      repeats=max(3, repeats), inner=10)
    kg_triples = stats["kg_triples"]
    return {"config": "distributed_fused", "rows": 2 * n_rows,
            "engine": engine, "dedup": dedup, "devices": n_dev,
            "kg_triples": kg_triples,
            "steady_s": round(steady_s, 5),
            "triples_per_s": round(kg_triples / max(steady_s, 1e-9)),
            "host_transfers_in_closure": 0,
            "bitwise_equal_single_device": True}


_WARM_START_CHILD = r"""
import hashlib, json, sys, time
from repro.api import EngineConfig, KGEngine
from repro.data.synthetic import make_group_b_dis

root, n_rows = sys.argv[1], int(sys.argv[2])
dis = make_group_b_dis(n_rows, 0.6, seed=0)
t0 = time.perf_counter()          # post-import: plan + compile-or-load + run
session = KGEngine(dis, config=EngineConfig(plan_store=root))
kg, stats = session.create_kg()
kg.data.block_until_ready()
dt = time.perf_counter() - t0
print(json.dumps({
    "seconds": dt,
    "codes_sha": hashlib.sha256(kg.to_codes().tobytes()).hexdigest(),
    "kg_triples": stats["kg_triples"],
    "store_hits": stats["store_hits"],
    "store_rejects": stats["store_rejects"]}))
"""


def check_warm_process_cold_start(n_rows: int) -> Dict[str, object]:
    """Acceptance gate for the persistent plan store: a FRESH process
    against a store populated by a previous process rehydrates the
    AOT-serialized executable — no re-trace, no re-compile — and must be
    ≥ 10× faster to first KG than the cold process that populated it,
    with the bit-identical result (sha over ``to_codes()``).

    Each child needs the device, and a parent that has started an
    accelerator backend holds it: so this check runs before the parent
    touches JAX (first in :func:`run`, and ``engine`` first in
    ``benchmarks.run``), and refuses to run after."""
    import hashlib  # noqa: F401  (used by the child)
    import os
    import subprocess
    import sys as _sys
    import tempfile

    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized() and \
            jax.default_backend() != "cpu":
        raise RuntimeError(
            "check_warm_process_cold_start must run before this process "
            f"initializes the {jax.default_backend()} backend: its child "
            "processes need the device")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    with tempfile.TemporaryDirectory() as root:
        runs = []
        for _ in range(2):   # run 1 populates (cold), run 2 rehydrates
            out = subprocess.run(
                [_sys.executable, "-c", _WARM_START_CHILD, root,
                 str(n_rows)], env=env, capture_output=True, text=True,
                timeout=600)
            assert out.returncode == 0, \
                f"stderr:\n{out.stderr}\nstdout:\n{out.stdout}"
            runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold["store_hits"] == 0, cold
    assert warm["store_hits"] == 1 and warm["store_rejects"] == 0, warm
    assert warm["codes_sha"] == cold["codes_sha"], \
        "store-rehydrated KG differs from the cold compile"
    cold_s, warm_s = cold["seconds"], warm["seconds"]
    assert warm_s * 10 <= cold_s, \
        f"warm process start only {cold_s / warm_s:.1f}x faster than cold"
    return {"config": "warm_process_cold_start", "rows": 2 * n_rows,
            "engine": "sdm", "dedup": None,
            "kg_triples": cold["kg_triples"],
            "cold_s": round(cold_s, 5), "warm_s": round(warm_s, 5),
            "warm_speedup": round(cold_s / max(warm_s, 1e-9), 2),
            "bitwise_equal": True}


def check_verifier_overhead(n_rows: int, engine: str, dedup: str,
                            repeats: int) -> Dict[str, object]:
    """Acceptance gate for the static verification layer (the reason
    ``verify="plan"`` can stay the default): the IR verifier + rewrite
    soundness gates add <5% to cold plan-build time, best-of-N with an
    absolute noise floor — a millisecond-scale verifier rides on a
    seconds-scale trace+compile. ``verify="full"`` (jaxpr audit on top)
    is recorded for the artifact but not gated."""
    mk = lambda: make_group_b_dis(n_rows, 0.6, seed=0)  # noqa: E731

    def cold(verify: str) -> float:
        best = float("inf")
        for _ in range(max(2, repeats)):
            clear_plan_cache()
            t0 = time.perf_counter()
            session = KGEngine(mk(), config=EngineConfig(
                engine=engine, dedup=dedup, verify=verify))
            kg, _ = session.create_kg()
            kg.data.block_until_ready()
            best = min(best, time.perf_counter() - t0)
        st = session.stats()["verify"]
        assert st["mode"] == verify and \
            st["plan_checks"] == (0 if verify == "off" else 1), st
        return best

    off_s = cold("off")
    plan_s = cold("plan")
    full_s = cold("full")
    overhead = plan_s - off_s

    # direct measurement of the verifier pass itself (the A/B delta above
    # is dominated by compile jitter; this is the actual added work)
    from repro.analysis import verify_plan
    from repro.plan.annotate import annotate
    session = KGEngine(mk(), config=EngineConfig(engine=engine, dedup=dedup,
                                                 verify="off"))
    session.create_kg()
    counts, caps = annotate(session._plan, mode=session.mode,
                            slack=session.slack)
    direct_s = timeit(
        lambda: verify_plan(session._plan, engine, counts=counts, caps=caps,
                            sources=session.sources,
                            slack=session.slack).raise_for_status(),
        repeats=max(3, repeats), inner=5)
    # the gate keys on the direct measure: back-to-back cold compiles of
    # the same plan jitter by O(100ms) on shared runners — far above the
    # millisecond-scale verifier — so the A/B delta is recorded in the
    # artifact but cannot be gated tightly
    assert direct_s <= 0.05 * off_s + 0.05, \
        (f"verify='plan' pass costs {direct_s:.3f}s against a "
         f"{off_s:.3f}s cold build (>5% + 50ms noise floor) — the "
         "default must stay cheap")
    return {"config": "verifier_overhead", "rows": 2 * n_rows,
            "engine": engine, "dedup": dedup,
            "cold_off_s": round(off_s, 5),
            "cold_plan_s": round(plan_s, 5),
            "cold_full_s": round(full_s, 5),
            "verify_plan_overhead_s": round(overhead, 5),
            "verify_plan_overhead_pct": round(100 * overhead
                                              / max(off_s, 1e-9), 2),
            "verify_full_overhead_s": round(full_s - off_s, 5),
            "verify_pass_s": round(direct_s, 5)}


def _join_heavy_dis(n_child: int, n_parent: int, seed: int = 0):
    """A join-heavy config with a LARGE parent relative to the child —
    the regime where the all_gather ⋈ exchange hits the ICI wall and
    hash-repartition wins (Iglesias et al. 2022's big-source bottleneck).
    Parent rows are mostly distinct (near-unique keys AND values) so
    pre-processing cannot shrink the gathered side and the join fan-out
    stays bounded."""
    rng = np.random.default_rng(seed)
    keys = [f"K{i}" for i in range(max(8, n_parent // 2))]
    child = [{"ID": int(i), "k": str(keys[rng.integers(0, len(keys))]),
              "v": f"v{i}"} for i in range(n_child)]
    parent = [{"ID": int(i), "k": str(keys[rng.integers(0, len(keys))]),
               "p": f"p{i}"} for i in range(n_parent)]
    return parse_dis({
        "sources": {
            "child": {"attrs": ["ID", "k", "v"], "records": child},
            "parent": {"attrs": ["ID", "k", "p"], "records": parent}},
        "maps": [
            {"name": "M1", "source": "child",
             "subject": {"template": "http://ex/C/{v}", "class": "ex:C"},
             "poms": [{"predicate": "ex:rel",
                       "object": {"parentTriplesMap": "M2",
                                  "joinCondition": {"child": "k",
                                                    "parent": "k"}}}]},
            {"name": "M2", "source": "parent",
             "subject": {"template": "http://ex/P/{p}", "class": "ex:P"},
             "poms": []}]})


def _auto_choices(session: KGEngine):
    return sorted({x.strategy
                   for x in session._last["entry"].exchanges.values()})


def check_join_exchange_crossover(n_rows: int, engine: str, dedup: str,
                                  repeats: int) -> List[Dict]:
    """Acceptance gates for the cost-modeled ⋈ exchange + the crossover
    measurement shipped in the bench artifact:

    * the large-parent config runs under ``join_exchange="repartition"``
      with ZERO host transfers inside the fused closure and produces the
      ``to_codes()``-bit-identical KG of both the gather strategy and the
      single-device planned path;
    * ``auto`` picks repartition on the large-parent config (with >1
      device) while keeping gather on the small-parent group-B config;
    * steady-state seconds for gather vs repartition land in the artifact
      (the repartition-vs-gather crossover on this machine/mesh).
    """
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("data",))
    # the parent must be genuinely large: the cost model's crossover sits
    # near COLLECTIVE_LAUNCH_S · v5e ICI bandwidth ≈ 100 KiB of gathered parent bytes
    # per device (~a few thousand rows per shard)
    n_child, n_parent = max(32, n_rows // 2), max(1 << 14, 8 * n_rows)
    big = lambda: _join_heavy_dis(n_child, n_parent)  # noqa: E731
    kg_single, _ = KGEngine(big(), config=EngineConfig(
        engine=engine, dedup=dedup)).create_kg()
    rows: List[Dict] = []
    steady: Dict[str, float] = {}
    kg_by_strategy = {}
    for strategy in ("gather", "repartition"):
        session = KGEngine(big(), config=EngineConfig(
            engine=engine, dedup=dedup, mesh=mesh, join_exchange=strategy))
        kg, stats = session.create_kg()
        assert np.array_equal(kg.to_codes(), kg_single.to_codes()), \
            f"{strategy} KG differs from the single-device planned path"
        kg_by_strategy[strategy] = kg
        entry = session._last["entry"]
        datas, counts = session._shard_sources(session.sources,
                                               entry.cap_locals)
        with forbid_transfers():   # device-resident incl. the ⋈ exchange
            jax.block_until_ready(entry.fn(datas, counts))
        steady[strategy] = timeit(
            lambda: jax.block_until_ready(entry.fn(datas, counts)),
            repeats=max(3, repeats), inner=10)
        rows.append({
            "config": f"join_exchange_{strategy}", "engine": engine,
            "dedup": dedup, "devices": n_dev,
            "child_rows": n_child, "parent_rows": n_parent,
            "kg_triples": stats["kg_triples"],
            "steady_s": round(steady[strategy], 5),
            "triples_per_s": round(stats["kg_triples"]
                                   / max(steady[strategy], 1e-9)),
            "host_transfers_in_closure": 0,
            "bitwise_equal_single_device": True})
    assert np.array_equal(kg_by_strategy["gather"].to_codes(),
                          kg_by_strategy["repartition"].to_codes())

    auto_big = KGEngine(big(), config=EngineConfig(
        engine=engine, dedup=dedup, mesh=mesh, join_exchange="auto"))
    auto_big.create_kg()
    big_choice = _auto_choices(auto_big)
    assert big_choice == (["repartition"] if n_dev > 1 else ["gather"]), \
        f"auto chose {big_choice} on the large-parent config ({n_dev} dev)"
    # fixed smoke-sized group-B (small parent): auto must keep gathering
    auto_small = KGEngine(make_group_b_dis(80, 0.6, seed=0),
                          config=EngineConfig(engine=engine, dedup=dedup,
                                              mesh=mesh,
                                              join_exchange="auto"))
    auto_small.create_kg()
    small_choice = _auto_choices(auto_small)
    assert small_choice == ["gather"], \
        f"auto chose {small_choice} on the small-parent group-B config"
    rows.append({
        "config": "join_exchange_auto", "engine": engine, "dedup": dedup,
        "devices": n_dev, "large_parent_choice": big_choice[0],
        "group_b_choice": small_choice[0],
        "gather_steady_s": round(steady["gather"], 5),
        "repartition_steady_s": round(steady["repartition"], 5),
        "repartition_speedup": round(steady["gather"]
                                     / max(steady["repartition"], 1e-9), 3)})
    return rows


def run(scale: float = 1.0, engine: str = "sdm", dedup: str = "hash",
        repeats: int = 3) -> List[Dict]:
    n = max(32, int(4000 * scale))
    # first: its child processes need the device this process has not
    # touched yet
    warm_start = check_warm_process_cold_start(max(16, n // 4))
    rows = [
        bench_cold_vs_cached(n, engine, dedup, repeats),
        bench_ingest(n, engine, dedup, batches=max(2, repeats),
                     batch_rows=max(4, n // 16)),
        check_overflow_recompile(max(16, n // 4), engine, dedup),
        check_distributed_closure_reuse(max(16, n // 4), dedup),
        check_fused_mesh_device_resident(max(16, n // 4), engine, dedup,
                                         repeats),
        warm_start,
        check_verifier_overhead(max(16, n // 4), engine, dedup, repeats),
    ]
    rows.extend(check_join_exchange_crossover(n, engine, dedup, repeats))
    rows.append({"config": "plan_cache", **plan_cache_stats()})
    return rows


def main(argv=None) -> List[Dict]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny cells, correctness gates only (CI)")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--engine", default="sdm")
    ap.add_argument("--dedup", default="hash")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    rows = run(scale=0.02 if args.smoke else args.scale, engine=args.engine,
               dedup=args.dedup, repeats=1 if args.smoke else args.repeats)
    save_rows("engine", rows)
    print_csv(rows)
    return rows


if __name__ == "__main__":
    main()
