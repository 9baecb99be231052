"""Fused mesh-plan tests: the whole pipeline inside one shard_map.

Single-process cases run on a 1-device mesh (the collective path with
n_shards=1); multi-device cases run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` following
``test_distributed.py`` — except under the CI multi-device matrix leg,
where the main process itself already sees 8 devices and the in-process
tests exercise the real collectives.
"""
import os
import subprocess
import sys

import numpy as np
import jax
import pytest

from repro.api import KGEngine
from repro.core import parse_dis
from repro.core.rdfizer import RDFizer
from repro.data.synthetic import make_group_b_dis
from repro.launch.mesh import make_mesh
from repro.relalg import Table, forbid_transfers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh():
    """All available devices on one ``data`` axis (1 locally, 8 on the CI
    multi-device leg — the same tests cover both)."""
    return make_mesh((jax.device_count(),), ("data",))


def _oracle(dis, sources, engine="sdm", dedup=None):
    acc = dis.copy()
    acc.sources = dict(sources)
    kg, _raw = RDFizer(acc, engine, dedup=dedup)()
    return kg


def _reencode(src_dis, name, vocab, attrs):
    recs = src_dis.sources[name].to_records(src_dis.vocab)
    return Table.from_records(recs, attrs, vocab)


# ---------------------------------------------------------------------------
# bit-identity: fused mesh == single-device planned == eager oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["sdm", "rmlmapper"])
@pytest.mark.parametrize("dedup", ["hash", "lex"])
def test_fused_mesh_bit_identical_across_engines_and_dedup(engine, dedup):
    mk = lambda: make_group_b_dis(96, 0.6, seed=21)  # noqa: E731
    kg_single, stats_single = KGEngine(mk(), engine=engine,
                                       dedup=dedup).create_kg()
    kg_mesh, stats = KGEngine(mk(), engine=engine, dedup=dedup,
                              mesh=_mesh()).create_kg()
    np.testing.assert_array_equal(kg_mesh.to_codes(), kg_single.to_codes())
    # the mesh raw count matches single-device semantics exactly (global
    # per-map δ under sdm, blind generation under rmlmapper) — interior δ
    # is a global repartition δ, so per-shard counts sum to the global ones
    assert stats["raw_triples"] == stats_single["raw_triples"]
    kg_eager = _oracle(mk(), mk().sources, engine, dedup)
    assert kg_mesh.row_set() == kg_eager.row_set()
    assert stats["recompiles"] == 0


def test_mesh_and_single_device_plans_do_not_share_cache_entries():
    mk = lambda: make_group_b_dis(48, 0.5, seed=22)  # noqa: E731
    _, s1 = KGEngine(mk()).create_kg()
    _, s2 = KGEngine(mk(), mesh=_mesh()).create_kg()
    assert not s2["plan_cache_hit"]     # mesh sig is part of the key
    _, s3 = KGEngine(mk(), mesh=_mesh()).create_kg()
    assert s3["plan_cache_hit"]         # same mesh sig hits


# ---------------------------------------------------------------------------
# device residency: no host gathers of intermediate triples
# ---------------------------------------------------------------------------

def test_fused_closure_runs_without_host_transfers():
    eng = KGEngine(make_group_b_dis(80, 0.6, seed=23), mesh=_mesh())
    eng.create_kg()
    entry = eng._last["entry"]
    datas, counts = eng._shard_sources(eng.sources, entry.cap_locals)
    with forbid_transfers():       # the whole pipeline incl. the sink δ
        out = entry.fn(datas, counts)
        jax.block_until_ready(out)


def test_session_reshards_only_replaced_sources():
    eng = KGEngine(make_group_b_dis(64, 0.6, seed=24), mesh=_mesh())
    eng.create_kg()
    cached = {name: hit[2] for name, hit in eng._shard_cache.items()}
    eng.run()                       # nothing replaced: same device blocks
    for name, hit in eng._shard_cache.items():
        assert hit[2] is cached[name]
    delta_src = make_group_b_dis(8, 0.5, seed=240)
    eng.ingest({"gene": _reencode(delta_src, "gene", eng.vocab,
                                  eng.sources["gene"].attrs)})
    assert eng._shard_cache["gene"][2] is not cached["gene"]   # re-sharded
    assert eng._shard_cache["chrom"][2] is cached["chrom"]     # untouched
    # a DIRECT source replacement (no ingest) must also re-shard: the
    # cache is identity-keyed, not ingest-keyed
    kg_before, _ = eng.create_kg()
    dis2 = make_group_b_dis(64, 0.6, seed=99)
    eng.sources["gene"] = _reencode(dis2, "gene", eng.vocab,
                                    eng.sources["gene"].attrs)
    kg_after, _ = eng.create_kg()
    assert eng._shard_cache["gene"][2] is not cached["gene"]
    kg_ref = _oracle(eng._dis, eng.sources)
    np.testing.assert_array_equal(kg_after.to_codes(), kg_ref.to_codes())


# ---------------------------------------------------------------------------
# ingest: shard-local capacity buckets, recompile-on-overflow
# ---------------------------------------------------------------------------

def test_mesh_ingest_within_bucket_reuses_closure():
    dis = make_group_b_dis(100, 0.6, seed=25)
    eng = KGEngine(dis, mesh=_mesh())
    eng.create_kg()
    delta_src = make_group_b_dis(8, 0.5, seed=250)
    kg, stats = eng.ingest(
        {"gene": _reencode(delta_src, "gene", eng.vocab,
                           dis.sources["gene"].attrs)})
    assert stats["recompiles"] == 0 and stats["plan_cache_hit"]
    kg_ref = _oracle(dis, eng.sources)
    np.testing.assert_array_equal(kg.to_codes(), kg_ref.to_codes())


def test_mesh_ingest_crossing_local_bucket_one_recompile_no_truncation():
    """A 16x extension outgrows every shard-local bucket: the session must
    rebuild its shard-local annotations (NOT reuse host-global caps),
    recompile exactly once, and produce the untruncated bit-exact KG."""
    dis = make_group_b_dis(64, 0.6, seed=26)
    eng = KGEngine(dis, mesh=_mesh())
    eng.create_kg()
    assert eng.stats()["recompiles"] == 0
    big = make_group_b_dis(16 * 64, 0.6, seed=260)
    kg, stats = eng.ingest(
        {"gene": _reencode(big, "gene", eng.vocab,
                           dis.sources["gene"].attrs)})
    assert stats["recompiles"] == 1
    kg_ref = _oracle(dis, eng.sources)
    np.testing.assert_array_equal(kg.to_codes(), kg_ref.to_codes())


def test_mesh_interior_overflow_recompiles_not_truncates():
    """Rows that stay inside the source bucket but blow past an interior
    shard-local δ capacity must flag overflow and recompile — never
    truncate. On one shard the 14 distinct values overflow the plan-time
    δ cap of 8 (one recompile); with more shards the per-shard blocks are
    small enough that every shard-local δ fits its cap and no recompile is
    *needed* — either way the KG must be complete and bit-exact."""
    values = [f"v{i % 4}" for i in range(40)]
    spec = {"sources": {"s": {"attrs": ["a", "b"], "records": [
        {"a": v, "b": v} for v in values]}},
        "maps": [{"name": "m", "source": "s",
                  "subject": {"template": "http://ex/T/{a}",
                              "class": "ex:C"},
                  "poms": [{"predicate": "ex:p",
                            "object": {"reference": "b"}}]}]}
    dis = parse_dis(spec)
    eng = KGEngine(dis, mesh=_mesh())
    eng.create_kg()
    fresh = [{"a": f"w{i}", "b": f"w{i}"} for i in range(10)]
    kg, stats = eng.ingest({"s": Table.from_records(fresh, ("a", "b"),
                                                    eng.vocab)})
    if jax.device_count() == 1:
        assert stats["recompiles"] == 1
    else:   # per-shard blocks fit: cached closure, zero recompiles
        assert stats["recompiles"] == 0 and stats["plan_cache_hit"]
    assert stats["kg_triples"] == 2 * (4 + 10)   # nothing truncated
    kg_ref = _oracle(dis, eng.sources)
    np.testing.assert_array_equal(kg.to_codes(), kg_ref.to_codes())


def test_repartition_overflow_recompiles_not_truncates():
    """Satellite of the ⋈ exchange work: a key-skewed ingest that blows
    past one shard's post-exchange join capacity (the Poisson-sized cap of
    ``annotate_local``) must trigger exactly ONE recompile — the
    ``safe_exchange`` rebuild whose caps are true bounds even under
    adversarial skew — and produce the bit-exact KG, never truncate.

    Seed: 40 child keys one row each; parent has one hot key (K1) with 16
    rows. The ingest adds 8 more K1 child rows *within* the child's source
    bucket, exploding the join total from 55 to 183 — past the plan-time
    cap on one device (exact total 55 → bucket 64) and past the hot
    shard's Poisson share on many."""
    child = [{"ID": i, "k": f"K{i}", "v": f"v{i}"} for i in range(40)]
    parent = [{"ID": i, "k": f"K{i}", "p": f"p{i}"} for i in range(40)]
    parent += [{"ID": 100 + i, "k": "K1", "p": f"hot{i}"} for i in range(15)]
    spec = {"sources": {
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
             "poms": []}]}
    dis = parse_dis(spec)
    eng = KGEngine(dis, mesh=_mesh(), join_exchange="repartition")
    eng.create_kg()
    assert eng.stats()["recompiles"] == 0    # the seed fits the Poisson caps
    fresh = [{"ID": 200 + i, "k": "K1", "v": f"w{i}"} for i in range(8)]
    kg, stats = eng.ingest({"child": Table.from_records(
        fresh, ("ID", "k", "v"), eng.vocab)})
    assert stats["recompiles"] == 1
    assert eng._last["entry"].safe_exchange
    kg_ref = _oracle(dis, eng.sources)
    np.testing.assert_array_equal(kg.to_codes(), kg_ref.to_codes())
    # the safe entry keeps serving: a re-run must not recompile again
    kg2, stats2 = eng.create_kg()
    assert stats2["recompiles"] == 1
    np.testing.assert_array_equal(kg2.to_codes(), kg.to_codes())


@pytest.mark.parametrize("engine", ["sdm", "rmlmapper"])
def test_mesh_ingest_sweep_bit_identical(engine):
    dis = make_group_b_dis(32, 0.6, seed=27)
    eng = KGEngine(dis, engine=engine, mesh=_mesh())
    eng.create_kg()
    for step in range(2):
        ext = make_group_b_dis(32 * (4 ** step), 0.6, seed=270 + step)
        deltas = {name: _reencode(ext, name, eng.vocab,
                                  dis.sources[name].attrs)
                  for name in ("gene", "chrom")}
        kg, _stats = eng.ingest(deltas)
        kg_ref = _oracle(dis, eng.sources, engine=engine)
        np.testing.assert_array_equal(kg.to_codes(), kg_ref.to_codes())


# ---------------------------------------------------------------------------
# multi-device (subprocess, like test_distributed.py)
# ---------------------------------------------------------------------------

def _run_with_devices(n_devices: int, code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr}\nstdout:\n{out.stdout}"
    return out.stdout


def test_multi_device_fused_mesh_bit_identical_and_device_resident():
    code = """
import jax, numpy as np
from repro.api import KGEngine
from repro.data.synthetic import make_group_b_dis
from repro.launch.mesh import make_mesh
from repro.relalg import forbid_transfers
mesh = make_mesh((8,), ("data",))
mk = lambda: make_group_b_dis(200, 0.6, seed=31)
kg_single, _ = KGEngine(mk()).create_kg()
eng = KGEngine(mk(), mesh=mesh)
kg_mesh, stats = eng.create_kg()
assert np.array_equal(kg_mesh.to_codes(), kg_single.to_codes()), "bit mismatch"
entry = eng._last["entry"]
datas, counts = eng._shard_sources(eng.sources, entry.cap_locals)
with forbid_transfers():
    out = entry.fn(datas, counts)
    jax.block_until_ready(out)
print("OK", int(kg_mesh.count))
"""
    out = _run_with_devices(8, code)
    assert "OK" in out


# ---------------------------------------------------------------------------
# the re-δ of a gathered mesh result: one jitted program per shape
# ---------------------------------------------------------------------------

def group_b_transcripts(n_rows: int, seed: int):
    """``make_group_b_dis`` with ``TripleMap1``'s subject map keyed by the
    gene source's transcript (``Transcript/{enst}``), so the KG grows with
    the rows and holds one ⋈ triple per matched transcript."""
    src = make_group_b_dis(n_rows, 0.75, seed=seed)
    spec = {"sources": {name: {"attrs": list(t.attrs),
                               "records": t.to_records(src.vocab)}
                        for name, t in src.sources.items()},
            "maps": [
                {"name": "TripleMap1", "source": "gene",
                 "subject": {"template":
                             "http://project-iasis.eu/Transcript/{enst}",
                             "class": "iasis:Transcript"},
                 "poms": [{"predicate": "iasis:isRelatedTo",
                           "object": {"parentTriplesMap": "TripleMap2",
                                      "joinCondition": {
                                          "child": "Genename",
                                          "parent": "Genename"}}}]},
                {"name": "TripleMap2", "source": "chrom",
                 "subject": {"template": "http://project-iasis.eu/"
                                         "Chromosome/{Chromosome}",
                             "class": "iasis:Chromosome"},
                 "poms": []}]}
    return parse_dis(spec)


def test_redistinct_program_cached_and_cleared():
    from repro.api import EngineConfig, clear_plan_cache, plan_cache_stats
    clear_plan_cache()
    config = EngineConfig(mesh=_mesh())
    kg0, _ = KGEngine(make_group_b_dis(64, 0.6, seed=26),
                      config=config).create_kg()
    assert plan_cache_stats()["redistinct_builds"] == 1
    kg1, _ = KGEngine(make_group_b_dis(64, 0.6, seed=26),
                      config=config).create_kg()
    assert plan_cache_stats()["redistinct_builds"] == 1
    np.testing.assert_array_equal(kg1.to_codes(), kg0.to_codes())
    clear_plan_cache()
    assert plan_cache_stats()["redistinct_builds"] == 0


_REDISTINCT_ONCE = """
import sys
import jax, numpy as np
sys.path.insert(0, {testdir!r})
from test_mesh_plan import group_b_transcripts
from repro.api import (EngineConfig, KGEngine, Query, TriplePattern,
                       plan_cache_stats)
from repro.launch.mesh import make_mesh
from repro.relalg import Table
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda e, s, **k: compiles.append(e)
    if e == "/jax/core/compile/backend_compile_duration" else None)
config = EngineConfig(mesh=make_mesh((4,), ("data",)), mode="bound")
base = group_b_transcripts(400, seed=41)
def session(seed):
    # a new dump of both sources, coded against one vocabulary
    dis = base.copy()
    other = group_b_transcripts(400, seed=seed)
    dis.sources = {{n: Table.from_records(t.to_records(other.vocab),
                                          t.attrs, dis.vocab)
                   for n, t in other.sources.items()}}
    return KGEngine(dis, config=config)
builds = []
for seed in (42, 43):
    kg, _ = session(seed).create_kg()
    builds.append(plan_cache_stats()["redistinct_builds"])
n = len(compiles)
eng = session(42)
kg, _ = eng.create_kg()
builds.append(plan_cache_stats()["redistinct_builds"])
assert builds == [1, 1, 1], builds
assert len(compiles) == n, "a rebuild in a warm bucket compiled"
q = Query(patterns=[TriplePattern("?s", "?p", "?o")])
ans = eng.query(q)
after_first = plan_cache_stats()["redistinct_builds"]
n = len(compiles)
again = eng.query(q)
assert plan_cache_stats()["redistinct_builds"] == after_first
assert len(compiles) == n, "a repeated mesh query compiled"
np.testing.assert_array_equal(again.to_codes(), ans.to_codes())
print("OK", builds, after_first)
"""


def test_multi_device_redistinct_built_once_per_bucket():
    """New mesh sessions over new dumps in one bucket build the re-δ
    program once, and a warm rebuild or a repeated mesh query compiles
    nothing."""
    out = _run_with_devices(4, _REDISTINCT_ONCE.format(
        testdir=os.path.join(REPO, "tests")))
    assert "OK [1, 1, 1]" in out


_TRANSCRIPTS_EXACT = """
import sys
import numpy as np
sys.path.insert(0, {testdir!r})
from test_mesh_plan import group_b_transcripts
from repro.api import EngineConfig, KGEngine
from repro.core.rdfizer import RDFizer
from repro.core.transform import apply_mapsdi_eager
from repro.launch.mesh import make_mesh
dedup = {dedup!r}
mk = lambda: group_b_transcripts(800, seed=44)
single, _ = KGEngine(mk(), config=EngineConfig(dedup=dedup)).create_kg()
mesh, _ = KGEngine(mk(), config=EngineConfig(
    dedup=dedup, mesh=make_mesh((4,), ("data",)), mode="bound",
    join_exchange="auto")).create_kg()
np.testing.assert_array_equal(mesh.to_codes(), single.to_codes())
pre, _ = apply_mapsdi_eager(mk(), dedup=dedup)
eager, _ = RDFizer(pre, "sdm", dedup=dedup)()
assert mesh.row_set() == eager.row_set()
print("OK", int(mesh.count))
"""


@pytest.mark.parametrize("dedup", ["hash", "lex"])
def test_multi_device_transcript_group_b_matches_single_device(dedup):
    """The Group B mapping keyed by transcript: the 4-device mesh KG is
    bit-identical to the single-device KG and holds the eager oracle's
    rows; it grows with the rows (about one ⋈ triple per row)."""
    out = _run_with_devices(4, _TRANSCRIPTS_EXACT.format(
        testdir=os.path.join(REPO, "tests"), dedup=dedup))
    assert int(out.split("OK ")[1]) > 800
