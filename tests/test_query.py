"""KGQuery: the jitted BGP query engine behind ``KGEngine.query``.

Covers the spec validation (named errors at construction), the lowering
(shared KG Scan, disconnected-BGP rejection, always-δ roots), single-device
execution against a naive host-side pattern-match oracle over
``to_codes()`` (joins, filters, projection, all-constant existence, empty
results, cross-ingest), the query plan-cache tier (repeat query = zero
re-trace), ``explain_query``, the ``EngineConfig`` consolidation
(construction-time validation, legacy-kwarg deprecation, config/kwarg
exclusivity), the persistent-store round trip in a fresh process, and an
8-virtual-device subprocess leg proving bit-identity across
{gather, repartition, auto}.
"""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.api import (EngineConfig, KGEngine, Query, QueryFilter,
                       TriplePattern)
from repro.data.synthetic import make_group_b_dis
from repro.plan.ir import Distinct, Scan, iter_nodes
from repro.query import KG_SOURCE, lower_query
from repro.query.oracle import bgp_oracle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def assert_query_matches_oracle(eng, kg, q):
    res = eng.query(q)
    got = np.unique(np.asarray(res.to_codes()), axis=0) \
        if res.count else np.zeros((0, len(res.attrs)), np.int32)
    want = bgp_oracle(kg, q)
    np.testing.assert_array_equal(got, want)
    # δ root: the device answer itself is already duplicate-free
    assert len(np.unique(np.asarray(res.to_codes()), axis=0)) == res.count \
        or res.count == 0
    return res


def _mk_engine(n=48, seed=1, **cfg):
    dis = make_group_b_dis(n, 0.6, seed=seed)
    eng = KGEngine(dis, config=EngineConfig(engine="sdm", dedup="hash",
                                            **cfg))
    kg, _ = eng.create_kg()
    return eng, kg


# ---------------------------------------------------------------------------
# spec validation (named errors, at construction)
# ---------------------------------------------------------------------------

def test_spec_validation_named_errors():
    with pytest.raises(ValueError, match="bad query variable"):
        TriplePattern("?1bad", "?p", "?o")
    with pytest.raises(ValueError, match="r_"):
        TriplePattern("?r_x", "?p", "?o")     # ⋈ rename-suffix collision
    with pytest.raises(ValueError, match="bad term constant"):
        TriplePattern((1,), "?p", "?o")
    with pytest.raises(ValueError, match="bad predicate constant"):
        TriplePattern("?s", (1, 2), "?o")
    with pytest.raises(ValueError, match="bad predicate constant"):
        TriplePattern("?s", True, "?o")       # bools are not codes
    with pytest.raises(ValueError, match="empty query"):
        Query(patterns=[])
    with pytest.raises(ValueError, match="both predicate and term"):
        Query(patterns=[TriplePattern("?x", "?x", "?o")])
    with pytest.raises(ValueError, match="unknown variable"):
        Query(patterns=[TriplePattern("?s", "?p", "?o")],
              filters=[QueryFilter("?zzz", "eq", (1, 2))])
    with pytest.raises(ValueError, match="single predicate code"):
        Query(patterns=[TriplePattern("?s", "?p", "?o")],
              filters=[QueryFilter("?p", "eq", (1, 2))])
    with pytest.raises(ValueError, match="filter on"):
        Query(patterns=[TriplePattern("?s", "?p", "?o")],
              filters=[QueryFilter("?s", "eq", 3)])
    with pytest.raises(ValueError, match="bad filter op"):
        QueryFilter("?s", "lt", (1, 2))
    with pytest.raises(ValueError, match="empty projection"):
        Query(patterns=[TriplePattern("?s", "?p", "?o")], project=())
    with pytest.raises(ValueError, match="not bound"):
        Query(patterns=[TriplePattern("?s", "?p", "?o")], project=("?q",))
    with pytest.raises(ValueError, match="duplicate variable"):
        Query(patterns=[TriplePattern("?s", "?p", "?o")],
              project=("?s", "?s"))


def test_lowering_shape_and_disconnected_bgps():
    q = Query(patterns=[TriplePattern("?s", "?p", "?o"),
                        TriplePattern("?o", "?p2", "?o2")])
    plan = lower_query(q)
    assert isinstance(plan.root, Distinct)    # always SELECT DISTINCT
    scans = [n for n in iter_nodes(plan.root) if isinstance(n, Scan)]
    assert len(set(map(id, scans))) == 1      # hash-consed: one KG Scan
    assert scans[0].source == KG_SOURCE
    assert plan.out_attrs == q.answer_attrs()
    with pytest.raises(ValueError, match="disconnected BGP"):
        lower_query(Query(patterns=[TriplePattern("?a", "?p", "?b"),
                                    TriplePattern("?x", "?q", "?y")]))
    with pytest.raises(ValueError, match="disconnected BGP"):
        lower_query(Query(patterns=[TriplePattern((0, 1), 2, (0, 3)),
                                    TriplePattern((0, 1), 2, (0, 4))]))
    with pytest.raises(ValueError, match="disconnected BGP"):
        lower_query(Query(patterns=[TriplePattern("?a", "?p", "?b"),
                                    TriplePattern((0, 1), 2, (0, 3))]))


# ---------------------------------------------------------------------------
# EngineConfig (satellites: consolidation + construction-time validation)
# ---------------------------------------------------------------------------

def test_engine_config_named_validation_errors():
    with pytest.raises(ValueError, match="unknown engine"):
        EngineConfig(engine="marklogic")
    with pytest.raises(ValueError, match="unknown dedup strategy"):
        EngineConfig(dedup="bloom")           # previously failed mid-run
    with pytest.raises(ValueError, match="unknown annotate mode"):
        EngineConfig(mode="guess")
    with pytest.raises(ValueError, match="bad slack"):
        EngineConfig(slack=0.0)               # would truncate on first run
    with pytest.raises(ValueError, match="bad slack"):
        EngineConfig(slack=float("nan"))
    with pytest.raises(ValueError, match="bad slack"):
        EngineConfig(slack="lots")
    with pytest.raises(ValueError, match="bad mesh_axis"):
        EngineConfig(mesh_axis="")
    with pytest.raises(ValueError, match="bad mesh_axis"):
        EngineConfig(mesh_axis=7)
    with pytest.raises(ValueError, match="unknown join exchange"):
        EngineConfig(join_exchange="broadcast")
    with pytest.raises(ValueError, match="unknown verify level"):
        EngineConfig(verify="paranoid")
    assert EngineConfig(slack=2).slack == 2.0  # coerced to float


def test_engine_config_mesh_axis_must_be_mesh_axis():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="not an axis of the mesh"):
        EngineConfig(mesh=mesh, mesh_axis="model")
    EngineConfig(mesh=mesh, mesh_axis="data")  # ok


def test_engine_constructor_validates_before_planning():
    dis = make_group_b_dis(16, 0.6, seed=0)
    with pytest.raises(ValueError, match="unknown dedup strategy"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            KGEngine(dis, dedup="bloom")
    with pytest.raises(ValueError, match="bad slack"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            KGEngine(dis, slack=-1)
    with pytest.raises(ValueError, match="bad mesh_axis"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            KGEngine(dis, mesh_axis="")


def test_legacy_kwargs_deprecation_and_exclusivity():
    import repro.api.engine as engine_mod
    dis = make_group_b_dis(16, 0.6, seed=0)
    engine_mod._WARNED_LEGACY.clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        KGEngine(dis, engine="sdm", dedup="hash")
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    # warn-once per combination
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        KGEngine(dis, engine="sdm", dedup="hash")
    assert not any(issubclass(x.category, DeprecationWarning) for x in w)
    # bare construction and config= never warn
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        KGEngine(dis)
        KGEngine(dis, config=EngineConfig(engine="rmlmapper"))
    assert not any(issubclass(x.category, DeprecationWarning) for x in w)
    with pytest.raises(ValueError, match="not both"):
        KGEngine(dis, engine="sdm", config=EngineConfig())
    with pytest.raises(TypeError, match="EngineConfig"):
        KGEngine(dis, config={"engine": "sdm"})


def test_config_is_the_cache_key_input():
    dis = make_group_b_dis(16, 0.6, seed=0)
    e1 = KGEngine(dis, config=EngineConfig(engine="sdm", dedup="hash"))
    e2 = KGEngine(dis, config=EngineConfig(engine="sdm", dedup="lex"))
    assert e1.config.cache_sig() != e2.config.cache_sig()
    assert e1._key(e1.sources) != e2._key(e2.sources)
    e3 = KGEngine(dis, config=EngineConfig(engine="sdm", dedup="hash"))
    assert e1._key(e1.sources) == e3._key(e3.sources)


# ---------------------------------------------------------------------------
# single-device execution vs the oracle
# ---------------------------------------------------------------------------

def test_single_pattern_full_scan_matches_oracle():
    eng, kg = _mk_engine()
    assert_query_matches_oracle(
        eng, kg, Query(patterns=[TriplePattern("?s", "?p", "?o")]))


def test_join_filters_projection_match_oracle():
    eng, kg = _mk_engine()
    codes = np.asarray(kg.to_codes())
    p0 = int(codes[0][2])
    q = Query(patterns=[TriplePattern("?s", "?p", "?o"),
                        TriplePattern("?o", "?p2", "?o2")],
              filters=[QueryFilter("?p", "eq", p0)],
              project=("?s", "?o2"))
    res = assert_query_matches_oracle(eng, kg, q)
    assert res.attrs == ("s__t", "s__v", "o2__t", "o2__v")
    # term-var neq lowers to the disjoint ∪ — still oracle-identical
    o0 = (int(codes[0][3]), int(codes[0][4]))
    assert_query_matches_oracle(
        eng, kg, Query(patterns=[TriplePattern("?s", "?p", "?o")],
                       filters=[QueryFilter("?o", "neq", o0)]))
    assert_query_matches_oracle(
        eng, kg, Query(patterns=[TriplePattern("?s", "?p", "?o")],
                       filters=[QueryFilter("?p", "neq", p0)]))


def test_constant_positions_and_repeated_var_match_oracle():
    eng, kg = _mk_engine()
    codes = np.asarray(kg.to_codes())
    row = codes[len(codes) // 2]
    assert_query_matches_oracle(
        eng, kg,
        Query(patterns=[TriplePattern((int(row[0]), int(row[1])),
                                      "?p", "?o")]))
    # repeated variable within one pattern (?x ?p ?x)
    assert_query_matches_oracle(
        eng, kg, Query(patterns=[TriplePattern("?x", "?p", "?x")]))


def test_all_constant_existence_and_empty_results():
    eng, kg = _mk_engine()
    row = np.asarray(kg.to_codes())[0]
    hit = Query(patterns=[TriplePattern((int(row[0]), int(row[1])),
                                        int(row[2]),
                                        (int(row[3]), int(row[4])))])
    res = eng.query(hit)
    assert int(res.count) == 1 and res.attrs == kg.attrs
    np.testing.assert_array_equal(np.asarray(res.to_codes())[0], row)
    miss = Query(patterns=[TriplePattern((int(row[0]), int(row[1])),
                                         987654, "?o")])
    assert int(eng.query(miss).count) == 0


def test_query_after_ingest_sees_new_kg():
    from repro.relalg import Table
    eng, kg = _mk_engine(n=24, seed=3)
    q = Query(patterns=[TriplePattern("?s", "?p", "?o")])
    before = assert_query_matches_oracle(eng, kg, q)
    ext = make_group_b_dis(24, 0.6, seed=9)
    recs = ext.sources["gene"].to_records(ext.vocab)
    delta = Table.from_records(recs, eng.sources["gene"].attrs, eng.vocab)
    kg2, _ = eng.ingest({"gene": delta})
    after = assert_query_matches_oracle(eng, kg2, q)
    assert int(after.count) >= int(before.count)


# ---------------------------------------------------------------------------
# the query plan-cache tier
# ---------------------------------------------------------------------------

def test_repeat_query_hits_cache_zero_retrace():
    from repro.api import clear_plan_cache
    clear_plan_cache()          # isolate from the process-global cache
    eng, kg = _mk_engine()
    q = Query(patterns=[TriplePattern("?s", "?p", "?o"),
                        TriplePattern("?o", "?p2", "?o2")])
    r1 = eng.query(q)
    fn1 = eng._q_last["entry"].fn
    # a structurally identical (but distinct) Query object: same key
    q2 = Query(patterns=[TriplePattern("?s", "?p", "?o"),
                         TriplePattern("?o", "?p2", "?o2")])
    r2 = eng.query(q2)
    st = eng.stats()["query"]
    assert st["cache_hits"] == 1 and st["cache_misses"] == 1
    assert st["recompiles"] == 0 and st["last_cache_hit"]
    assert eng._q_last["entry"].fn is fn1      # zero re-trace: same closure
    np.testing.assert_array_equal(r1.to_codes(), r2.to_codes())
    # a different query is a different key
    eng.query(Query(patterns=[TriplePattern("?s", "?p", "?o")]))
    assert eng.stats()["query"]["cache_misses"] == 2


def test_query_cache_shared_across_sessions():
    q = Query(patterns=[TriplePattern("?s", "?p", "?o")])
    e1, _ = _mk_engine(seed=5)
    e1.query(q)
    e2, _ = _mk_engine(seed=5)
    e2.query(q)
    assert e2.stats()["query"]["cache_hits"] == 1


def test_explain_query_renders_tree():
    eng, kg = _mk_engine()
    q = Query(patterns=[TriplePattern("?s", "?p", "?o"),
                        TriplePattern("?o", "?p2", "?o2")])
    text = eng.explain_query(q)
    assert "scan __kg__" in text
    assert "δ" in text and "⋈" in text
    assert "verify: ok" in text
    assert "rows=" in text and "cap=" in text


def test_verify_full_audits_query_closures():
    from repro.api import clear_plan_cache
    clear_plan_cache()          # verify level is not part of the cache key
    eng, kg = _mk_engine(verify="full")
    q = Query(patterns=[TriplePattern("?s", "?p", "?o"),
                        TriplePattern("?o", "?p2", "?o2")])
    assert_query_matches_oracle(eng, kg, q)
    assert eng.stats()["verify"]["audits"] >= 2  # creation + query builds


# ---------------------------------------------------------------------------
# persistent store round trip (fresh process)
# ---------------------------------------------------------------------------

def _run_with_devices(n_devices, code, *args):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code] + list(args), env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr}\nstdout:\n{out.stdout}"
    return out.stdout


_STORE_CODE = """
import sys
import numpy as np
from repro.api import EngineConfig, KGEngine, Query, TriplePattern
from repro.data.synthetic import make_group_b_dis
root, role = sys.argv[1], sys.argv[2]
cfg = EngineConfig(engine="sdm", dedup="hash", plan_store=root)
eng = KGEngine(make_group_b_dis(48, 0.6, seed=1), config=cfg)
eng.create_kg()
q = Query(patterns=[TriplePattern("?s", "?p", "?o"),
                    TriplePattern("?o", "?p2", "?o2")])
res = eng.query(q)
st = eng.stats()["query"]
if role == "reader":
    assert st["store_hits"] == 1, st       # rehydrated, not recompiled
    assert eng._q_last["entry"].origin == "store"
print("RESULT", np.asarray(res.to_codes()).tolist())
"""


def test_query_store_roundtrip_fresh_process(tmp_path):
    root = str(tmp_path / "plans")
    out_w = _run_with_devices(1, _STORE_CODE, root, "writer")
    out_r = _run_with_devices(1, _STORE_CODE, root, "reader")
    assert out_w.splitlines()[-1] == out_r.splitlines()[-1]


# ---------------------------------------------------------------------------
# 8-virtual-device leg: {gather, repartition, auto} × bit-identity
# ---------------------------------------------------------------------------

_MESH_CODE = """
import numpy as np
from repro.api import EngineConfig, KGEngine, Query, QueryFilter, TriplePattern
from repro.launch.mesh import make_mesh
from repro.data.synthetic import make_group_b_dis
import sys; sys.path.insert(0, {testdir!r})
from test_query import bgp_oracle

mk = lambda: make_group_b_dis(96, 0.6, seed=7)
q = Query(patterns=[TriplePattern("?s", "?p", "?o"),
                    TriplePattern("?o", "?p2", "?o2")])
eng1 = KGEngine(mk(), config=EngineConfig(engine="sdm", dedup="hash"))
kg1, _ = eng1.create_kg()
ref = np.asarray(eng1.query(q).to_codes())
np.testing.assert_array_equal(np.unique(ref, axis=0), bgp_oracle(kg1, q))
mesh = make_mesh((8,), ("data",))
for exch in ("gather", "repartition", "auto"):
    eng = KGEngine(mk(), config=EngineConfig(engine="sdm", dedup="hash",
                                             mesh=mesh, join_exchange=exch,
                                             verify="full"))
    eng.create_kg()
    got = np.asarray(eng.query(q).to_codes())
    np.testing.assert_array_equal(got, ref), exch
    # repeat: the query tier caches per (query, mesh sig)
    eng.query(q)
    assert eng.stats()["query"]["cache_hits"] == 1, exch
print("OK", len(ref))
"""


def test_multi_device_query_bit_identical_all_exchanges():
    code = _MESH_CODE.format(testdir=os.path.join(REPO, "tests"))
    out = _run_with_devices(8, code)
    assert "OK" in out
