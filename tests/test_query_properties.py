"""Property-based KGQuery verification (hypothesis — test extra):

    engine.query(bgp) == naive host-side pattern matching over to_codes(),

bit-identically, for randomized *connected* BGPs (1-3 chained patterns
with variable/constant positions drawn from the live KG plus off-KG
constants for empty results, eq/neq filters, random projections), on
whatever device topology the process was launched with: single device, or
a full ``("data",)`` mesh when ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` (the CI legs run this file under both). Also covers the
all-constant existence form and re-querying across ``ingest()``.

The seeded non-hypothesis suite in ``test_query.py`` covers the same
invariants in environments without the extra.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="test extra: pip install -r "
                    "requirements.txt")
import jax
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.api import (EngineConfig, KGEngine, Query, QueryFilter,
                       TriplePattern)
from repro.data.synthetic import make_group_b_dis
from repro.query.oracle import bgp_oracle
from repro.relalg import Table

_SESSION = {}


def _session():
    """One engine + KG per process, shared across examples (the query tier
    caches per structural key anyway; fresh engines would only re-pay KG
    creation). Meshed over every device when more than one is visible."""
    if not _SESSION:
        mesh = None
        if len(jax.devices()) > 1:
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((len(jax.devices()),), ("data",))
        cfg = EngineConfig(engine="sdm", dedup="hash", mesh=mesh)
        eng = KGEngine(make_group_b_dis(64, 0.6, seed=11), config=cfg)
        kg, _ = eng.create_kg()
        _SESSION["eng"], _SESSION["kg"] = eng, kg
        _SESSION["codes"] = np.asarray(kg.to_codes())
    return _SESSION["eng"], _SESSION["kg"], _SESSION["codes"]


def _term_const(codes, draw_row, pos, bogus):
    if bogus:
        return (999_983, 999_979)
    row = codes[draw_row % len(codes)]
    cols = (0, 1) if pos == "s" else (3, 4)
    return (int(row[cols[0]]), int(row[cols[1]]))


def _pred_const(codes, draw_row, bogus):
    return 999_989 if bogus else int(codes[draw_row % len(codes)][2])


@st.composite
def bgps(draw):
    """A connected chain BGP: pattern i = (?v{i}, p_i, ?v{i+1}); the free
    ends (subject of the first, object of the last) and every predicate
    may independently become constants drawn from the KG (or off-KG codes
    for guaranteed-empty branches)."""
    _eng, _kg, codes = _session()
    n = draw(st.integers(1, 3))
    rows = draw(st.lists(st.integers(0, 10_000), min_size=2 * n + 2,
                         max_size=2 * n + 2))
    pats = []
    term_vars = [f"?v{i}" for i in range(n + 1)]
    for i in range(n):
        s, o = term_vars[i], term_vars[i + 1]
        if i == 0 and draw(st.booleans()):
            s = _term_const(codes, rows[2 * i], "s", draw(
                st.integers(0, 9)) == 0)
        if i == n - 1 and n > 1 and draw(st.booleans()):
            o = _term_const(codes, rows[2 * i + 1], "o", draw(
                st.integers(0, 9)) == 0)
        kind = draw(st.sampled_from(["var", "shared_var", "const"]))
        p = {"var": f"?p{i}", "shared_var": "?p0"}.get(kind) \
            or _pred_const(codes, rows[2 * n], draw(
                st.integers(0, 9)) == 0)
        pats.append(TriplePattern(s, p, o))
    q0 = Query(patterns=pats)       # bound-variable inventory pre-filters
    kinds = q0.var_kinds()
    names = sorted(kinds)
    filters = []
    for _ in range(draw(st.integers(0, 2))):
        if not names:
            break
        name = draw(st.sampled_from(names))
        op = draw(st.sampled_from(["eq", "neq"]))
        bogus = draw(st.integers(0, 9)) == 0
        term = (_pred_const(codes, rows[2 * n + 1], bogus)
                if kinds[name] == "pred"
                else _term_const(codes, rows[2 * n + 1], "o", bogus))
        filters.append(QueryFilter(f"?{name}", op, term))
    project = None
    if names and draw(st.booleans()):
        k = draw(st.integers(1, len(names)))
        project = tuple(f"?{v}" for v in draw(st.permutations(names))[:k])
    return Query(patterns=pats, filters=tuple(filters), project=project)


@settings(max_examples=25, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.data_too_large])
@given(q=bgps())
def test_random_bgp_matches_host_oracle(q):
    eng, kg, _codes = _session()
    res = eng.query(q)
    got = (np.unique(np.asarray(res.to_codes()), axis=0) if res.count
           else np.zeros((0, len(res.attrs)), np.int32))
    np.testing.assert_array_equal(got, bgp_oracle(kg, q))
    assert res.attrs == q.answer_attrs()


@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.data_too_large])
@given(row=st.integers(0, 10_000), miss=st.booleans())
def test_all_constant_existence_matches_oracle(row, miss):
    eng, kg, codes = _session()
    r = codes[row % len(codes)]
    q = Query(patterns=[TriplePattern(
        (int(r[0]), int(r[1])),
        999_989 if miss else int(r[2]),
        (int(r[3]), int(r[4])))])
    res = eng.query(q)
    got = (np.unique(np.asarray(res.to_codes()), axis=0) if res.count
           else np.zeros((0, len(res.attrs)), np.int32))
    np.testing.assert_array_equal(got, bgp_oracle(kg, q))
    assert int(res.count) == (0 if miss else 1)


@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.data_too_large])
@given(seed=st.integers(0, 5), factor=st.integers(1, 4))
def test_query_consistent_across_ingest(seed, factor):
    """The same BGP re-queried after ingest() answers over the grown KG —
    bit-identical to the oracle on the new snapshot both times."""
    mesh = None
    if len(jax.devices()) > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((len(jax.devices()),), ("data",))
    eng = KGEngine(make_group_b_dis(24, 0.6, seed=seed),
                   config=EngineConfig(engine="sdm", dedup="hash",
                                       mesh=mesh))
    kg, _ = eng.create_kg()
    q = Query(patterns=[TriplePattern("?s", "?p", "?o"),
                        TriplePattern("?o", "?p2", "?o2")])
    for snapshot in (kg,):
        res = eng.query(q)
        got = (np.unique(np.asarray(res.to_codes()), axis=0) if res.count
               else np.zeros((0, len(res.attrs)), np.int32))
        np.testing.assert_array_equal(got, bgp_oracle(snapshot, q))
    ext = make_group_b_dis(24 * factor, 0.6, seed=seed + 17)
    recs = ext.sources["gene"].to_records(ext.vocab)
    delta = Table.from_records(
        recs, eng.sources["gene"].attrs, eng.vocab)
    kg2, _ = eng.ingest({"gene": delta})
    res2 = eng.query(q)
    got2 = (np.unique(np.asarray(res2.to_codes()), axis=0) if res2.count
            else np.zeros((0, len(res2.attrs)), np.int32))
    np.testing.assert_array_equal(got2, bgp_oracle(kg2, q))
