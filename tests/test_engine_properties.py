"""Property-based KGEngine verification (hypothesis — test extra):

    engine.ingest(extension) == fresh eager run over seed + extension,
    bit-identically, for extensions 1x-16x the seed size,

with the recompile counter bounded by the number of capacity-bucket
crossings. The seeded non-hypothesis sweep in ``test_engine.py`` covers
the same invariants in environments without the extra.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="test extra: pip install -r "
                    "requirements.txt")
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.api import KGEngine, store_key
from repro.api.store import canonical
from repro.core.rdfizer import RDFizer
from repro.data.synthetic import make_group_b_dis
from repro.relalg import Table


def _oracle(dis, sources, engine="sdm", dedup=None):
    acc = dis.copy()
    acc.sources = dict(sources)
    kg, _raw = RDFizer(acc, engine, dedup=dedup)()
    return kg


def _reencode(src_dis, name, vocab, attrs):
    recs = src_dis.sources[name].to_records(src_dis.vocab)
    return Table.from_records(recs, attrs, vocab)


@settings(max_examples=12, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.data_too_large])
@given(factor=st.integers(1, 16), seed=st.integers(0, 7),
       engine=st.sampled_from(["rmlmapper", "sdm"]),
       dedup=st.sampled_from(["lex", "hash"]),
       both_sources=st.booleans())
def test_ingest_extension_bit_identical_to_fresh_run(factor, seed, engine,
                                                     dedup, both_sources):
    """Micro-batch ingestion of a 1x-16x extension produces exactly the KG
    a from-scratch eager evaluation of the accumulated sources would."""
    dis = make_group_b_dis(24, 0.6, seed=seed)
    eng = KGEngine(dis, engine=engine, dedup=dedup)
    eng.create_kg()
    # the plan cache is process-wide: create_kg may already have rebuilt a
    # same-bucket plan cached by an earlier session over other data, so the
    # ingest's own recompiles are counted from here
    recompiles_before = eng.recompiles
    ext = make_group_b_dis(24 * factor, 0.6, seed=seed + 31)
    names = ("gene", "chrom") if both_sources else ("gene",)
    deltas = {name: _reencode(ext, name, eng.vocab,
                              dis.sources[name].attrs)
              for name in names}
    kg, stats = eng.ingest(deltas)
    kg_ref = _oracle(dis, eng.sources, engine=engine, dedup=dedup)
    np.testing.assert_array_equal(kg.to_codes(), kg_ref.to_codes())
    # a single ingest crosses each capacity bucket at most once
    assert stats["recompiles"] - recompiles_before <= 1
    # and a re-run without new data must not recompile again
    kg2, stats2 = eng.create_kg()
    assert stats2["recompiles"] == stats["recompiles"]
    np.testing.assert_array_equal(kg2.to_codes(), kg.to_codes())


@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.data_too_large])
@given(seed=st.integers(0, 5), n_batches=st.integers(2, 5))
def test_repeated_small_ingests_accumulate_correctly(seed, n_batches):
    """A stream of small batches equals one fresh run at every step."""
    dis = make_group_b_dis(32, 0.6, seed=seed)
    eng = KGEngine(dis)
    eng.create_kg()
    for b in range(n_batches):
        ext = make_group_b_dis(8, 0.5, seed=1000 + 10 * seed + b)
        kg, _stats = eng.ingest(
            {"gene": _reencode(ext, "gene", eng.vocab,
                               dis.sources["gene"].attrs)})
    kg_ref = _oracle(dis, eng.sources)
    np.testing.assert_array_equal(kg.to_codes(), kg_ref.to_codes())


# ---------------------------------------------------------------------------
# persistent plan store: key determinism (no id()/dict-order leakage)
# ---------------------------------------------------------------------------

_ENV = {"format": 1, "jax": "x", "jaxlib": "y", "backend": "cpu",
        "device_kind": "cpu", "device_count": 1}

_session_params = st.tuples(
    st.sampled_from([8, 24, 48, 96]),            # n_rows → capacity buckets
    st.integers(0, 3),                           # data seed
    st.sampled_from(["rmlmapper", "sdm"]),
    st.sampled_from([None, "lex", "hash"]),
    st.sampled_from(["exact", "bound"]),
    st.sampled_from([1.0, 2.0]))                 # bound-mode slack


def _session_key(params):
    n_rows, seed, engine, dedup, mode, slack = params
    eng = KGEngine(make_group_b_dis(n_rows, 0.6, seed=seed), engine=engine,
                   dedup=dedup, mode=mode, slack=slack, jit=False)
    return eng._key(eng.sources)


@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.data_too_large])
@given(a=_session_params, b=_session_params)
def test_store_keys_collide_iff_session_keys_collide(a, b):
    """The on-disk key is a sha256 of the canonicalized in-process key:
    two sessions share a store entry exactly when they would share an
    in-process LRU entry. Both directions matter — a missed collision
    wastes compiles; a spurious one would serve the WRONG executable."""
    k1, k2 = _session_key(a), _session_key(b)
    assert (store_key(k1, _ENV) == store_key(k2, _ENV)) == (k1 == k2)
    # rebuilding the same session in THIS process reproduces the key
    # exactly (no id()/insertion-order component can be hiding in it)
    assert store_key(_session_key(a), _ENV) == store_key(k1, _ENV)


@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.data_too_large])
@given(params=_session_params,
       field=st.sampled_from(sorted(_ENV)),
       value=st.sampled_from(["other", 7]))
def test_envelope_changes_always_change_the_store_key(params, field, value):
    """Any envelope drift — version bump, backend/device change — maps
    the same session to a DIFFERENT store entry (stale executables are
    unreachable rather than rejected-on-load in the common case)."""
    k = _session_key(params)
    env2 = dict(_ENV)
    env2[field] = value
    assert (store_key(k, env2) == store_key(k, _ENV)) == (env2 == _ENV)


def test_canonical_rejects_process_unstable_key_components():
    """``canonical`` admits only value types whose repr is process-stable;
    anything that could smuggle an ``id()`` or iteration order into the
    key must raise, not silently produce an irreproducible key."""
    for bad in ({"a": 1}, [1, 2], {1, 2}, object(), b"bytes",
                (1, (2, [3]))):
        with pytest.raises(TypeError):
            canonical(bad)
    # the admitted types round-trip deterministically
    key = (None, True, 3, 2.5, "s", ("nested", 0))
    assert canonical(key) == canonical((None, True, 3, 2.5, "s",
                                        ("nested", 0)))
