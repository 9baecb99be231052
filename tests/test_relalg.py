"""Unit + property tests for the relational-algebra substrate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="test extra: pip install -r "
                    "requirements.txt (non-hypothesis δ coverage lives in "
                    "test_dedup_strategies.py)")
from hypothesis import given, settings, strategies as st

from repro.relalg import (PAD_ID, Table, Vocab, compact, distinct, equi_join,
                          project, rename, select_eq, union)


def _table(rows, attrs, capacity=None):
    codes = (np.asarray(rows, dtype=np.int32)
             if rows else np.zeros((0, len(attrs)), np.int32))
    return Table.from_codes(codes, attrs, capacity)


# ---------------------------------------------------------------------------
# construction / vocab
# ---------------------------------------------------------------------------

def test_from_records_roundtrip():
    vocab = Vocab()
    recs = [{"a": "x", "b": 1}, {"a": "y", "b": 2}, {"a": "x", "b": 1}]
    t = Table.from_records(recs, ["a", "b"], vocab, capacity=8)
    assert t.capacity == 8 and int(t.count) == 3
    assert t.to_records(vocab) == recs


def test_padding_is_pad_id():
    t = _table([[1, 2]], ["a", "b"], capacity=4)
    assert (np.asarray(t.data)[1:] == PAD_ID).all()


# ---------------------------------------------------------------------------
# unary ops
# ---------------------------------------------------------------------------

def test_project_and_rename():
    t = _table([[1, 2, 3], [4, 5, 6]], ["a", "b", "c"])
    p = project(t, ["c", "a"])
    assert p.attrs == ("c", "a")
    assert p.row_set() == {(3, 1), (6, 4)}
    r = rename(p, {"c": "z"})
    assert r.attrs == ("z", "a")


def test_select_eq():
    t = _table([[1, 7], [2, 7], [1, 8]], ["k", "v"], capacity=6)
    s = select_eq(t, "k", 1)
    assert int(s.count) == 2
    assert s.row_set() == {(1, 7), (1, 8)}


def test_distinct_basic():
    t = _table([[1, 2], [1, 2], [3, 4], [1, 2], [3, 4]], ["a", "b"],
               capacity=10)
    d = distinct(t)
    assert int(d.count) == 2
    assert d.row_set() == {(1, 2), (3, 4)}
    # padding stays canonical
    assert (np.asarray(d.data)[2:] == PAD_ID).all()


def test_distinct_empty():
    t = _table([], ["a"], capacity=4)
    d = distinct(t)
    assert int(d.count) == 0


# ---------------------------------------------------------------------------
# binary ops
# ---------------------------------------------------------------------------

def test_union_bag_and_set():
    a = _table([[1], [2]], ["x"], capacity=4)
    b = _table([[2], [3]], ["x"], capacity=4)
    u = union(a, b)
    assert int(u.count) == 4
    s = union(a, b, dedup=True)
    assert s.row_set() == {(1,), (2,), (3,)}


def test_union_aligns_attr_order():
    a = _table([[1, 10]], ["x", "y"])
    b = _table([[20, 2]], ["y", "x"])
    u = union(a, b)
    assert u.attrs == ("x", "y")
    assert u.row_set() == {(1, 10), (2, 20)}


def test_equi_join_matches_numpy():
    left = _table([[1, 100], [2, 200], [2, 201], [9, 900]], ["k", "lv"],
                  capacity=8)
    right = _table([[2, 7], [1, 5], [2, 6]], ["k", "rv"], capacity=8)
    out, total = equi_join(left, right, "k", "k", out_capacity=16)
    assert int(total) == 5  # 1x1 + 2x2 matches
    assert out.attrs == ("k", "lv", "r_k", "rv")
    assert out.row_set() == {
        (1, 100, 1, 5),
        (2, 200, 2, 7), (2, 200, 2, 6),
        (2, 201, 2, 7), (2, 201, 2, 6),
    }


def test_equi_join_overflow_clamps_but_reports_total():
    left = _table([[1, 0], [1, 1]], ["k", "lv"], capacity=4)
    right = _table([[1, 0], [1, 1], [1, 2]], ["k", "rv"], capacity=4)
    out, total = equi_join(left, right, "k", "k", out_capacity=4)
    assert int(total) == 6
    assert int(out.count) == 4


def test_equi_join_no_matches():
    left = _table([[1, 0]], ["k", "lv"], capacity=4)
    right = _table([[2, 0]], ["k", "rv"], capacity=4)
    out, total = equi_join(left, right, "k", "k", out_capacity=4)
    assert int(total) == 0 and int(out.count) == 0


@pytest.mark.parametrize("share", [0.0, 0.05, 0.3, 1.0])
@pytest.mark.parametrize("capacity", [1, 7, 4096, 4099, 100_003])
def test_compact_matches_numpy(capacity, share):
    """Kept rows first in their order, then an all-PAD_ID tail, and the
    count: with codes at both ends of the range and rows whose content is
    all PAD_ID (kept or dropped alike)."""
    rng = np.random.default_rng(capacity * 100 + int(share * 100))
    data = rng.integers(0, PAD_ID, size=(capacity, 5), dtype=np.int32)
    ends = rng.random(data.shape) < 0.2
    data[ends] = rng.choice(np.array([0, PAD_ID - 1], np.int32),
                            size=int(ends.sum()))
    data[rng.random(capacity) < 0.1] = PAD_ID
    keep = rng.random(capacity) < share
    want = np.full_like(data, PAD_ID)
    want[:keep.sum()] = data[keep]
    out, count = jax.jit(compact)(jnp.asarray(data), jnp.asarray(keep))
    assert int(count) == int(keep.sum())
    np.testing.assert_array_equal(np.asarray(out), want)


# ---------------------------------------------------------------------------
# hypothesis property tests: fixed-shape ops == python set/bag semantics
# ---------------------------------------------------------------------------

rows_strategy = st.lists(
    st.lists(st.integers(0, 6), min_size=2, max_size=2),
    min_size=0, max_size=24)


@settings(max_examples=60, deadline=None)
@given(rows=rows_strategy)
def test_prop_distinct_matches_set(rows):
    t = _table(rows, ["a", "b"], capacity=max(1, len(rows) + 3))
    d = distinct(t)
    assert d.row_set() == {tuple(r) for r in rows}
    assert int(d.count) == len({tuple(r) for r in rows})


@settings(max_examples=60, deadline=None)
@given(rows_a=rows_strategy, rows_b=rows_strategy)
def test_prop_union_set_semantics(rows_a, rows_b):
    a = _table(rows_a, ["a", "b"], capacity=max(1, len(rows_a) + 2))
    b = _table(rows_b, ["a", "b"], capacity=max(1, len(rows_b) + 2))
    u = union(a, b, dedup=True)
    assert u.row_set() == {tuple(r) for r in rows_a} | {tuple(r) for r in rows_b}


@settings(max_examples=60, deadline=None)
@given(rows_a=rows_strategy, rows_b=rows_strategy)
def test_prop_join_matches_nested_loop(rows_a, rows_b):
    a = _table(rows_a, ["k", "lv"], capacity=max(1, len(rows_a)))
    b = _table(rows_b, ["k", "rv"], capacity=max(1, len(rows_b)))
    expected = {(ka, va, kb, vb)
                for ka, va in map(tuple, rows_a)
                for kb, vb in map(tuple, rows_b) if ka == kb}
    cap = max(1, len(rows_a) * len(rows_b))
    out, total = equi_join(a, b, "k", "k", out_capacity=cap)
    # bag cardinality must match the nested loop too
    n_expected = sum(1 for ka, _ in map(tuple, rows_a)
                     for kb, _ in map(tuple, rows_b) if ka == kb)
    assert int(total) == n_expected
    assert out.row_set() == expected


@settings(max_examples=40, deadline=None)
@given(rows=rows_strategy)
def test_prop_projection_pushdown_axiom(rows):
    """π_A(δ(T)) has the same set of rows as δ(π_A(T)) — the relational
    axiom MapSDI Rule 1 relies on (projection then dedup commute w.r.t. the
    produced set)."""
    t = _table(rows, ["a", "b"], capacity=max(1, len(rows) + 1))
    lhs = distinct(project(t, ["a"]))
    rhs = project(distinct(t), ["a"])
    assert lhs.row_set() == rhs.row_set()
