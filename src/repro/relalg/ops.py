"""Masked fixed-shape relational operators on :class:`Table`.

Every operator is jit-compatible: outputs have static capacities and a
dynamic valid-row ``count``. Padding rows carry ``PAD_ID`` in every column so
lexicographic sorts (``lax.sort`` with ``num_keys``) push them to the end.

These are the building blocks the MapSDI transformation rules are defined
over: projection (Rule 1/2), union+rename (Rule 3), distinct (duplicate
elimination), and the sort-merge equi-join used by triple-map join
conditions.

Duplicate elimination (δ) — the single hottest operator in both MapSDI
pre-processing and the RDFizer sinks — comes in two strategies:

* ``"lex"``  — full K-key lexicographic ``lax.sort`` over every column,
  then a neighbor compare. Always exact; cost grows with K.
* ``"hash"`` — the default: one Pallas ``rowhash`` pass turns each row into
  a 32-bit key, a single-key sort carries the row permutation, and a fused
  hash+neighbor-flag kernel verifies full-row equality of sorted neighbors.
  Detected 32-bit collisions (equal hash, unequal row) trigger a
  ``lax.cond`` fallback to the exact lex path, so the result is always
  bit-identical to ``"lex"``. See ``docs/relalg.md`` for the correctness
  argument.

``DEFAULT_DEDUP`` selects the engine-wide default; every δ entry point
(:func:`distinct`, :func:`union` with dedup, the RDFizer, the Rule 1–3
transforms and the distributed dedup) accepts a ``dedup`` override.
"""
from __future__ import annotations

from typing import Callable, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.radix_partition import radix_partition
from repro.kernels.rowhash import hash_neighbor_flags, rowhash

from .encoding import PAD_ID
from .table import Table

# Engine-wide default δ strategy. "hash" is exact (collision fallback) and
# turns the K-key sort into a single-key sort; "lex" is the classic path.
DEFAULT_DEDUP = "hash"

# The hash δ swaps its single global sort for a radix partition + per-bucket
# sorts once the matrix has this many rows (sort cost is O(N log N); B
# independent bucket sorts cost O(N log(N/B)) and the partition is one
# linear kernel pass). Below the threshold the partition overhead dominates.
RADIX_DEDUP_MIN_ROWS = 4096
RADIX_DEDUP_BUCKETS = 8

_UINT32_MAX = 0xFFFFFFFF


def _resolve_dedup(dedup: Optional[str]) -> str:
    strategy = DEFAULT_DEDUP if dedup is None else dedup
    if strategy not in ("lex", "hash"):
        raise ValueError(f"unknown dedup strategy {strategy!r} "
                         "(expected 'lex' or 'hash')")
    return strategy


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _masked_data(table: Table) -> jax.Array:
    """Table data with padding rows forced to PAD_ID in every column."""
    return jnp.where(table.valid_mask[:, None], table.data,
                     jnp.int32(PAD_ID))


def compact(data: jax.Array, keep: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Move rows with ``keep`` set to the front, in their order; return
    (data, count), with every row from ``count`` on all PAD_ID.

    One single-key sort, no scatter and no gather: a kept row's key is its
    index, a dropped row's key is the capacity, so kept rows sort first in
    their original order (their keys are unique, so the sort need not be
    stable) and dropped rows behind them, where the tail mask overwrites
    them. The columns ride along as 1-D payload operands. On the TPU v5e
    this is 15–20× faster than a row scatter to cumsum destinations and 2–3×
    faster than a key-only sort plus a row gather (``docs/relalg.md`` §1).

    Its operations carry the scope ``compact`` (under the calling plan
    operator's scope)."""
    with jax.named_scope("compact"):
        capacity, k = data.shape
        idx = jnp.arange(capacity, dtype=jnp.int32)
        key = jnp.where(keep.astype(bool), idx, jnp.int32(capacity))
        key, *cols = lax.sort((key, *(data[:, c] for c in range(k))),
                              dimension=0, num_keys=1, is_stable=False)
        out = jnp.where((key < capacity)[:, None], jnp.stack(cols, axis=1),
                        jnp.asarray(PAD_ID, data.dtype))
        return out, keep.astype(jnp.int32).sum()


def lex_sorted_rows(data: jax.Array) -> jax.Array:
    """``data[N, K]``'s rows in lexicographic order.

    One stable sort per column, last column first (LSD), in a loop over a
    single compiled ``(key, position)`` sort: the TPU compiler takes
    minutes for one K-key comparator sort at 10^5 rows (K = 5), and a
    fraction of that for this loop. Equal rows come out adjacent, in the
    same order as a K-key sort."""
    n, k = data.shape
    pos = jnp.arange(n, dtype=jnp.int32)
    # the loop carry varies over the same mesh axes as ``data`` (inside
    # shard_map a carry may not change its replication type)
    perm0 = lax.pcast(pos, tuple(jax.typeof(data).vma), to="varying")

    def pass_(i, perm):
        key = lax.dynamic_index_in_dim(data, k - 1 - i, axis=1,
                                       keepdims=False)[perm]
        # (key, position) as keys: stable by position, no stable sort
        _, _, perm = lax.sort((key, pos, perm), dimension=0, num_keys=2,
                              is_stable=False)
        return perm

    return data[lax.fori_loop(0, k, pass_, perm0)]


def sort_lex(table: Table) -> jax.Array:
    """Rows sorted lexicographically by all columns; padding last."""
    return lex_sorted_rows(_masked_data(table))


# ---------------------------------------------------------------------------
# unary operators
# ---------------------------------------------------------------------------

def project(table: Table, attrs: Sequence[str]) -> Table:
    """π_attrs — keep only ``attrs`` (bag semantics: rows unchanged)."""
    idx = [table.col_index(a) for a in attrs]
    return Table(data=table.data[:, jnp.asarray(idx)], count=table.count,
                 attrs=tuple(attrs))


def project_as(table: Table, spec: Sequence[Tuple[str, str]]) -> Table:
    """π with renaming: ``spec`` is ``[(source_attr, new_name), ...]``.

    Unlike :func:`project`, a source attribute may appear several times
    (needed when one attribute plays multiple roles after a Rule-3 merge).
    """
    names = [n for _, n in spec]
    if len(set(names)) != len(names):
        raise ValueError(f"project_as produces duplicate attrs: {names}")
    idx = [table.col_index(a) for a, _ in spec]
    return Table(data=table.data[:, jnp.asarray(idx)], count=table.count,
                 attrs=tuple(names))


def rename(table: Table, mapping: Mapping[str, str]) -> Table:
    """ρ — rename attributes (data untouched)."""
    new_attrs = tuple(mapping.get(a, a) for a in table.attrs)
    if len(set(new_attrs)) != len(new_attrs):
        raise ValueError(f"rename produces duplicate attrs: {new_attrs}")
    return Table(data=table.data, count=table.count, attrs=new_attrs)


def select_mask(table: Table, mask: jax.Array) -> Table:
    """σ — keep rows where ``mask`` holds (and the row is valid)."""
    keep = mask & table.valid_mask
    data, count = compact(table.data, keep)
    return Table(data=data, count=count, attrs=table.attrs)


def select_eq(table: Table, attr: str, code: jax.Array | int) -> Table:
    return select_mask(table, table.column(attr) == jnp.int32(code))


def select_neq(table: Table, attr: str, code: jax.Array | int) -> Table:
    return select_mask(table, table.column(attr) != jnp.int32(code))


def distinct_rows(data: jax.Array, count: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """Matrix-level lex δ: ``data[N, K]`` with ``count`` valid rows ->
    deduplicated ``(data, count)``. Shared by Table ops and the shard_map
    distributed dedup (which works on raw row matrices inside shards).

    Lexicographic full-row sort (:func:`lex_sorted_rows`), then
    first-occurrence compaction: a neighbour compare and :func:`compact`.
    Always exact; also the collision fallback of
    :func:`distinct_rows_hashed`.
    """
    capacity, k = data.shape
    valid_in = jnp.arange(capacity, dtype=jnp.int32) < count
    masked = jnp.where(valid_in[:, None], data, jnp.int32(PAD_ID))
    sorted_data = lex_sorted_rows(masked)
    prev = jnp.roll(sorted_data, 1, axis=0)
    first = jnp.any(sorted_data != prev, axis=1)
    first = first.at[0].set(True)
    valid = jnp.arange(capacity, dtype=jnp.int32) < count
    return compact(sorted_data, first & valid)


def distinct_rows_hashed(data: jax.Array, count: jax.Array, *,
                         use_pallas: Optional[bool] = None,
                         hash_fn: Optional[Callable[[jax.Array], jax.Array]]
                         = None,
                         radix: Optional[bool] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """Matrix-level hash-first δ — bit-identical results to
    :func:`distinct_rows`.

    Two layouts share the hash-first idea; both end in the fused
    hash+neighbor-flag pass and a first-occurrence compaction:

    * **sorted** — one stable single-key sort on the 32-bit row hash
      carrying the row permutation;
    * **radix** — an order-preserving radix partition into
      :data:`RADIX_DEDUP_BUCKETS` hash buckets (bucket = the hash's top
      bits, so concatenated buckets stay in global hash order) followed by
      independent per-bucket sorts. Picked automatically at
      :data:`RADIX_DEDUP_MIN_ROWS` rows (``radix`` overrides); falls back
      to the sorted layout on bucket overflow, so the output is a pure
      function of the row set regardless of layout.

    Correctness under collisions: the keep-mask only merges *adjacent equal
    rows*, so a collision can never drop a distinct row. It could keep a
    duplicate (two equal rows separated by a colliding distinct row), but
    that interleaving requires an equal-hash run containing two different
    row values — exactly the ``collide`` flag the fused kernel raises, which
    routes the whole call through the exact lex path via ``lax.cond``.

    ``hash_fn`` overrides the row hash (tests force collisions with it);
    the pure-jnp flag path and sorted layout are used then, since the
    fused kernel and the partition kernel hard-code the production hash.
    """
    capacity, _ = data.shape
    if radix is None:
        radix = hash_fn is None and capacity >= RADIX_DEDUP_MIN_ROWS
    if radix and hash_fn is None:
        return _distinct_hashed_radix(data, count, use_pallas=use_pallas)
    return _distinct_hashed_sorted(data, count, use_pallas=use_pallas,
                                   hash_fn=hash_fn)


def _distinct_hashed_sorted(data: jax.Array, count: jax.Array, *,
                            use_pallas: Optional[bool] = None,
                            hash_fn: Optional[Callable[[jax.Array],
                                                       jax.Array]] = None
                            ) -> Tuple[jax.Array, jax.Array]:
    """Single-global-sort layout of the hash δ (see
    :func:`distinct_rows_hashed`)."""
    capacity, k = data.shape
    idx = jnp.arange(capacity, dtype=jnp.int32)
    valid_in = idx < count
    masked = jnp.where(valid_in[:, None], data, jnp.int32(PAD_ID))

    h = (rowhash(masked, use_pallas=use_pallas) if hash_fn is None
         else hash_fn(masked))
    # padding sorts last: stable sort keeps valid rows (smaller original
    # index) ahead of pads even when a valid row genuinely hashes to max
    h = jnp.where(valid_in, h, jnp.uint32(_UINT32_MAX))
    # (hash, index) as a two-key unstable sort: the same order as a stable
    # sort on the hash (indices are unique), at a fraction of the TPU
    # compile time of a stable sort
    _, perm = lax.sort((h, idx), dimension=0, num_keys=2, is_stable=False)
    rows = masked[perm]
    valid_s = perm < count

    if hash_fn is None:
        _, keep_raw, coll_raw = hash_neighbor_flags(rows,
                                                    use_pallas=use_pallas)
        keep_raw = keep_raw.astype(bool)
        coll_raw = coll_raw.astype(bool)
    else:
        hs = h[perm]
        prev_rows = jnp.roll(rows, 1, axis=0)
        row_eq = jnp.all(rows == prev_rows, axis=1)
        hash_eq = hs == jnp.roll(hs, 1)
        keep_raw = (~(hash_eq & row_eq)).at[0].set(True)
        coll_raw = (hash_eq & ~row_eq).at[0].set(False)

    prev_valid = jnp.roll(valid_s, 1).at[0].set(False)
    collision = jnp.any(coll_raw & valid_s & prev_valid)
    keep = keep_raw & valid_s

    return lax.cond(collision,
                    lambda: distinct_rows(data, count),
                    lambda: compact(rows, keep))


def _radix_dedup_cap(capacity: int, n_buckets: int) -> int:
    """Per-bucket capacity: Poisson mean + 6σ slack (same bound family as
    ``repro.core.distributed.sink_bucket_cap``; overflow falls back)."""
    m = capacity / n_buckets
    return max(8, int(-(-(m + 6.0 * m ** 0.5 + 8.0) // 1)))


def _distinct_hashed_radix(data: jax.Array, count: jax.Array, *,
                           use_pallas: Optional[bool] = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """Radix-bucketed layout of the hash δ (see
    :func:`distinct_rows_hashed`).

    The order-preserving partition buckets rows by the hash's *top* bits
    and keeps original order inside each bucket, so per-bucket stable
    sorts on (hash, position) concatenate to exactly the global stable
    hash order — the flattened buckets feed the same neighbor-flag pass
    as the sorted layout and yield a bit-identical δ.

    Two extra fallback triggers relative to the sorted layout:

    * bucket **overflow** (adversarially skewed hashes) would drop rows —
      re-run through the sorted layout (identical output, just slower);
    * a valid row whose *content* is all PAD_ID can sit right after a
      bucket's padding tail and be merged into it by the neighbor compare
      (the sorted layout can't hit this: stable sort keeps valid rows
      ahead of same-key pads). Detected as a suppressed keep with an
      invalid predecessor and routed through the fallback too.
    """
    capacity, k = data.shape
    nb = RADIX_DEDUP_BUCKETS
    cb = _radix_dedup_cap(capacity, nb)
    buckets, counts, overflow = radix_partition(
        data, count, n_buckets=nb, cap_bucket=cb, order_preserving=True,
        use_pallas=use_pallas)

    flat = buckets.reshape(nb * cb, k)
    h = rowhash(flat, use_pallas=use_pallas).reshape(nb, cb)
    pos = jnp.arange(cb, dtype=jnp.int32)[None, :]
    valid2d = pos < counts[:, None]
    h = jnp.where(valid2d, h, jnp.uint32(_UINT32_MAX))  # pads sort last
    _, perm = lax.sort((h, jnp.broadcast_to(pos, (nb, cb))),
                       dimension=1, num_keys=2, is_stable=False)
    rows = jnp.take_along_axis(buckets, perm[..., None], axis=1
                               ).reshape(nb * cb, k)
    # valid rows occupy each bucket's head before AND after the sort
    # (stable; within-bucket pads start at counts[b] and sort behind any
    # valid row even on a max-hash tie), so the mask needs no permuting
    valid_s = valid2d.reshape(nb * cb)

    _, keep_raw, coll_raw = hash_neighbor_flags(rows, use_pallas=use_pallas)
    keep_raw = keep_raw.astype(bool)
    coll_raw = coll_raw.astype(bool)
    prev_valid = jnp.roll(valid_s, 1).at[0].set(False)
    collision = jnp.any(coll_raw & valid_s & prev_valid)
    pad_merge = jnp.any(~keep_raw & valid_s & ~prev_valid)
    keep = keep_raw & valid_s

    def _fallback() -> Tuple[jax.Array, jax.Array]:
        return _distinct_hashed_sorted(data, count, use_pallas=use_pallas)

    def _take() -> Tuple[jax.Array, jax.Array]:
        out, n = compact(rows, keep)
        # δ output fits the input capacity (n <= count <= capacity) and
        # compact fronts the kept rows, so the slack tail is all-PAD
        return out[:capacity], n

    return lax.cond(overflow | collision | pad_merge, _fallback, _take)


def dedup_rows(data: jax.Array, count: jax.Array,
               dedup: Optional[str] = None, *,
               use_pallas: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Matrix-level δ under the selected strategy (None = engine default).

    The single implementation shared by :func:`distinct`, set-:func:`union`,
    the RDFizer sinks and the distributed shard-local dedup.
    """
    if _resolve_dedup(dedup) == "lex":
        return distinct_rows(data, count)
    return distinct_rows_hashed(data, count, use_pallas=use_pallas)


def distinct(table: Table, dedup: Optional[str] = None) -> Table:
    """δ — eliminate duplicate rows (set semantics).

    ``dedup`` picks the strategy (``"lex"`` | ``"hash"``; None = engine
    default, :data:`DEFAULT_DEDUP`). Both produce identical row sets.
    """
    data, count = dedup_rows(table.data, table.count, dedup)
    return Table(data=data, count=count, attrs=table.attrs)


# ---------------------------------------------------------------------------
# binary operators
# ---------------------------------------------------------------------------

def union(a: Table, b: Table, dedup: bool | str = False) -> Table:
    """∪ — concatenate rows (b's columns aligned to a's attr order).

    ``dedup`` selects the semantics: ``False`` is bag-union; ``True`` is
    set-union (π/∪/δ as in Transformation Rule 3) under the engine-default
    δ strategy; a strategy string (``"lex"`` | ``"hash"``) is set-union
    under that strategy.
    """
    if set(a.attrs) != set(b.attrs):
        raise ValueError(f"union schema mismatch: {a.attrs} vs {b.attrs}")
    b_aligned = project(b, a.attrs)
    data = jnp.concatenate([_masked_data(a), _masked_data(b_aligned)], axis=0)
    keep = jnp.concatenate([a.valid_mask, b_aligned.valid_mask])
    data, count = compact(data, keep)
    out = Table(data=data, count=count, attrs=a.attrs)
    if dedup is False:
        return out
    return distinct(out, dedup=None if dedup is True else dedup)


def append_rows(base: Table, delta: Table,
                capacity: Optional[int] = None) -> Table:
    """Append ``delta``'s valid rows after ``base``'s (micro-batch ingestion).

    ``delta``'s columns are aligned to ``base.attrs`` by name. When the
    combined rows fit ``base.capacity`` the write lands in the padding
    region and the output keeps base's shape — a shape-stable update, so a
    jitted closure over the table re-runs with zero re-trace. Otherwise the
    buffer grows to ``capacity`` (default: the next :func:`bucket_cap`
    bucket), which changes the shape — the caller's recompile signal.

    Host cost: two scalar syncs (the row counts); row data stays on device.
    """
    from .guard import host_int
    from .table import bucket_cap
    aligned = project(delta, base.attrs)
    n0, n1 = host_int(base.count), host_int(delta.count)
    total = n0 + n1
    if total > base.capacity:
        cap = bucket_cap(total) if capacity is None else capacity
        if cap < total:
            raise ValueError(f"{total} rows exceed capacity {cap}")
        pad = jnp.full((cap - base.capacity, base.n_attrs), jnp.int32(PAD_ID))
        grown = jnp.concatenate([_masked_data(base), pad], axis=0)
        base = Table(data=grown, count=base.count, attrs=base.attrs)
    idx = jnp.arange(aligned.capacity, dtype=jnp.int32)
    dest = jnp.where(idx < jnp.int32(n1), idx + jnp.int32(n0),
                     jnp.int32(base.capacity))      # invalid rows -> dropped
    data = _masked_data(base).at[dest].set(_masked_data(aligned), mode="drop")
    return Table(data=data, count=jnp.int32(total), attrs=base.attrs)


def equi_join(left: Table, right: Table, left_key: str, right_key: str,
              out_capacity: int, right_suffix: str = "r_",
              ) -> Tuple[Table, jax.Array]:
    """⋈ — sort-merge equi-join with a static output capacity.

    Returns ``(table, total_matches)``; ``total_matches`` may exceed the
    capacity (overflow detection is the caller's job — the MapSDI planner
    sizes capacities from source cardinalities).

    Output attrs: left attrs followed by right attrs, right-side names that
    collide with a left name get ``right_suffix`` prepended. The join key is
    kept on both sides (they are equal by construction).
    """
    lk = jnp.where(left.valid_mask, left.column(left_key), jnp.int32(PAD_ID))
    rk = jnp.where(right.valid_mask, right.column(right_key),
                   jnp.int32(PAD_ID))

    cap_r = right.capacity
    rk_sorted, perm = lax.sort(   # (key, index): stable order, see δ
        (rk, jnp.arange(cap_r, dtype=jnp.int32)), dimension=0, num_keys=2,
        is_stable=False)

    lo = jnp.searchsorted(rk_sorted, lk, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(rk_sorted, lk, side="right").astype(jnp.int32)
    counts = jnp.where(left.valid_mask & (lk != PAD_ID), hi - lo, 0)

    offsets = jnp.cumsum(counts)                       # inclusive
    starts = offsets - counts
    total = offsets[left.capacity - 1] if left.capacity > 0 else jnp.int32(0)

    j = jnp.arange(out_capacity, dtype=jnp.int32)
    left_idx = jnp.searchsorted(offsets, j, side="right").astype(jnp.int32)
    left_idx_c = jnp.clip(left_idx, 0, left.capacity - 1)
    within = j - starts[left_idx_c]
    right_pos = jnp.clip(lo[left_idx_c] + within, 0, cap_r - 1)
    right_idx = perm[right_pos]
    valid_out = j < jnp.minimum(total, out_capacity)

    left_rows = left.data[left_idx_c]
    right_rows = right.data[right_idx]
    rows = jnp.concatenate([left_rows, right_rows], axis=1)
    rows = jnp.where(valid_out[:, None], rows, jnp.int32(PAD_ID))

    left_names = set(left.attrs)
    right_attrs = tuple(
        (right_suffix + a) if a in left_names else a for a in right.attrs)
    out = Table(data=rows, count=jnp.minimum(total, out_capacity),
                attrs=left.attrs + right_attrs)
    return out, total.astype(jnp.int32)
