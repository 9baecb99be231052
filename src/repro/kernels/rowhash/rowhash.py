"""Pallas TPU kernels: 32-bit mixing hash over int32 rows, plus the fused
hash + sorted-neighbor-flag pass behind hash-first duplicate elimination.

Both kernels work lane-dense. The ``[N, K]`` row matrix is handed to the
kernel column-major as ``[K, N/128, 128]`` (row ``r`` sits at sublane
``r // 128``, lane ``r % 128`` of every column plane), so one grid step
hashes a ``[K, block_n/128, 128]`` tile with full-width vector ops and
writes ``block_n`` outputs as a dense ``[block_n/128, 128]`` block. The
K-column mix is unrolled (K is static and small for relational rows): one
HBM read per element, one HBM write per output row. ``block_n`` is rounded
up to :data:`ROW_TILE` rows, one ``(8, 128)`` vreg per column, which is
the granule the TPU tiles both the input planes and the 1-D outputs in.

``hash_neighbor_flags_pallas`` additionally compares every row with its
predecessor in row order. Inside a tile that is a shift by one along the
flattened ``(sublane, lane)`` order: a lane rotate, plus a sublane rotate
for lane 0. The tile's first row compares against the last row of the
previous tile, which a second, ``(K, 8, 128)`` view of the same input
brings in, so hash, neighbor compare and keep-mask happen in one pass.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import out_struct

from .ref import FNV_OFFSET, FNV_PRIME, GOLDEN

LANES = 128
SUBLANES = 8
#: rows in one (8, 128) vreg: the granule of ``block_n``
ROW_TILE = SUBLANES * LANES
#: default rows per grid step (64 sublanes x 128 lanes per column)
DEFAULT_BLOCK_N = 8 * ROW_TILE


def _fmix32(x):
    x = x ^ lax.shift_right_logical(x, jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ lax.shift_right_logical(x, jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ lax.shift_right_logical(x, jnp.uint32(16))
    return x


def _row_hashes(cols) -> jax.Array:
    """Hash rows given as a list of K same-shape uint32 column planes."""
    h = jnp.full(cols[0].shape, jnp.uint32(FNV_OFFSET), dtype=jnp.uint32)
    for col, c in enumerate(cols):
        salt = jnp.uint32((GOLDEN * (col + 1)) & 0xFFFFFFFF)
        h = (h ^ _fmix32(c + salt)) * jnp.uint32(FNV_PRIME)
    return _fmix32(h)


def _planes(ref, k: int):
    return [ref[c].astype(jnp.uint32) for c in range(k)]


def _tiling(n: int, block_n: int) -> Tuple[int, int]:
    """(rows per grid step, padded row count): ``block_n`` rounded up to a
    :data:`ROW_TILE` multiple and capped at the padded matrix."""
    n_tiles = max(1, -(-n // ROW_TILE))
    block = min(max(1, -(-block_n // ROW_TILE)), n_tiles) * ROW_TILE
    return block, -(-n_tiles * ROW_TILE // block) * block


def _lane_dense(x: jax.Array, n_pad: int) -> jax.Array:
    """[N, K] -> zero-padded column planes [K, n_pad/128, 128]."""
    n, k = x.shape
    xt = jnp.pad(x.T, ((0, 0), (0, n_pad - n)))
    return xt.reshape(k, n_pad // LANES, LANES)


def _rowhash_kernel(x_ref, o_ref, *, k: int):
    o_ref[...] = _row_hashes(_planes(x_ref, k))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def rowhash_pallas(x: jax.Array, *, block_n: int = DEFAULT_BLOCK_N,
                   interpret: bool = False) -> jax.Array:
    """[N, K] int32 -> [N] uint32. N is padded to a block multiple."""
    n, k = x.shape
    block, n_pad = _tiling(n, block_n)
    rows = block // LANES
    out = pl.pallas_call(
        functools.partial(_rowhash_kernel, k=k),
        grid=(n_pad // block,),
        in_specs=[pl.BlockSpec((k, rows, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=out_struct((n_pad // LANES, LANES), jnp.uint32, x),
        interpret=interpret,
    )(_lane_dense(x, n_pad))
    return out.reshape(n_pad)[:n]


def _shift_in(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a`` [rows, 128] shifted one step later along its flattened
    (sublane, lane) order, with ``b[-1, -1]`` (``b`` is [8, 128]) shifted
    into position [0, 0]."""
    lanes = pltpu.roll(a, 1, 1)                 # [r, l] <- a[r, l-1]
    wrap = pltpu.roll(lanes, 1, 0)              # [r, 0] <- a[r-1, 127]
    first = pltpu.roll(pltpu.roll(b, 1, 1), 1, 0)   # [0, 0] <- b[7, 127]
    if a.shape[0] > SUBLANES:
        first = jnp.concatenate(
            [first, jnp.zeros((a.shape[0] - SUBLANES, LANES), a.dtype)], 0)
    sub = lax.broadcasted_iota(jnp.int32, a.shape, 0)
    lane = lax.broadcasted_iota(jnp.int32, a.shape, 1)
    out = jnp.where(lane == 0, wrap, lanes)
    return jnp.where((sub == 0) & (lane == 0), first, out)


def _hash_flags_kernel(x_ref, b_ref, h_ref, keep_ref, coll_ref, *, k: int):
    x = _planes(x_ref, k)          # K planes [rows, 128] of this tile
    b = _planes(b_ref, k)          # K planes [8, 128]: [7, 127] is the
    #                                previous tile's last row (tile 0: row 7
    #                                of itself; overridden below)
    h = _row_hashes(x)
    prev_h = _shift_in(h, _row_hashes(b))
    row_eq = None
    for xc, bc in zip(x, b):
        eq = xc == _shift_in(xc, bc)
        row_eq = eq if row_eq is None else row_eq & eq
    hash_eq = h == prev_h
    keep = ~(hash_eq & row_eq)
    coll = hash_eq & ~row_eq
    # the very first row of the whole matrix has no predecessor
    sub = lax.broadcasted_iota(jnp.int32, h.shape, 0)
    lane = lax.broadcasted_iota(jnp.int32, h.shape, 1)
    global_first = (pl.program_id(0) == 0) & (sub == 0) & (lane == 0)
    h_ref[...] = h
    keep_ref[...] = (keep | global_first).astype(jnp.int32)
    coll_ref[...] = (coll & ~global_first).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def hash_neighbor_flags_pallas(rows: jax.Array, *,
                               block_n: int = DEFAULT_BLOCK_N,
                               interpret: bool = False
                               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused flags over hash-sorted ``rows[N, K]``: ``(hash, keep, collide)``.

    ``keep[i]`` is 1 iff row i differs from row i-1 (hash or content) — the
    first-occurrence mask of a duplicate run. ``collide[i]`` is 1 iff the
    hashes match but the rows differ (a genuine 32-bit collision). Semantics
    match :func:`repro.kernels.rowhash.ref.hash_neighbor_flags_ref`.
    """
    n, k = rows.shape
    block, n_pad = _tiling(n, block_n)
    sub_rows = block // LANES
    per_block = sub_rows // SUBLANES     # (8, 128) groups per tile
    planes = _lane_dense(rows, n_pad)
    out_spec = pl.BlockSpec((sub_rows, LANES), lambda i: (i, 0))
    h, keep, coll = pl.pallas_call(
        functools.partial(_hash_flags_kernel, k=k),
        grid=(n_pad // block,),
        in_specs=[pl.BlockSpec((k, sub_rows, LANES), lambda i: (0, i, 0)),
                  # the (8, 128) group just before tile i (tile 0: itself)
                  pl.BlockSpec((k, SUBLANES, LANES),
                               lambda i: (0, jnp.maximum(i * per_block - 1,
                                                         0), 0))],
        out_specs=(out_spec, out_spec, out_spec),
        out_shape=tuple(out_struct((n_pad // LANES, LANES), dt, rows)
                        for dt in (jnp.uint32, jnp.int32, jnp.int32)),
        interpret=interpret,
    )(planes, planes)
    return tuple(o.reshape(n_pad)[:n] for o in (h, keep, coll))
