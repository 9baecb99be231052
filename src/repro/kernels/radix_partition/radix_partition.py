"""Pallas TPU kernel: one-pass radix partition of coded rows into
fixed-capacity hash buckets.

The sequential TPU grid walks ``(block_n, K)`` row tiles while the whole
bucketed output block stays VMEM-resident (constant index map → the block
is "revisited" every step and written back to HBM once at the end).  Each
step:

1. hashes the tile's key columns (same unrolled FNV/murmur mix as the
   rowhash kernel) and derives a bucket target per row — ``h &
   (n_buckets-1)`` in exchange mode, ``h >> (32-log2 n_buckets)`` in
   order-preserving mode; rows past ``count`` get a sentinel target;
2. groups the tile's rows by bucket *without a sort*: an exclusive
   per-bucket rank plus an in-tile bucket offset (both computed with small
   one-hot matmuls on the MXU) form a complete permutation of the tile,
   applied as a ``[block_n, block_n]`` one-hot matmul.  int32 row payloads
   ride through the f32 MXU as two 16-bit limbs (exact: each output slot
   has exactly one source row and limbs are < 2^16) and are recombined;
3. copies each bucket's now-contiguous run from the tile scratch into its
   region of the resident output with a masked dynamic-slice blend.  The
   per-bucket running totals live in the SMEM counts output (doubling as
   the cross-tile histogram), so slice starts are SMEM-sourced scalars.
   A row whose bucket is already at capacity is simply never written —
   overflow shows up in the (unclamped) counts, never as corruption.

Within a bucket rows keep their original order (rank is a stable running
count), so the result is bit-identical to the oracle and to the historical
stable-sort bucketization it replaces.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import out_struct
from repro.kernels.rowhash.ref import FNV_OFFSET, FNV_PRIME, GOLDEN

from .ref import PAD_ID, bucket_shift

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def _fmix32(x):
    x = x ^ lax.shift_right_logical(x, jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ lax.shift_right_logical(x, jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ lax.shift_right_logical(x, jnp.uint32(16))
    return x


def _mm(a, b):
    """Exact small-int matmul through the MXU."""
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           precision=_HIGHEST,
                           preferred_element_type=_F32)


def _radix_partition_kernel(count_ref, x_ref, o_ref, counts_ref, tile_ref,
                            ts_ref, *, n_buckets: int, cap_bucket: int,
                            block_n: int, key_cols: Tuple[int, ...],
                            shift: Optional[int]):
    i = pl.program_id(0)
    nb1 = n_buckets + 1  # + sentinel bucket for invalid rows

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, PAD_ID, jnp.int32)
        tile_ref[...] = jnp.full(tile_ref.shape, PAD_ID, jnp.int32)
        for b in range(n_buckets):
            counts_ref[b] = 0

    x = x_ref[...]                                        # [block_n, K]
    ridx = i * block_n + lax.broadcasted_iota(jnp.int32, (block_n, 1), 0)
    valid = ridx < count_ref[0, 0]
    masked = jnp.where(valid, x, jnp.int32(PAD_ID))

    # --- bucket targets (column-unrolled row hash, as in rowhash) ---
    h = jnp.full((block_n, 1), jnp.uint32(FNV_OFFSET), dtype=jnp.uint32)
    for j, col in enumerate(key_cols):
        salt = jnp.uint32((GOLDEN * (j + 1)) & 0xFFFFFFFF)
        v = _fmix32(masked[:, col:col + 1].astype(jnp.uint32) + salt)
        h = (h ^ v) * jnp.uint32(FNV_PRIME)
    h = _fmix32(h)
    if shift is None:
        t = (h & jnp.uint32(n_buckets - 1)).astype(jnp.int32)
    else:
        t = lax.shift_right_logical(h, jnp.uint32(shift)).astype(jnp.int32)
    t = jnp.where(valid, t, jnp.int32(n_buckets))         # [block_n, 1]

    # --- in-tile grouping permutation (histogram → rank → one-hot) ---
    onehot = (t == lax.broadcasted_iota(jnp.int32, (block_n, nb1), 1)
              ).astype(_F32)                              # [block_n, nb1]
    tile_counts = _mm(jnp.ones((1, block_n), _F32), onehot)        # [1, nb1]
    upper = (lax.broadcasted_iota(jnp.int32, (nb1, nb1), 0)
             < lax.broadcasted_iota(jnp.int32, (nb1, nb1), 1)).astype(_F32)
    tile_offset = _mm(tile_counts, upper)                 # excl. cumsum
    lower = (lax.broadcasted_iota(jnp.int32, (block_n, block_n), 0)
             > lax.broadcasted_iota(jnp.int32, (block_n, block_n), 1)
             ).astype(_F32)
    excl = _mm(lower, onehot)            # same-bucket predecessors per row
    rank = jnp.sum(excl * onehot, axis=1, keepdims=True)  # [block_n, 1]
    base = lax.dot_general(onehot, tile_offset, (((1,), (1,)), ((), ())),
                           precision=_HIGHEST,
                           preferred_element_type=_F32)   # [block_n, 1]
    dest = base + rank  # complete permutation of 0..block_n-1

    # apply P[d, j] = (dest_j == d) via two 16-bit-limb matmuls; the limbs
    # stay int32 (logical shift, mask) on their way to and from f32, since
    # the chip converts only signed integers to and from floats
    pt = (dest.astype(jnp.int32)
          == lax.broadcasted_iota(jnp.int32, (block_n, block_n), 1)
          ).astype(_F32)                                  # [j, d]
    hi = lax.shift_right_logical(masked, jnp.int32(16)).astype(_F32)
    lo = (masked & jnp.int32(0xFFFF)).astype(_F32)
    phi = lax.dot_general(pt, hi, (((0,), (0,)), ((), ())),
                          precision=_HIGHEST, preferred_element_type=_F32)
    plo = lax.dot_general(pt, lo, (((0,), (0,)), ((), ())),
                          precision=_HIGHEST, preferred_element_type=_F32)
    perm = (lax.shift_left(phi.astype(jnp.int32), jnp.int32(16))
            | plo.astype(jnp.int32))
    tile_ref[0:block_n, :] = perm

    # --- per-bucket blend-copy into the resident output ---
    off = lax.broadcasted_iota(jnp.int32, (block_n, 1), 0)
    ts_ref[0] = 0
    for b in range(n_buckets):
        ts = ts_ref[0]                       # in-tile start of bucket b
        base_b = counts_ref[b]               # rows already placed in b
        cnt_b = jnp.sum(onehot[:, b:b + 1]).astype(jnp.int32)
        start = b * cap_bucket + jnp.minimum(base_b, cap_bucket)
        src = tile_ref[pl.ds(ts, block_n), :]
        keep = (off < cnt_b) & (base_b + off < cap_bucket)
        cur = o_ref[pl.ds(start, block_n), :]
        o_ref[pl.ds(start, block_n), :] = jnp.where(keep, src, cur)
        counts_ref[b] = base_b + cnt_b
        ts_ref[0] = ts + cnt_b


@functools.partial(jax.jit, static_argnames=(
    "n_buckets", "cap_bucket", "key_cols", "order_preserving", "block_n",
    "interpret"))
def radix_partition_pallas(data: jax.Array, count: jax.Array, *,
                           n_buckets: int, cap_bucket: int,
                           key_cols: Optional[Tuple[int, ...]] = None,
                           order_preserving: bool = False,
                           block_n: int = 256, interpret: bool = False
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Kernel-backed twin of :func:`.ref.radix_partition_ref`.

    ``n_buckets`` must be a power of two (the exchange-mode modulo is a
    mask; the dispatcher falls back to the oracle otherwise). Returns
    ``(buckets [n_buckets, cap_bucket, K], clamped counts, overflow)``.
    """
    n, k = data.shape
    if n_buckets & (n_buckets - 1) or n_buckets < 2:
        raise ValueError(f"kernel needs a power-of-two bucket count >= 2, "
                         f"got {n_buckets}")
    cols = tuple(range(k)) if key_cols is None else tuple(key_cols)
    shift = bucket_shift(n_buckets) if order_preserving else None
    n_pad = max(((n + block_n - 1) // block_n) * block_n, block_n)
    if n_pad != n:
        data = jnp.pad(data, ((0, n_pad - n), (0, 0)),
                       constant_values=PAD_ID)
    out_rows = n_buckets * cap_bucket + block_n  # slack for clamped writes
    flat, raw = pl.pallas_call(
        functools.partial(_radix_partition_kernel, n_buckets=n_buckets,
                          cap_bucket=cap_bucket, block_n=block_n,
                          key_cols=cols, shift=shift),
        grid=(n_pad // block_n,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((block_n, k), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((out_rows, k), lambda i: (0, 0)),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=(out_struct((out_rows, k), jnp.int32, data, count),
                   out_struct((n_buckets,), jnp.int32, data, count)),
        scratch_shapes=[pltpu.VMEM((2 * block_n, k), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(jnp.asarray(count, jnp.int32).reshape(1, 1), data)
    buckets = flat[:n_buckets * cap_bucket].reshape(n_buckets, cap_bucket, k)
    return (buckets, jnp.minimum(raw, cap_bucket),
            jnp.any(raw > cap_bucket))
