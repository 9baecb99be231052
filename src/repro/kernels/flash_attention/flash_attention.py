"""Pallas TPU flash attention: online-softmax block attention.

Grid ``(B*H, num_q_blocks, num_k_blocks)`` with the k dimension sequential
("arbitrary") so the running max/denominator/accumulator live in VMEM
scratch across k steps. Per step the kernel touches one ``(block_q, D)`` q
tile and one ``(block_k, D)`` k/v tile — VMEM footprint is
``O(block_q·D + block_k·D + block_q·block_k)`` independent of sequence
length, vs the O(S²) score matrix XLA would materialize.

GQA is handled by the k/v BlockSpec index maps (q head -> kv head), causal
and sliding-window masking by absolute-position predicates; fully-masked
(q-block, k-block) pairs skip the MXU work entirely via ``pl.when``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import MASK_VALUE


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr, *,
               scale: float, block_q: int, block_k: int, causal: bool,
               window: int, kv_len: int, q_offset: int, num_kb: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    q_first = qi * block_q + q_offset          # absolute pos of first q row
    q_last = q_first + block_q - 1
    k_first = ki * block_k
    k_last = k_first + block_k - 1

    live = k_first < kv_len                    # padded kv tail
    if causal:
        live &= k_first <= q_last
    if window > 0:
        # the youngest pair in the block is (q_first, k_last); if even that
        # is older than the window, every pair is
        live &= k_last > q_first - window

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        q_pos = q_first + lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0)
        k_pos = k_first + lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + p.sum(axis=-1)
        m_scr[...] = m_new
        v = v_ref[...].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc[...] = acc[...] * alpha[:, None] + pv

    @pl.when(ki == num_kb - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "block_q",
                              "block_k", "kv_len", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: Optional[int] = None,
                           scale: Optional[float] = None,
                           kv_len: Optional[int] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q [B,H,S,D], k/v [B,KH,Sk,D] -> [B,H,S,D]. Sequences are padded to
    block multiples; ``kv_len`` masks the padded tail (defaults to Sk)."""
    b, h, s_q, d = q.shape
    _, kh, s_k, _ = k.shape
    assert h % kh == 0
    group = h // kh
    scale_val = float(d ** -0.5 if scale is None else scale)
    kv_len_val = int(s_k if kv_len is None else kv_len)
    window_val = int(window or 0)

    # pad to block multiples
    sq_p = -(-s_q // block_q) * block_q
    sk_p = -(-s_k // block_k) * block_k
    if sq_p != s_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - s_q), (0, 0)))
    if sk_p != s_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - s_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - s_k), (0, 0)))

    qf = q.reshape(b * h, sq_p, d)
    kf = k.reshape(b * kh, sk_p, d)
    vf = v.reshape(b * kh, sk_p, d)
    num_qb = sq_p // block_q
    num_kb = sk_p // block_k

    def kv_index(bh, qi, ki):
        return (bh // h) * kh + (bh % h) // group, ki, 0

    kernel = functools.partial(
        _fa_kernel, scale=scale_val, block_q=block_q, block_k=block_k,
        causal=causal, window=window_val, kv_len=kv_len_val,
        q_offset=kv_len_val - s_q, num_kb=num_kb)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, block_k, d), kv_index),
            pl.BlockSpec((None, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :s_q].reshape(b, h, s_q, d)
