"""Pallas TPU kernel for the Mamba2 SSD chunk scan.

Same scheme as the rwkv6 kernel: grid ``(B*H, T/L)`` with the chunk
dimension sequential; the [N, P] recurrent state lives in VMEM scratch
across chunk steps. Per chunk: two MXU matmuls for the intra-chunk scores
and output, one for the state delta — HBM traffic is one read of
x·dt / decay / B / C and one write of y per token, state never leaves
VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(xdt_ref, la_ref, b_ref, c_ref, o_ref, fs_ref, state, *,
                num_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    xdt = xdt_ref[...].astype(jnp.float32)   # [L,P]
    la = la_ref[...].astype(jnp.float32)     # [L]
    b = b_ref[...].astype(jnp.float32)       # [L,N]
    c = c_ref[...].astype(jnp.float32)       # [L,N]
    l = xdt.shape[0]

    cum = jnp.cumsum(la)
    diff = cum[:, None] - cum[None, :]
    mask = jnp.tril(jnp.ones((l, l), dtype=bool))
    decay = jnp.where(mask, jnp.exp(jnp.where(mask, diff, 0.0)), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * decay
    s_in = state[...]                         # [N,P]
    q = c * jnp.exp(cum)[:, None]
    y = (jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(q, s_in, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32))
    o_ref[...] = y.astype(o_ref.dtype)

    bw = b * jnp.exp(cum[-1] - cum)[:, None]
    delta = jax.lax.dot_general(bw, xdt, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    state[...] = jnp.exp(cum[-1]) * s_in + delta

    @pl.when(ci == num_chunks - 1)
    def _finish():
        fs_ref[...] = state[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_ssd_pallas(xdt: jax.Array, la: jax.Array, b: jax.Array,
                      c: jax.Array, *, chunk: int = 64,
                      interpret: bool = False):
    """xdt [B,H,T,P] (= x*dt); la [B,H,T] (= dt*A); b/c [B,T,N].
    Returns (y [B,H,T,P], state [B,H,N,P]). T must be a chunk multiple."""
    bb, h, t, p = xdt.shape
    n = b.shape[-1]
    assert t % chunk == 0, (t, chunk)
    num_chunks = t // chunk
    xf = xdt.reshape(bb * h, t, p)
    lf = la.reshape(bb * h, t)

    y, fs = pl.pallas_call(
        functools.partial(_ssd_kernel, num_chunks=num_chunks),
        grid=(bb * h, num_chunks),
        in_specs=[
            pl.BlockSpec((None, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, chunk), lambda bh, ci: (bh, ci)),
            pl.BlockSpec((None, chunk, n), lambda bh, ci: (bh // h, ci, 0)),
            pl.BlockSpec((None, chunk, n), lambda bh, ci: (bh // h, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, n, p), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bb * h, t, p), xdt.dtype),
                   jax.ShapeDtypeStruct((bb * h, n, p), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xf, lf, b, c)
    return y.reshape(bb, h, t, p), fs.reshape(bb, h, n, p)
