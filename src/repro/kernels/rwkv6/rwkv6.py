"""Pallas TPU kernel for the RWKV6 time-mix recurrence (chunked).

Grid ``(B*H, T/L)`` with the chunk dimension sequential; the [N,N]
recurrent state lives in VMEM scratch across chunk steps so it never
round-trips HBM. Per chunk the math is the same matrix form as
``ref.rwkv6_chunked`` (exact log-space intra-chunk scores — stable for any
decay), so HBM traffic is one read of r/k/v/w and one write of y per token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, fs_ref,
                  state, *, num_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    r = r_ref[...].astype(jnp.float32)   # [L,N]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)   # [N]
    l, n = r.shape

    lw = jnp.log(jnp.maximum(w, 1e-30))
    cum = jnp.cumsum(lw, axis=0)
    cum_excl = cum - lw
    diff = cum_excl[:, None, :] - cum[None, :, :]       # [L,L,N] <= 0
    mask = jnp.tril(jnp.ones((l, l), dtype=bool), k=-1)
    diff = jnp.where(mask[:, :, None], diff, -1e30)
    scores = jnp.einsum("tsn,tn,sn->ts", jnp.exp(diff), r, k)
    bonus = jnp.sum(r * u[None, :] * k, axis=-1)

    q_t = r * jnp.exp(cum_excl)
    s_in = state[...]
    y = scores @ v + bonus[:, None] * v + q_t @ s_in
    o_ref[...] = y.astype(o_ref.dtype)

    d_tot = jnp.exp(cum[-1])
    m = (k * jnp.exp(cum[-1][None, :] - cum)).T @ v
    state[...] = d_tot[:, None] * s_in + m

    @pl.when(c == num_chunks - 1)
    def _finish():
        fs_ref[...] = state[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_pallas(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                 u: jax.Array, *, chunk: int = 32,
                 interpret: bool = False):
    """r/k/v/w: [B,H,T,N]; u: [H,N] -> (y [B,H,T,N], state [B,H,N,N]).
    T must be a chunk multiple (the ops wrapper pads)."""
    b, h, t, n = r.shape
    assert t % chunk == 0, (t, chunk)
    num_chunks = t // chunk
    rf, kf, vf, wf = (x.reshape(b * h, t, n) for x in (r, k, v, w))

    def x_spec():
        return pl.BlockSpec((None, chunk, n), lambda bh, c: (bh, c, 0))

    y, fs = pl.pallas_call(
        functools.partial(_rwkv6_kernel, num_chunks=num_chunks),
        grid=(b * h, num_chunks),
        in_specs=[x_spec(), x_spec(), x_spec(), x_spec(),
                  pl.BlockSpec((None, n), lambda bh, c: (bh % h, 0))],
        out_specs=[x_spec(),
                   pl.BlockSpec((None, n, n), lambda bh, c: (bh, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b * h, t, n), r.dtype),
                   jax.ShapeDtypeStruct((b * h, n, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, u)
    return (y.reshape(b, h, t, n), fs.reshape(b, h, n, n))
