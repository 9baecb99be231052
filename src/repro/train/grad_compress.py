"""Int8 error-feedback gradient compression for the cross-pod all-reduce.

At 2 pods the per-step cross-pod traffic of a dense sync is
``2 x params x 4B`` over the slow inter-pod links. This module quantizes
each gradient leaf to int8 (per-leaf max-abs scale) BEFORE the pod
all-reduce and keeps the quantization error in an error-feedback buffer
(added back the next step), which preserves convergence (Seide et al.;
Karimireddy et al.). Traffic drops 4x (fp32) / 2x (bf16 grads).

Implementation: the train step's gradients come out of pjit already
averaged over (data, model) *within* a pod; the compressed stage runs
under ``shard_map`` over the ``pod`` axis only (other axes stay auto), so
the only collective it owns is the pod-axis psum of int8 payloads
(accumulated in int32).
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from repro.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def quantize_leaf(g: jax.Array, err: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """g + err -> (int8 payload, scale, new error)."""
    gf = g.astype(jnp.float32) + err
    scale = jnp.max(jnp.abs(gf)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    new_err = gf - q.astype(jnp.float32) * scale
    return q, scale, new_err


def dequantize_leaf(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def init_error_buffers(params) -> Any:
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compress_allreduce(grads, err_buffers, *, axis: str = "pod"):
    """Per-pod body (inside shard_map over ``axis``): quantize+EF, psum the
    int16 payload over pods, dequantize with the mean scale."""
    n = lax.axis_size(axis)

    def per_leaf(g, e):
        q, scale, new_e = quantize_leaf(g, e)
        # int16 payload: the sum of <=128 pods' int8 values cannot
        # overflow, and the wire carries 2 bytes/param instead of the 4
        # of an f32 all-reduce
        q_sum = lax.psum(q.astype(jnp.int16), axis)
        scale_mean = lax.pmean(scale, axis)
        return (q_sum.astype(jnp.float32) * scale_mean / n).astype(g.dtype), \
            new_e

    out = jax.tree_util.tree_map(per_leaf, grads, err_buffers)
    new_grads = jax.tree_util.tree_map(
        lambda pair: pair[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree_util.tree_map(
        lambda pair: pair[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return new_grads, new_err


def hierarchical_compress_allreduce(grads, err_buffers, *,
                                    pod_axis: str = "pod",
                                    inner_axis: str = "data"):
    """Hierarchical compressed gradient sync (both axes manual):

        reduce-scatter over ``inner_axis`` (within-pod, fast ICI)
        -> int8+EF quantize the 1/|data|-sized shard
        -> int16 psum over ``pod_axis``  (the only cross-DCI transfer)
        -> dequantize -> all-gather over ``inner_axis``

    This matches XLA's own hierarchical all-reduce shape (RS -> cross-pod
    -> AG) but carries 2 B/param over the pod boundary instead of 4 — a
    naive full-copy quantized psum actually moves MORE cross-pod bytes
    than the hierarchy (measured; see EXPERIMENTS.md). The EF buffers live
    on the scattered shard: shape ceil(n / |data|) per leaf
    (:func:`init_scattered_error_buffers`)."""
    n_inner = lax.axis_size(inner_axis)
    n_pods = lax.axis_size(pod_axis)

    def per_leaf(g, e):
        flat = g.astype(jnp.float32).ravel()
        pad = (-flat.shape[0]) % n_inner
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        shard = lax.psum_scatter(flat, inner_axis, scatter_dimension=0,
                                 tiled=True)            # [n_padded/|data|]
        q, scale, new_e = quantize_leaf(shard, e)
        q_sum = lax.psum(q.astype(jnp.int16), pod_axis)
        scale_mean = lax.pmean(scale, pod_axis)
        # /n_pods for the pod mean; /n_inner because the RS summed the
        # per-rank means over the (manual) data axis
        shard_out = (q_sum.astype(jnp.float32) * scale_mean
                     / (n_pods * n_inner))
        full = lax.all_gather(shard_out, inner_axis, axis=0, tiled=True)
        if pad:
            full = full[:-pad]
        return full.reshape(g.shape).astype(g.dtype), new_e

    out = jax.tree_util.tree_map(per_leaf, grads, err_buffers)
    new_grads = jax.tree_util.tree_map(
        lambda p: p[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree_util.tree_map(
        lambda p: p[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return new_grads, new_err


def init_scattered_error_buffers(params, n_inner: int):
    """EF buffers matching the reduce-scattered shard of each leaf."""
    def per(p):
        n = 1
        for d in p.shape:
            n *= d
        return jnp.zeros(((n + n_inner - 1) // n_inner,), jnp.float32)
    return jax.tree_util.tree_map(per, params)


def make_pod_grad_compress(mesh: Mesh, param_specs_tree,
                           axis: str = "pod"):
    """Wrap :func:`compress_allreduce` in shard_map over the pod axis.

    ``param_specs_tree``: tree with the gradients' structure (values
    unused). Only the ``pod`` axis is manual inside the shard_map —
    gradients are replicated across pods (no fsdp_pods), so every in/out
    spec is P() w.r.t. ``pod``; the within-pod (data/model) shardings
    remain automatic and untouched."""
    body = functools.partial(compress_allreduce, axis=axis)
    specs = jax.tree_util.tree_map(lambda _: P(), param_specs_tree)

    def fn(grads, err):
        return shard_map(
            body, mesh=mesh,
            in_specs=(specs, specs), out_specs=(specs, specs),
            check_vma=False, axis_names=frozenset({axis}),
        )(grads, err)

    return fn
