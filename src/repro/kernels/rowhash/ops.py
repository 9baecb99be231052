"""Dispatching wrappers: Pallas on TPU, oracle (or interpret mode) on CPU."""
from __future__ import annotations

from typing import Tuple

import jax

from repro.kernels import dispatch_path, resolve_use_pallas

from .ref import hash_neighbor_flags_ref, rowhash_ref
from .rowhash import DEFAULT_BLOCK_N, hash_neighbor_flags_pallas, rowhash_pallas


def rowhash(x: jax.Array, *, use_pallas: bool | None = None,
            block_n: int = DEFAULT_BLOCK_N) -> jax.Array:
    """[N, K] int32 -> [N] uint32 row hashes (kernel on TPU, ref elsewhere)."""
    path = dispatch_path("rowhash", resolve_use_pallas(use_pallas))
    if path == "oracle":
        return rowhash_ref(x)
    return rowhash_pallas(x, block_n=block_n, interpret=path == "interpret")


def hash_neighbor_flags(rows: jax.Array, *, use_pallas: bool | None = None,
                        block_n: int = DEFAULT_BLOCK_N
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused (hash, keep, collide) over hash-sorted ``rows[N, K]``.

    Kernel on TPU, pure-jnp oracle elsewhere (the Pallas interpreter is far
    slower than the oracle for this memory-bound pass).
    """
    path = dispatch_path("hash_neighbor_flags",
                         resolve_use_pallas(use_pallas))
    if path == "oracle":
        return hash_neighbor_flags_ref(rows)
    return hash_neighbor_flags_pallas(rows, block_n=block_n,
                                      interpret=path == "interpret")
