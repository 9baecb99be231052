"""Dispatching wrapper: Pallas radix partition on TPU, oracle elsewhere.

On top of the shared backend gate (``repro.kernels.resolve_use_pallas``)
this dispatcher applies a *feasibility* gate: the kernel keeps the whole
bucketed output VMEM-resident and unrolls a per-bucket copy loop, so it
only pays off (and only fits) for moderate bucket counts and output
footprints. Infeasible shapes use the oracle — the two are bit-identical,
so results never depend on which path ran; the choice is tallied in
:data:`repro.kernels.DISPATCH_COUNTS` like every dispatcher's.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.kernels import dispatch_path, resolve_use_pallas

from .radix_partition import radix_partition_pallas
from .ref import radix_partition_ref

#: kernel feasibility bounds (beyond them the oracle is used)
MAX_BUCKETS = 64
MAX_VMEM_OUT_BYTES = 6 * 2**20


def vmem_bytes(rows: int, k: int) -> int:
    """Bytes an int32 ``[rows, k]`` block takes in VMEM, which tiles 32-bit
    data in (8 sublanes, 128 lanes): a 5-column row occupies 128 lanes."""
    return -(-rows // 8) * 8 * -(-k // 128) * 128 * 4


def kernel_feasible(n: int, k: int, n_buckets: int, cap_bucket: int,
                    block_n: int = 256) -> bool:
    """True iff the Pallas kernel supports this shape.

    Power-of-two bucket count >= 2 (the kernel's modulo is a bit mask),
    bounded bucket fan-out (per-bucket copy is unrolled), and the resident
    output block must fit comfortably in VMEM, counted in the chip's tiled
    layout (:func:`vmem_bytes`).
    """
    if n == 0 or k == 0:
        return False
    if n_buckets < 2 or n_buckets & (n_buckets - 1) or n_buckets > MAX_BUCKETS:
        return False
    out_bytes = vmem_bytes(n_buckets * cap_bucket + block_n, k)
    return out_bytes + vmem_bytes(2 * block_n, k) <= MAX_VMEM_OUT_BYTES


def radix_partition(data: jax.Array, count: jax.Array, *,
                    n_buckets: int, cap_bucket: int,
                    key_cols: Optional[Tuple[int, ...]] = None,
                    order_preserving: bool = False,
                    use_pallas: Optional[bool] = None,
                    block_n: int = 256
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partition ``data[cap_local, K]``'s first ``count`` rows into
    ``n_buckets`` hash buckets of ``cap_bucket`` rows each.

    Returns ``(buckets [n_buckets, cap_bucket, K], counts [n_buckets],
    overflow)`` with rows in original relative order inside each bucket,
    PAD elsewhere, counts clamped, and ``overflow`` raised (never silent)
    when a bucket's true occupancy exceeds ``cap_bucket``.
    """
    n, k = data.shape
    path = dispatch_path(
        "radix_partition",
        resolve_use_pallas(use_pallas)
        and kernel_feasible(n, k, n_buckets, cap_bucket, block_n))
    if path != "oracle":
        return radix_partition_pallas(
            data, count, n_buckets=n_buckets, cap_bucket=cap_bucket,
            key_cols=None if key_cols is None else tuple(key_cols),
            order_preserving=order_preserving, block_n=block_n,
            interpret=path == "interpret")
    return radix_partition_ref(
        data, count, n_buckets=n_buckets, cap_bucket=cap_bucket,
        key_cols=None if key_cols is None else tuple(key_cols),
        order_preserving=order_preserving)
