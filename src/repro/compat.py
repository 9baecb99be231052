"""``shard_map`` with the repo's interpret-mode replication policy.

Import :func:`shard_map` from here instead of calling ``jax.shard_map``
directly, so every distributed body gets the same ``check_vma`` default.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, axis_names=None, check_vma=None):
    """``jax.shard_map`` with ``axis_names`` (manual axes; default: all
    mesh axes) and ``check_vma`` passed through when given.

    When ``REPRO_PALLAS_INTERPRET`` forces interpret-mode Pallas kernels
    into the distributed bodies (the CI interpret leg), the replication
    check defaults to off: ``pallas_call`` has no replication rule, and
    every collective body here produces explicitly sharded outputs anyway.
    """
    if check_vma is None:
        from repro.kernels import pallas_interpret_forced
        if pallas_interpret_forced():
            check_vma = False
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


__all__ = ["shard_map"]
