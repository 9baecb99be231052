"""Compile the KG path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jax compiles for a chip
that is described (``v5e:2x2``) but not attached, and refuses what the chip
would refuse (block shapes off the (8, 128) tiling, unsupported casts,
VMEM overruns). Each test asserts that the Mosaic kernel is really in the
compiled program (``tpu_custom_call``), at the sizes the engine runs.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.radix_partition import kernel_feasible, radix_partition_pallas
from repro.kernels.rowhash import hash_neighbor_flags_pallas, rowhash_pallas
from repro.relalg.ops import RADIX_DEDUP_BUCKETS, _radix_dedup_cap, compact

ROWS, COLS = 1 << 20, 5


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 slice (four chips), with the persistent compile
    cache off (its entries for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _largest_feasible(n_buckets, cap_of) -> int:
    """Largest row count ``kernel_feasible`` admits (binary search)."""
    lo, hi = 1, 1 << 24
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if kernel_feasible(mid, COLS, n_buckets, cap_of(mid)):
            lo = mid
        else:
            hi = mid - 1
    return lo


def test_rowhash_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((ROWS, COLS), jnp.int32, sharding=one_chip)
    assert "tpu_custom_call" in _compile_text(rowhash_pallas, x)


def test_hash_neighbor_flags_compile_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((ROWS, COLS), jnp.int32, sharding=one_chip)
    assert "tpu_custom_call" in _compile_text(hash_neighbor_flags_pallas, x)


@pytest.mark.parametrize("order_preserving,n_buckets", [
    (True, RADIX_DEDUP_BUCKETS),     # the hash-δ partition stage
    (False, 4),                      # a four-shard exchange
])
def test_radix_partition_compiles_for_v5e(one_chip, order_preserving,
                                          n_buckets):
    if order_preserving:
        def cap_of(n):
            return _radix_dedup_cap(n, n_buckets)
    else:
        def cap_of(n):
            return -(-n // n_buckets)
    n = _largest_feasible(n_buckets, cap_of)
    assert not kernel_feasible(n + 1, COLS, n_buckets, cap_of(n + 1))
    data = jax.ShapeDtypeStruct((n, COLS), jnp.int32, sharding=one_chip)
    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def part(d, c):
        return radix_partition_pallas(d, c, n_buckets=n_buckets,
                                      cap_bucket=cap_of(n),
                                      order_preserving=order_preserving)
    assert "tpu_custom_call" in _compile_text(part, data, count)


@pytest.mark.parametrize("rows", [8_437_824, 2_097_152])
def test_compact_compiles_without_scatter_for_v5e(one_chip, rows):
    """At the sink δ's and the emitter's row counts in the benchmark's two
    cells, compaction is a sort: a row scatter costs 60–90 ns per row on
    the v5e."""
    data = jax.ShapeDtypeStruct((rows, COLS), jnp.int32, sharding=one_chip)
    keep = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip)
    ops = set(re.findall(r"\s(sort|scatter)\(", _compile_text(compact, data,
                                                              keep)))
    assert ops == {"sort"}


@pytest.mark.parametrize("kernel", ["rowhash", "hash_neighbor_flags",
                                    "radix_partition"])
def test_kernels_compile_inside_shard_map_for_v5e_mesh(four_chips, kernel):
    """Inside the fused mesh plan the kernels run per shard under
    ``shard_map``'s replication check: their outputs must declare the mesh
    axes they vary over."""
    rows = jax.ShapeDtypeStruct((4 * 8192, COLS), jnp.int32,
                                sharding=NamedSharding(four_chips, P("data")))
    counts = jax.ShapeDtypeStruct((4,), jnp.int32,
                                  sharding=NamedSharding(four_chips, P("data")))
    bodies = {
        "rowhash": lambda x, n: rowhash_pallas(x),
        "hash_neighbor_flags": lambda x, n: hash_neighbor_flags_pallas(x),
        "radix_partition": lambda x, n: radix_partition_pallas(
            x, n[0], n_buckets=4, cap_bucket=2048)[:2],
    }
    fn = jax.shard_map(bodies[kernel], mesh=four_chips,
                       in_specs=(P("data"), P("data")), out_specs=P("data"))
    assert "tpu_custom_call" in _compile_text(fn, rows, counts)
