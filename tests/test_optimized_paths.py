"""Correctness tests for the §Perf optimized paths: local MoE dispatch,
dense decode attention, u16-packed dedup exchange, int8 grad compression.
Multi-device cases run in subprocesses with forced host devices."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_with_devices(n_devices: int, code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


# ---------------------------------------------------------------------------
# dense decode attention == blockwise == ref
# ---------------------------------------------------------------------------

def test_dense_decode_attention_matches_blockwise():
    from repro.models.layers import blockwise_attention, \
        dense_decode_attention
    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(0, 1, (2, 8, 1, 64)), jnp.float32)
    k = jnp.asarray(r.normal(0, 1, (2, 4, 256, 64)), jnp.float32)
    v = jnp.asarray(r.normal(0, 1, (2, 4, 256, 64)), jnp.float32)
    for kv_len in (256, 200):
        for window in (None, 64):
            a = dense_decode_attention(q, k, v, window=window,
                                       kv_len=kv_len)
            b = blockwise_attention(q, k, v, causal=True, window=window,
                                    kv_len=kv_len, block_k=64)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)


def test_banded_local_attention_matches_blockwise():
    from repro.models.layers import (banded_local_attention,
                                     blockwise_attention)
    r = np.random.default_rng(1)
    for s, w, blk in ((256, 64, 64), (512, 128, 128), (256, 32, 64)):
        q = jnp.asarray(r.normal(0, 1, (2, 4, s, 32)), jnp.float32)
        k = jnp.asarray(r.normal(0, 1, (2, 2, s, 32)), jnp.float32)
        v = jnp.asarray(r.normal(0, 1, (2, 2, s, 32)), jnp.float32)
        a = banded_local_attention(q, k, v, window=w, block=blk)
        b = blockwise_attention(q, k, v, causal=True, window=w,
                                block_k=blk)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_gemma_banded_scan_matches_generic():
    """Period-structured banded scan == homogeneous traced-window scan."""
    import dataclasses
    from repro.configs.base import get_config, reduced_config
    from repro.distributed.sharding import init_params
    from repro.models import get_model
    cfg0 = reduced_config(get_config("gemma3-4b"))
    m = get_model(cfg0.family)
    params = init_params(m.param_specs(cfg0), jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg0.vocab_size, (2, 64)), jnp.int32)
    on = m.apply(dataclasses.replace(cfg0, banded_local=True), params, toks)
    off = m.apply(dataclasses.replace(cfg0, banded_local=False), params,
                  toks)
    np.testing.assert_allclose(np.asarray(on, np.float32),
                               np.asarray(off, np.float32),
                               atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------------
# MoE local dispatch == global dispatch (dropless) on a 2x4 mesh
# ---------------------------------------------------------------------------

def test_moe_local_matches_global_multidevice():
    code = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config, reduced_config, ShapeSpec
from repro.models import auto_rules
from repro.models import moe as M
from repro.models.layers import ShardCtx
from repro.distributed.sharding import init_params, param_shardings
from repro.launch.mesh import make_mesh
cfg0 = reduced_config(get_config('olmoe-1b-7b'))
cfg = dataclasses.replace(cfg0, capacity_factor=float(cfg0.n_experts))
mesh = make_mesh((2, 4), ("data", "model"))
rules = auto_rules(cfg, mesh, ShapeSpec("t", 32, 4, "train"))
ctx = ShardCtx(mesh, rules)
specs = M.moe_mlp_specs(cfg)
p = init_params(specs, jax.random.PRNGKey(1))
p = jax.device_put(p, param_shardings(specs, mesh, rules))
x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (4, 32, cfg.d_model)),
                jnp.bfloat16)
g = jax.jit(lambda p, x: M.moe_block(
    dataclasses.replace(cfg, moe_impl="global"), p, x, ctx))(p, x)
l = jax.jit(lambda p, x: M.moe_block(
    dataclasses.replace(cfg, moe_impl="local"), p, x, ctx))(p, x)
d = np.abs(np.asarray(g, np.float32) - np.asarray(l, np.float32)).max()
assert d <= 0.02, d
# gradients flow and are finite
def loss(p):
    return M.moe_block(dataclasses.replace(cfg, moe_impl="local"),
                       p, x, ctx).astype(jnp.float32).sum()
grads = jax.jit(jax.grad(loss))(p)
assert all(bool(jnp.isfinite(v.astype(jnp.float32)).all())
           for v in jax.tree_util.tree_leaves(grads))
print("OK", d)
"""
    out = _run_with_devices(8, code)
    assert "OK" in out


def test_moe_local_cpu_fallback():
    """Single device / no model axis -> silently uses the global path."""
    import dataclasses
    from repro.configs.base import get_config, reduced_config
    from repro.models import moe as M
    from repro.distributed.sharding import init_params
    cfg = dataclasses.replace(reduced_config(get_config("olmoe-1b-7b")),
                              moe_impl="local")
    p = init_params(M.moe_mlp_specs(cfg), jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (2, 16,
                                                           cfg.d_model)),
                    jnp.bfloat16)
    out = M.moe_block(cfg, p, x, None)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


# ---------------------------------------------------------------------------
# packed dedup exchange
# ---------------------------------------------------------------------------

def test_pack_unpack_roundtrip():
    from repro.core.distributed import pack_u16_pairs, unpack_u16_pairs
    r = np.random.default_rng(3)
    for k in (1, 2, 3, 5, 8):
        x = jnp.asarray(r.integers(0, 65536, (40, k)), jnp.int32)
        packed = pack_u16_pairs(x)
        assert packed.shape == (40, (k + 1) // 2)
        back = unpack_u16_pairs(packed, k)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


@pytest.mark.parametrize("pack", [False, True])
def test_distributed_distinct_packed(pack):
    code = f"""
import numpy as np
from repro.launch.mesh import make_mesh
from repro.relalg import Table, distinct
from repro.core.distributed import distributed_distinct_table
mesh = make_mesh((4,), ("data",))
rng = np.random.default_rng(11)
rows = rng.integers(0, 500, size=(2048, 5)).astype(np.int32)
t = Table.from_codes(rows, list("abcde"))
out, overflow = distributed_distinct_table(t, mesh, "data",
                                           pack_u16={pack})
assert not overflow
assert out.row_set() == distinct(t).row_set()
print("OK", int(out.count))
"""
    out = _run_with_devices(4, code)
    assert "OK" in out


# ---------------------------------------------------------------------------
# int8 error-feedback grad compression
# ---------------------------------------------------------------------------

def test_grad_compress_pod_allreduce():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.train.grad_compress import (compress_allreduce,
                                       init_error_buffers,
                                       make_pod_grad_compress)
mesh = make_mesh((2, 2), ("pod", "data"))
r = np.random.default_rng(5)
# per-pod gradients (replicated over data): simulate with distinct values
g_pod = {"w": jnp.asarray(r.normal(0, 1, (2, 64, 32)), jnp.float32)}

# reference: exact mean over pods
want = np.asarray(g_pod["w"]).mean(axis=0)

specs = {"w": P()}
fn = make_pod_grad_compress(mesh, specs, axis="pod")

# place each pod's grad on its shard: value differs across pod axis =>
# emulate by shard_map over pod ourselves feeding per-pod slices
import functools
from jax import lax
def driver(gs):
    idx = lax.axis_index("pod")
    g = {"w": gs[idx]}
    e = {"w": jnp.zeros_like(g["w"])}
    out, new_e = compress_allreduce(g, e, axis="pod")
    return out["w"]
from repro.compat import shard_map
got = jax.jit(shard_map(driver, mesh=mesh,
    in_specs=P(None, None, None), out_specs=P(None, None),
    check_vma=False, axis_names=frozenset({"pod"})))(g_pod["w"])
err = np.abs(np.asarray(got) - want).max() / max(np.abs(want).max(), 1e-9)
# single-step int8 error ~ max|g|/127 per pod + cross-pod scale mismatch;
# the error-feedback buffer cancels it across steps (separate test)
assert err < 0.06, err
print("OK", err)
"""
    out = _run_with_devices(4, code)
    assert "OK" in out


def test_error_feedback_converges():
    """EF accumulates residuals: mean of compressed grads over steps
    approaches the true mean gradient."""
    from repro.train.grad_compress import quantize_leaf, dequantize_leaf
    g = jnp.asarray(np.random.default_rng(7).normal(0, 1, (256,)),
                    jnp.float32)
    err = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    for _ in range(50):
        q, scale, err = quantize_leaf(g, err)
        total = total + dequantize_leaf(q, scale)
    approx = np.asarray(total) / 50
    assert np.abs(approx - np.asarray(g)).max() < 0.01
