"""Per-kernel correctness sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracle, across shapes and dtypes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2.mamba2 import mamba2_ssd_pallas
from repro.kernels.mamba2.ref import ssd_chunked, ssd_scan_ref
from repro.kernels.rowhash.ops import rowhash
from repro.kernels.rowhash.ref import rowhash_ref
from repro.kernels.rowhash.rowhash import rowhash_pallas
from repro.kernels.rwkv6.ref import rwkv6_chunked, rwkv6_scan_ref
from repro.kernels.rwkv6.rwkv6 import rwkv6_pallas


def _rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,kh,s,d", [
    (1, 4, 4, 256, 64),      # MHA
    (2, 4, 2, 128, 64),      # GQA 2:1
    (1, 8, 1, 256, 32),      # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(b, h, kh, s, d, dtype):
    r = _rng(1)
    q = jnp.asarray(r.normal(0, 1, (b, h, s, d)), dtype)
    k = jnp.asarray(r.normal(0, 1, (b, kh, s, d)), dtype)
    v = jnp.asarray(r.normal(0, 1, (b, kh, s, d)), dtype)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_window(window):
    r = _rng(2)
    q = jnp.asarray(r.normal(0, 1, (1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(r.normal(0, 1, (1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(r.normal(0, 1, (1, 2, 256, 64)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_kv_len_mask():
    r = _rng(3)
    q = jnp.asarray(r.normal(0, 1, (1, 2, 1, 64)), jnp.float32)  # decode
    k = jnp.asarray(r.normal(0, 1, (1, 2, 384, 64)), jnp.float32)
    v = jnp.asarray(r.normal(0, 1, (1, 2, 384, 64)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=False, kv_len=200,
                                 interpret=True)
    ref = attention_ref(q, k, v, causal=False, kv_len=200)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_unpadded_seq():
    """Non-block-multiple seq exercises the padding path."""
    r = _rng(4)
    q = jnp.asarray(r.normal(0, 1, (1, 2, 200, 64)), jnp.float32)
    k = jnp.asarray(r.normal(0, 1, (1, 2, 200, 64)), jnp.float32)
    v = jnp.asarray(r.normal(0, 1, (1, 2, 200, 64)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------

def _rwkv_inputs(b, h, t, n, dtype=jnp.float32, seed=5):
    r = _rng(seed)
    rr = jnp.asarray(r.normal(0, 1, (b, h, t, n)), dtype)
    k = jnp.asarray(r.normal(0, 0.3, (b, h, t, n)), dtype)
    v = jnp.asarray(r.normal(0, 1, (b, h, t, n)), dtype)
    w = jnp.asarray(r.uniform(0.6, 0.999, (b, h, t, n)), jnp.float32)
    u = jnp.asarray(r.normal(0, 0.3, (h, n)), jnp.float32)
    return rr, k, v, w, u


@pytest.mark.parametrize("b,h,t,n", [(1, 1, 64, 16), (2, 3, 128, 32),
                                     (1, 2, 96, 64)])
def test_rwkv6_chunked_vs_scan(b, h, t, n):
    rr, k, v, w, u = _rwkv_inputs(b, h, t, n)
    y_ref, s_ref = rwkv6_scan_ref(rr, k, v, w, u)
    y, s = rwkv6_chunked(rr, k, v, w, u, chunk=32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("b,h,t,n,chunk", [(1, 2, 64, 16, 16),
                                           (2, 1, 128, 32, 32),
                                           (1, 1, 64, 64, 32)])
def test_rwkv6_pallas_vs_scan(b, h, t, n, chunk):
    rr, k, v, w, u = _rwkv_inputs(b, h, t, n, seed=6)
    y_ref, s_ref = rwkv6_scan_ref(rr, k, v, w, u)
    y, s = rwkv6_pallas(rr, k, v, w, u, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=1e-3, rtol=1e-3)


def test_rwkv6_bf16_inputs():
    rr, k, v, w, u = _rwkv_inputs(1, 2, 64, 32, dtype=jnp.bfloat16, seed=7)
    y_ref, _ = rwkv6_scan_ref(rr, k, v, w, u)
    y, _ = rwkv6_pallas(rr, k, v, w, u, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_rwkv6_carried_state():
    """Chunked path with a carried state == scan continued from it."""
    rr, k, v, w, u = _rwkv_inputs(1, 2, 128, 16, seed=8)
    y_all, s_all = rwkv6_scan_ref(rr, k, v, w, u)
    half = 64
    _, s_half = rwkv6_scan_ref(rr[:, :, :half], k[:, :, :half],
                               v[:, :, :half], w[:, :, :half], u)
    y2, s2 = rwkv6_chunked(rr[:, :, half:], k[:, :, half:], v[:, :, half:],
                           w[:, :, half:], u, state=s_half, chunk=32)
    np.testing.assert_allclose(np.asarray(y2),
                               np.asarray(y_all[:, :, half:]),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_all),
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# mamba2 SSD
# ---------------------------------------------------------------------------

def _ssd_inputs(b, h, t, p, n, seed=9):
    r = _rng(seed)
    x = jnp.asarray(r.normal(0, 1, (b, h, t, p)), jnp.float32)
    dt = jnp.asarray(r.uniform(0.001, 0.1, (b, h, t)), jnp.float32)
    a = jnp.asarray(-r.uniform(0.5, 2.0, (h,)), jnp.float32)
    bb = jnp.asarray(r.normal(0, 1, (b, t, n)), jnp.float32)
    c = jnp.asarray(r.normal(0, 1, (b, t, n)), jnp.float32)
    return x, dt, a, bb, c


@pytest.mark.parametrize("b,h,t,p,n", [(1, 1, 64, 16, 16), (2, 2, 128, 32, 16),
                                       (1, 3, 192, 64, 64)])
def test_ssd_chunked_vs_scan(b, h, t, p, n):
    x, dt, a, bb, c = _ssd_inputs(b, h, t, p, n)
    y_ref, s_ref = ssd_scan_ref(x, dt, a, bb, c)
    y, s = ssd_chunked(x, dt, a, bb, c, chunk=64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("b,h,t,p,n,chunk", [(1, 2, 128, 16, 16, 32),
                                             (2, 1, 128, 32, 64, 64)])
def test_ssd_pallas_vs_scan(b, h, t, p, n, chunk):
    x, dt, a, bb, c = _ssd_inputs(b, h, t, p, n, seed=10)
    y_ref, s_ref = ssd_scan_ref(x, dt, a, bb, c)
    la = dt * a[None, :, None]
    xdt = x * dt[..., None]
    y, s = mamba2_ssd_pallas(xdt, la, bb, c, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=1e-3, rtol=1e-3)


def test_ssd_carried_state():
    x, dt, a, bb, c = _ssd_inputs(1, 2, 128, 16, 16, seed=11)
    y_all, s_all = ssd_scan_ref(x, dt, a, bb, c)
    _, s_half = ssd_scan_ref(x[:, :, :64], dt[:, :, :64], a,
                             bb[:, :64], c[:, :64])
    y2, s2 = ssd_chunked(x[:, :, 64:], dt[:, :, 64:], a, bb[:, 64:],
                         c[:, 64:], state=s_half, chunk=32)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_all[:, :, 64:]),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_all),
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# rowhash
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k", [(16, 1), (256, 3), (1000, 5), (4096, 8),
                                 (3000, 5), (8199, 2)])
def test_rowhash_matches_ref(n, k):
    r = _rng(12)
    x = jnp.asarray(r.integers(-2**31, 2**31 - 1, (n, k)), jnp.int32)
    got = rowhash_pallas(x, block_n=1024, interpret=True)
    ref = rowhash_ref(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_rowhash_equal_rows_equal_hash():
    x = jnp.asarray([[1, 2, 3], [1, 2, 3], [3, 2, 1]], jnp.int32)
    h = rowhash(x)
    assert h[0] == h[1]
    assert h[0] != h[2]          # (vanishingly unlikely to collide)


def test_rowhash_distribution():
    """Mixed hashes should spread across buckets (chi-square sanity)."""
    r = _rng(13)
    x = jnp.asarray(r.integers(0, 4, (8192, 2)), jnp.int32)  # few distinct
    h = np.asarray(rowhash(x)).astype(np.uint64)
    buckets = h % 16
    # distinct rows only: 16 possible rows -> their buckets should not all
    # collide into one or two values
    distinct = np.unique(np.asarray(x), axis=0)
    hd = np.asarray(rowhash(jnp.asarray(distinct))).astype(np.uint64) % 8
    assert len(np.unique(hd)) >= 4
    assert len(np.unique(buckets)) >= 4


# ---------------------------------------------------------------------------
# fused hash + neighbor-flag kernel (hash-first dedup)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,block_n", [
    # block_n rounds up to the 1024-row (8, 128) granule
    (64, 2, 16), (300, 4, 64), (1024, 5, 256), (257, 3, 128),
    # several blocks, row counts off the granule
    (3000, 4, 1024), (5000, 5, 2048), (8199, 3, 1024),
])
def test_hash_neighbor_flags_matches_ref(n, k, block_n):
    from repro.kernels.rowhash.ref import hash_neighbor_flags_ref
    from repro.kernels.rowhash.rowhash import hash_neighbor_flags_pallas
    r = _rng(21)
    rows = r.integers(0, 6, (n, k)).astype(np.int32)  # many duplicate runs
    h = np.asarray(rowhash_ref(jnp.asarray(rows)))
    rows = jnp.asarray(rows[np.argsort(h, kind="stable")])  # hash-sorted
    got = hash_neighbor_flags_pallas(rows, block_n=block_n, interpret=True)
    ref = hash_neighbor_flags_ref(rows)
    for g, want in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want))


@pytest.mark.parametrize("boundary", [1024, 2048])
def test_hash_neighbor_flags_block_boundary(boundary):
    """A duplicate run and a real 32-bit collision pair that straddle a
    block boundary are flagged exactly as the oracle flags them."""
    from repro.kernels.rowhash.ref import hash_neighbor_flags_ref
    from repro.kernels.rowhash.rowhash import hash_neighbor_flags_pallas
    rows = _rng(22).integers(0, 1 << 20, (3 * 1024 + 5, 2)).astype(np.int32)
    rows[boundary - 3:boundary + 2] = (9, 9)          # run across the edge
    rows[boundary + 1023] = (573955, 771106)          # colliding pair
    rows[boundary + 1024] = (1046201, 851388)         # across the next edge
    rows = jnp.asarray(rows)
    got = hash_neighbor_flags_pallas(rows, block_n=1024, interpret=True)
    ref = hash_neighbor_flags_ref(rows)
    for g, want in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want))
    assert int(ref[1][boundary]) == 0 and int(ref[2][boundary + 1024]) == 1


def test_hash_neighbor_flags_semantics():
    """keep = first occurrence of each duplicate run; collide = equal hash,
    different row (checked on a crafted sequence with both cases)."""
    from repro.kernels.rowhash.ref import hash_neighbor_flags_ref
    rows = jnp.asarray([[1, 2], [1, 2], [1, 2], [5, 6]], jnp.int32)
    h, keep, coll = hash_neighbor_flags_ref(rows)
    np.testing.assert_array_equal(np.asarray(keep), [1, 0, 0, 1])
    np.testing.assert_array_equal(np.asarray(coll), [0, 0, 0, 0])
    # the collide case: adjacent distinct rows with a REAL 32-bit hash
    # collision (pair brute-forced against the production hash)
    rows = jnp.asarray([[573955, 771106], [1046201, 851388]], jnp.int32)
    h, keep, coll = hash_neighbor_flags_ref(rows)
    assert h[0] == h[1]
    np.testing.assert_array_equal(np.asarray(keep), [1, 1])  # rows differ
    np.testing.assert_array_equal(np.asarray(coll), [0, 1])  # flagged
