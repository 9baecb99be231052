"""Radix-partition kernel package + cost-model calibration tests.

Adversarial coverage for :mod:`repro.kernels.radix_partition` — the local
bucketization stage under every join exchange and global-δ repartition:

* bit-identity of ref oracle, Pallas kernel (interpret mode) and the
  historical sort path across shapes, counts and ``key_cols`` subsets,
* overflow is a *flag*, never silent corruption (all-rows-to-one-bucket),
* empty shards, whole-row vs subset keys, order-preserving top-bit mode,
* a hypothesis property: valid bucket rows are a permutation of the valid
  input rows whenever nothing overflowed,
* the radix-accelerated δ (``distinct_rows_hashed``) is bit-identical to
  the single-sort path it replaces,
* an 8-virtual-device subprocess leg proving the exchange paths built on
  the kernel stay exact,

plus the measured-bandwidth calibration surface: signatures, degenerate
fits, ``join_exchange_cost(calibration=...)`` and store-envelope drift.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributed import _partition_local, _partition_local_sorted
from repro.kernels import (pallas_interpret_forced, resolve_use_pallas)
from repro.kernels.radix_partition import (bucket_shift, kernel_feasible,
                                           radix_partition,
                                           radix_partition_pallas,
                                           radix_partition_ref)
from repro.kernels.radix_partition import ref as radix_ref_mod
from repro.relalg.encoding import PAD_ID

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rows(n, k, seed=0, lo=0, hi=1 << 20):
    rng = np.random.default_rng(seed)
    return rng.integers(lo, hi, size=(n, k)).astype(np.int32)


def _as_tuples(buckets, counts):
    out = []
    for b in range(buckets.shape[0]):
        out.append([tuple(int(v) for v in row)
                    for row in np.asarray(buckets[b][: int(counts[b])])])
    return out


# ---------------------------------------------------------------------------
# differential: ref == Pallas(interpret) == historical sort path
# ---------------------------------------------------------------------------

CASES = [
    # (n, k, n_buckets, cap_bucket, count, key_cols)
    (64, 3, 4, 64, 64, None),
    (200, 5, 8, 128, 137, None),
    (256, 2, 2, 256, 0, None),          # empty shard
    (300, 4, 16, 64, 300, (1, 3)),      # join-key subset
    (128, 1, 4, 64, 100, (0,)),
    (512, 6, 8, 32, 512, None),         # tight caps → likely overflow
]


@pytest.mark.parametrize("n,k,nb,cb,count,key_cols", CASES)
def test_ref_matches_sort_path(n, k, nb, cb, count, key_cols):
    data = jnp.asarray(_rows(n, k, seed=n + k))
    cnt = jnp.int32(count)
    rb, rc, ro = radix_partition_ref(data, cnt, n_buckets=nb, cap_bucket=cb,
                                     key_cols=key_cols)
    sb, sc, so = _partition_local_sorted(data, cnt, nb, cb, None,
                                         key_cols=key_cols)
    assert bool(ro) == bool(so)
    np.testing.assert_array_equal(np.asarray(rc), np.asarray(sc))
    np.testing.assert_array_equal(np.asarray(rb), np.asarray(sb))


@pytest.mark.parametrize("n,k,nb,cb,count,key_cols", CASES)
def test_pallas_interpret_matches_ref(n, k, nb, cb, count, key_cols):
    data = jnp.asarray(_rows(n, k, seed=n + k))
    cnt = jnp.int32(count)
    rb, rc, ro = radix_partition_ref(data, cnt, n_buckets=nb, cap_bucket=cb,
                                     key_cols=key_cols)
    pb, pc, po = radix_partition_pallas(
        data, cnt, n_buckets=nb, cap_bucket=cb, key_cols=key_cols,
        block_n=128, interpret=True)
    assert bool(po) == bool(ro)
    np.testing.assert_array_equal(np.asarray(pc), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(pb), np.asarray(rb))


@pytest.mark.parametrize("order_preserving", [False, True])
def test_pallas_interpret_full_int32_range(order_preserving):
    """Payloads across the whole int32 range (negatives, PAD_ID) survive
    the kernel's 16-bit-limb permutation bit for bit."""
    data = _rows(700, 5, seed=31, lo=-2**31, hi=2**31 - 1)
    data[::7, 2] = PAD_ID
    data[1::7, 0] = -1
    data = jnp.asarray(data)
    cnt = jnp.int32(650)
    kw = dict(n_buckets=8, cap_bucket=160, order_preserving=order_preserving)
    rb, rc, ro = radix_partition_ref(data, cnt, **kw)
    pb, pc, po = radix_partition_pallas(data, cnt, block_n=256,
                                        interpret=True, **kw)
    assert bool(po) == bool(ro)
    np.testing.assert_array_equal(np.asarray(pc), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(pb), np.asarray(rb))


def test_dispatcher_matches_partition_local():
    # the production wiring: _partition_local IS the dispatcher
    data = jnp.asarray(_rows(333, 4, seed=9))
    cnt = jnp.int32(301)
    for key_cols in (None, (0, 2)):
        db, dc, do = _partition_local(data, cnt, 8, 128, None,
                                      key_cols=key_cols)
        sb, sc, so = _partition_local_sorted(data, cnt, 8, 128, None,
                                             key_cols=key_cols)
        assert bool(do) == bool(so)
        np.testing.assert_array_equal(np.asarray(dc), np.asarray(sc))
        np.testing.assert_array_equal(np.asarray(db), np.asarray(sb))


# ---------------------------------------------------------------------------
# adversarial shapes
# ---------------------------------------------------------------------------

def test_all_rows_one_bucket_overflows_without_corruption():
    # every row identical → every row hashes to ONE bucket; cap too small
    row = np.array([[7, 11, 13]], dtype=np.int32)
    data = jnp.asarray(np.repeat(row, 96, axis=0))
    buckets, counts, overflow = radix_partition(
        data, jnp.int32(96), n_buckets=4, cap_bucket=32)
    assert bool(overflow), "overflow must be FLAGGED, not silently dropped"
    counts = np.asarray(counts)
    assert counts.sum() == 32 and counts.max() == 32   # clamped, not garbage
    hot = int(counts.argmax())
    # surviving rows are pristine copies; other buckets stay all-PAD
    np.testing.assert_array_equal(np.asarray(buckets[hot][:32]),
                                  np.repeat(row, 32, axis=0))
    for b in range(4):
        if b != hot:
            assert (np.asarray(buckets[b]) == PAD_ID).all()


def test_empty_shard():
    data = jnp.asarray(_rows(64, 3, seed=1))
    buckets, counts, overflow = radix_partition(
        data, jnp.int32(0), n_buckets=4, cap_bucket=16)
    assert not bool(overflow)
    assert (np.asarray(counts) == 0).all()
    assert (np.asarray(buckets) == PAD_ID).all()


def test_key_cols_subset_groups_equal_keys():
    # equal join keys must land in one bucket regardless of payload cols
    keys = np.repeat(np.arange(16, dtype=np.int32), 8)[:, None]
    payload = _rows(128, 2, seed=3)
    data = jnp.asarray(np.concatenate([keys, payload], axis=1))
    buckets, counts, overflow = radix_partition(
        data, jnp.int32(128), n_buckets=8, cap_bucket=64, key_cols=(0,))
    assert not bool(overflow)
    for b, rows in enumerate(_as_tuples(buckets, counts)):
        for r in rows:
            other = [o for o in rows if o[0] == r[0]]
            assert len(other) == 8       # all 8 payload variants co-located


def test_order_preserving_top_bits():
    nb = 8
    shift = bucket_shift(nb)
    from repro.kernels.rowhash import rowhash
    data = jnp.asarray(_rows(256, 3, seed=4))
    buckets, counts, overflow = radix_partition(
        data, jnp.int32(256), n_buckets=nb, cap_bucket=128,
        order_preserving=True)
    assert not bool(overflow)
    for b in range(nb):
        cnt = int(counts[b])
        if cnt == 0:
            continue
        h = np.asarray(rowhash(buckets[b][:cnt])).astype(np.uint32)
        assert ((h >> shift) == b).all()


def test_bucket_shift_validation():
    assert bucket_shift(2) == 31 and bucket_shift(64) == 26
    for bad in (0, 3, 12):
        with pytest.raises(ValueError):
            bucket_shift(bad)
    with pytest.raises(ValueError):
        radix_partition_pallas(jnp.zeros((8, 2), jnp.int32), jnp.int32(8),
                               n_buckets=3, cap_bucket=8)


def test_kernel_feasibility_gate():
    assert kernel_feasible(1024, 5, 8, 256)
    assert not kernel_feasible(0, 5, 8, 256)          # empty
    assert not kernel_feasible(1024, 5, 3, 256)       # non-power-of-two
    assert not kernel_feasible(1024, 5, 128, 256)     # too many buckets
    assert not kernel_feasible(1 << 22, 8, 64, 1 << 20)   # VMEM blowout


def test_pad_id_parity():
    # the kernel package hard-codes the sentinel; pin it to the encoder's
    assert radix_ref_mod.PAD_ID == PAD_ID


def test_interpret_env_flag(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert not pallas_interpret_forced()
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert pallas_interpret_forced()
    assert resolve_use_pallas(None)          # forced on, even off-TPU
    assert not resolve_use_pallas(False)     # explicit override still wins
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert not pallas_interpret_forced()


# ---------------------------------------------------------------------------
# property: partition is a permutation of the valid rows
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - bare environment
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    @given(
        n=st.integers(1, 200),
        k=st.integers(1, 6),
        nb=st.sampled_from([2, 4, 8, 16]),
        frac=st.floats(0.0, 1.0),
        lo_card=st.booleans(),        # low-cardinality values → collisions
        seed=st.integers(0, 2**16),
    )
    @settings(deadline=None)
    def test_partition_is_permutation_of_valid_rows(n, k, nb, frac,
                                                    lo_card, seed):
        count = int(round(n * frac))
        hi = 4 if lo_card else (1 << 20)
        data = jnp.asarray(_rows(n, k, seed=seed, hi=hi))
        cap = n + 8                   # generous: overflow impossible
        buckets, counts, overflow = radix_partition(
            data, jnp.int32(count), n_buckets=nb, cap_bucket=cap)
        assert not bool(overflow)
        got = sorted(r for rows in _as_tuples(buckets, counts) for r in rows)
        want = sorted(tuple(int(v) for v in row)
                      for row in np.asarray(data)[:count])
        assert got == want


# ---------------------------------------------------------------------------
# δ on the radix path
# ---------------------------------------------------------------------------

def test_radix_dedup_bit_identical_to_sorted():
    from repro.relalg.ops import distinct_rows, distinct_rows_hashed
    for seed, hi in ((0, 50), (1, 1 << 20), (2, 3)):
        data = jnp.asarray(_rows(4096, 4, seed=seed, hi=hi))
        cnt = jnp.int32(4000)
        rd, rn = distinct_rows_hashed(data, cnt, radix=True)
        sd, sn = distinct_rows_hashed(data, cnt, radix=False)
        assert int(rn) == int(sn)
        np.testing.assert_array_equal(np.asarray(rd), np.asarray(sd))
        ld, ln = distinct_rows(data, cnt)
        got = {tuple(map(int, r)) for r in np.asarray(rd)[: int(rn)]}
        want = {tuple(map(int, r)) for r in np.asarray(ld)[: int(ln)]}
        assert got == want


def test_radix_dedup_auto_threshold():
    from repro.relalg.ops import (RADIX_DEDUP_MIN_ROWS, distinct_rows_hashed)
    small = jnp.asarray(_rows(RADIX_DEDUP_MIN_ROWS - 1, 3, seed=5, hi=9))
    big = jnp.asarray(_rows(RADIX_DEDUP_MIN_ROWS, 3, seed=5, hi=9))
    for data in (small, big):
        n = data.shape[0]
        d, cnt = distinct_rows_hashed(data, jnp.int32(n))
        got = {tuple(map(int, r)) for r in np.asarray(d)[: int(cnt)]}
        want = {tuple(map(int, r)) for r in np.asarray(data)}
        assert got == want


def test_radix_dedup_all_pad_content_rows():
    # valid rows whose CONTENT equals the padding sentinel must survive
    from repro.relalg.ops import distinct_rows_hashed
    data = np.full((4096, 3), PAD_ID, dtype=np.int32)
    data[: 2048] = _rows(2048, 3, seed=6, hi=7)
    d, cnt = distinct_rows_hashed(jnp.asarray(data), jnp.int32(4096))
    got = {tuple(map(int, r)) for r in np.asarray(d)[: int(cnt)]}
    want = {tuple(map(int, r)) for r in data}
    assert got == want                   # includes the all-PAD-content row


# ---------------------------------------------------------------------------
# multi-device leg (subprocess so this process keeps 1 device)
# ---------------------------------------------------------------------------

def _run_with_devices(n_devices: int, code: str,
                      extra_env: dict = None) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(extra_env or {})
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr}\nstdout:\n{out.stdout}"
    return out.stdout


_EIGHT_DEVICE_CODE = """
import numpy as np, jax
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map
from repro.launch.mesh import make_mesh
from repro.relalg import Table, distinct
from repro.core.distributed import (distributed_distinct_table,
                                    repartition_by_key, shard_table,
                                    unshard_rows)
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(11)
rows = rng.integers(0, 60, size=(4096, 5)).astype(np.int32)
t = Table.from_codes(rows, list("abcde"))
out, overflow = distributed_distinct_table(t, mesh, "data")
assert not overflow
assert out.row_set() == distinct(t).row_set()
# the join-exchange primitive: hash-repartition by a key column subset
data, counts, cap = shard_table(t, mesh, "data")
def body(d, c):
    out, cnt, ov = repartition_by_key(d, c.reshape(()), axis="data",
                                      n_shards=8, cap_bucket=cap,
                                      key_cols=(0,))
    return out, cnt.reshape(1), ov.reshape(1)
rdata, rcounts, rover = jax.jit(shard_map(
    body, mesh, in_specs=(P("data"), P("data")),
    out_specs=(P("data"), P("data"), P("data"))))(data, counts)
assert not bool(np.asarray(rover).any()), "exchange bucket overflow"
back = unshard_rows(rdata, rcounts, 8 * cap)
assert sorted(map(tuple, back)) == sorted(map(tuple, rows)), "rows lost"
shard_of_key = {}
for s in range(8):
    block = np.asarray(rdata)[s * 8 * cap:(s + 1) * 8 * cap]
    for r in block[: int(np.asarray(rcounts)[s])]:
        assert shard_of_key.setdefault(int(r[0]), s) == s, "key split"
print("OK")
"""


def test_eight_device_exchange_paths_exact():
    out = _run_with_devices(8, _EIGHT_DEVICE_CODE)
    assert "OK" in out


def test_eight_device_interpret_mode_leg():
    # the CI interpret leg: Pallas kernels in interpreter mode, 8 devices
    out = _run_with_devices(8, _EIGHT_DEVICE_CODE,
                            extra_env={"REPRO_PALLAS_INTERPRET": "1"})
    assert "OK" in out


# ---------------------------------------------------------------------------
# measured-bandwidth calibration
# ---------------------------------------------------------------------------

def test_static_calibration_signature():
    from repro.launch.mesh import Calibration, static_calibration
    assert static_calibration().signature() == ("static",)
    measured = Calibration(all_gather_bw=1e9, all_to_all_bw=2e9,
                           launch_s=1e-5, source="measured")
    sig = measured.signature()
    assert sig != ("static",) and sig[0] == "measured"


def test_degenerate_fit_falls_back_to_static():
    from repro.launch.mesh import (_fit_line, make_mesh,
                                   measure_collective_bandwidth)
    # single-device axis: nothing to measure, and the result says so
    mesh = make_mesh((1,), ("data",))
    with pytest.warns(RuntimeWarning, match="nothing to measure"):
        cal = measure_collective_bandwidth(mesh, "data")
    assert cal.source == "static" and "nothing to measure" in cal.fallback
    # non-positive slope → NaN sentinel
    bw, _ = _fit_line([1e6, 2e6, 3e6], [3e-3, 2e-3, 1e-3])
    assert np.isnan(bw)


def test_join_exchange_cost_consumes_calibration():
    from repro.launch.mesh import Calibration
    from repro.plan.annotate import join_exchange_cost
    base = join_exchange_cost(1024, 4, 65536, 6, 8)
    assert base.cost_source == "static"
    # 100x slower links, same wire bytes → same strategy inputs, higher
    # seconds, "measured" provenance
    slow = Calibration(all_gather_bw=50e9 / 100, all_to_all_bw=50e9 / 100,
                       launch_s=0.0, source="measured")
    priced = join_exchange_cost(1024, 4, 65536, 6, 8, calibration=slow)
    assert priced.cost_source == "measured"
    assert priced.gather_bytes == base.gather_bytes
    assert priced.repartition_bytes == base.repartition_bytes
    assert priced.gather_seconds > base.gather_seconds * 10
    assert priced.repartition_seconds > base.repartition_seconds * 10


def test_store_envelope_calibration_drift():
    from repro.api.store import store_envelope
    from repro.launch.mesh import Calibration, static_calibration
    none_env = store_envelope()
    static_env = store_envelope(static_calibration())
    assert none_env == static_env            # static fallback ≡ no calibration
    m1 = Calibration(all_gather_bw=1e9, all_to_all_bw=1e9, launch_s=1e-5,
                     source="measured")
    m2 = Calibration(all_gather_bw=9e9, all_to_all_bw=9e9, launch_s=1e-5,
                     source="measured")
    e1, e2 = store_envelope(m1), store_envelope(m2)
    assert e1 != none_env                    # measured ≠ static
    assert e1 != e2                          # drifted measurement ≠ old one
    assert store_envelope(m1) == e1          # deterministic
