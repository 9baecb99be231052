import gzip
import json
import os
import types

import pytest

from kgbench import device, devtrace, kernel_bytes, layers

MS = 1_000_000  # ns
KERNEL = ("%rowhash_pallas.7 = u32[64,128]{1,0:T(8,128)S(1)} custom-call("
          "s32[5,64,128]{2,1,0:T(8,128)} %copy_bitcast_fusion.3), "
          "custom_call_target=\"tpu_custom_call\"")
SORT = ("%sort.3 = (u32[8,1024]{1,0:T(8,128)}, s32[8,1024]{1,0:T(8,128)}) "
        "sort(u32[8,1024]{1,0:T(8,128)} %a, s32[8,1024]{1,0:T(8,128)} %b), "
        "dimensions={1}, to_apply=%region_8.20")
COND = ("%cond.37 = (s32[16,5]{0,1:T(8,128)}, s32[]{:T(128)}) conditional("
        "s32[]{:T(128)} %p, (s32[16,5]{0,1:T(8,128)}) %t), "
        "branch_computations={%r1, %r2}")
FUSION = ("%fusion.16 = s32[16,5]{0,1:T(8,128)} fusion(s32[16,5]{0,1:T(8,"
          "128)} %b, s32[16]{0:T(1024)} %c), kind=kCustom, calls=%f.16")
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "groupA-create.trace.json.gz")


def _extract():
    """Two devices; window [0, 100 ms); spans upload [0, 40), create_kg
    [40, 100). Device 0: a conditional [50, 60) holding a sort [50, 55)
    and a fusion [55, 60), a kernel [70, 80), an op after the window.
    Device 1: a sort [50, 70)."""
    return {
        "devices": {
            "0": [[COND, 50 * MS, 10 * MS], [SORT, 50 * MS, 5 * MS],
                  [FUSION, 55 * MS, 5 * MS],
                  [KERNEL, 70 * MS, 10 * MS],
                  [FUSION, 120 * MS, 5 * MS]],
            "1": [[SORT, 50 * MS, 20 * MS]],
        },
        "spans": [["window", 0, 100 * MS], ["upload", 0, 40 * MS],
                  ["create_kg", 40 * MS, 60 * MS]],
    }


def test_hlo_parts():
    assert devtrace.hlo_parts(SORT)[:3] == (
        "sort.3", "(u32[8,1024]{1,0:T(8,128)}, s32[8,1024]{1,0:T(8,128)})",
        "sort")
    inst, shape, op, operands = devtrace.hlo_parts(KERNEL)
    assert (inst, op) == ("rowhash_pallas.7", "custom-call")
    assert operands == "s32[5,64,128]{2,1,0:T(8,128)} %copy_bitcast_fusion.3"
    assert devtrace.hlo_parts("jit_fn(123)") == ("jit_fn(123)", "", "", "")
    assert devtrace.short_name(FUSION) == "fusion.16 fusion s32[16,5]"


def test_union_and_gaps():
    iv = [(0, 10), (5, 8), (20, 30), (25, 40), (90, 200)]
    assert devtrace.union_length(iv, 0, 100) == 10 + 20 + 10
    assert devtrace.gaps(iv, 0, 100) == [(10, 20), (40, 90)]
    assert devtrace.union_length([], 0, 10) == 0
    assert devtrace.gaps([], 0, 10) == [(0, 10)]


def test_self_time_takes_nested_ops_out():
    evs = _extract()["devices"]["0"][:3]
    assert devtrace.self_times(evs) == [0.0, 5 * MS, 5 * MS]


def test_busy_idle_and_window_from_the_window_span():
    p = devtrace.Profile.from_extract(_extract(), n_devices=2)
    assert p.window_s == pytest.approx(0.1)
    # device 0 busy 10 + 10 ms (nested ops count once), device 1 20 ms;
    # the op after the window is left out
    assert p.busy_s == pytest.approx(0.020)
    assert p.idle_share == pytest.approx(0.8)


def test_op_class_share():
    p = devtrace.Profile.from_extract(_extract(), n_devices=2)
    run = types.SimpleNamespace(profile=p)
    assert p.op_seconds(layers.is_sort) == pytest.approx(0.0125)
    assert layers.sort_share_pct(run) == pytest.approx(62.5)


def test_breakdown_uses_self_time_and_labels_gaps():
    p = devtrace.Profile.from_extract(_extract(), n_devices=2)
    b = p.breakdown()
    assert b["device_ops"][0] == ["sort.3 sort (u32[8,1024], s32[8,1024])",
                                  pytest.approx(0.0125)]
    assert dict(b["device_ops"])["cond.37 conditional (s32[16,5], s32[])"] \
        == 0.0
    assert {lbl for lbl, _ in b["idle_gaps"]} <= {"upload", "create_kg"}
    assert b["idle_gaps"][0] == ["upload", pytest.approx(0.05)]


def test_kernel_roofline_arithmetic():
    p = devtrace.Profile.from_extract(_extract(), n_devices=2)
    ev = p.events(lambda e: kernel_bytes.kernel_of(e) is not None)
    assert [kernel_bytes.kernel_of(e) for e in ev] == ["rowhash"]
    nbytes = 64 * 128 * 4 + 5 * 64 * 128 * 4
    assert kernel_bytes.hbm_bytes(ev[0]) == nbytes
    run = types.SimpleNamespace(profile=p, peaks=device.peaks_for(
        "TPU v5 lite"))
    want = 100 * (nbytes / 819e9) / 0.010
    assert layers.kernel_roofline_pct(run, {"rowhash"}) == pytest.approx(want)
    assert layers.kernel_roofline_pct(run, {"radix_partition"}) is None


def test_second_view_of_the_input_is_not_counted():
    text = ("%hash_neighbor_flags_pallas.6 = (u32[8,128]{1,0}, s32[8,128]"
            "{1,0}, s32[8,128]{1,0}) custom-call(s32[2,8,128]{2,1,0} %a, "
            "s32[2,8,128]{2,1,0} %b), custom_call_target=\"tpu_custom_call\"")
    assert kernel_bytes.hbm_bytes((text, 0, 1)) == (
        3 * 8 * 128 * 4 + 2 * 8 * 128 * 4)
    assert kernel_bytes.hbm_bytes((FUSION, 0, 1)) is None


def test_no_profile_reads_nothing():
    run = types.SimpleNamespace(profile=None)
    assert layers.idle_share_pct(run) is None
    assert layers.sort_share_pct(run) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        device.peaks_for("TPU v99")
    assert device.peaks_for("TPU v5 lite").hbm_bw == 819e9


def test_a_trace_without_the_devices_is_refused():
    with pytest.raises(ValueError):
        devtrace.Profile.from_extract(_extract(), n_devices=4)


def test_recorded_chip_trace():
    """A traced groupA-create window recorded on one v5e chip (volume 0.5,
    two rebuilds): the numbers its run reported, recomputed."""
    with gzip.open(RECORDED, "rt") as f:
        p = devtrace.Profile.from_extract(json.load(f), n_devices=1)
    run = types.SimpleNamespace(profile=p, peaks=device.peaks_for(
        "TPU v5 lite"))
    assert p.window_s == pytest.approx(14.457303425)
    assert p.busy_s == pytest.approx(9.796195429)
    assert layers.idle_share_pct(run) == pytest.approx(32.2405075, abs=1e-6)
    assert 0 < layers.sort_share_pct(run) < 100
    roof = layers.kernel_roofline_pct(run, {"rowhash", "hash_neighbor_flags"})
    assert 10 < roof < 100
    b = p.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0][0] == "upload"
    assert sum(t for _, t in b["device_ops"]) <= p.busy_s
