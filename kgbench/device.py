"""The chip: its published peaks, the check that the cell's chips are
there, and what JAX reports about them.

The peaks are the benchmark's own copy, keyed by ``device_kind``, so no
change to the system can move the yardstick. Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM
at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links of
50 GB/s). A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float      # FLOP/s
    ops_int8: float        # OP/s
    hbm_bw: float          # bytes/s
    hbm_bytes: float       # bytes
    ici_bw: float          # bytes/s per link


PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, ops_int8=393e12,
                             hbm_bw=819e9, hbm_bytes=16e9, ici_bw=50e9),
}


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def peaks_for(kind: str) -> ChipPeaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known kinds: {sorted(PEAKS)}") from None


def require_chips(n: int) -> List:
    """The first ``n`` TPU devices, or :class:`NoChip`."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's devices are {devices[0].platform!r} "
                     f"({devices[0].device_kind}), not TPU chips")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[:n]


def describe(devices: List) -> Dict[str, object]:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices: List) -> int:
    """The peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))
