"""Mesh construction for the production pod(s) and local testing.

Importing this module never touches jax device state — meshes are built by
FUNCTIONS so the dry-run can set ``XLA_FLAGS`` before first jax init.

Production target: TPU v5e pods, 256 chips each, mesh (data=16, model=16);
the multi-pod configuration adds a leading ``pod`` axis (2 pods = 512
chips). ``pod`` and ``data`` are both batch-parallel; FSDP weight sharding
stays *within* a pod so cross-pod ICI traffic is one gradient all-reduce
per step.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """jax.make_mesh with explicit Auto axis types and device slicing (the
    dry-run forces 512 host devices but the single-pod mesh uses 256)."""
    n = math.prod(shape)
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    dev_array = np.asarray(devices[:n]).reshape(tuple(shape))
    return jax.sharding.Mesh(
        dev_array, tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """The graded production mesh: (16,16) single pod / (2,16,16) two pods."""
    shape: Tuple[int, ...] = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1, data: Optional[int] = None
                    ) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests/examples)."""
    n = jax.device_count()
    data = data if data is not None else max(1, n // model)
    return make_mesh((data, model), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""
    flops_bf16: float           # FLOP/s
    hbm_bw: float               # bytes/s
    ici_bw: float               # bytes/s per link (~ per exchange direction)
    hbm_bytes: int              # HBM capacity


#: ``jax.Device.device_kind`` of a TPU v5e chip
V5E = "TPU v5 lite"

#: Per-chip peaks keyed by ``device_kind``; a kind not listed has no row
#: (``KeyError``), never a default, and callers off-TPU name ``V5E``.
#: Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB
#: HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links of
#: 50 GB/s).
PEAKS: Dict[str, ChipPeaks] = {
    V5E: ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9,
                   hbm_bytes=16 * 2**30),
}


# ---------------------------------------------------------------------------
# measured-bandwidth collective calibration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Calibration:
    """Per-collective bandwidth model ``t = launch_s + wire_bytes / bw``.

    ``source`` records provenance: ``"static"`` = the v5e datasheet
    constants of :data:`PEAKS` (the cost model's default), ``"measured"`` = fitted
    from microbenchmarks on the live mesh by
    :func:`measure_collective_bandwidth`. The cost model
    (:func:`repro.plan.annotate.join_exchange_cost`) treats the two
    identically — only the numbers (and the plan-cache signature) differ.
    """
    all_gather_bw: float        # bytes/s of per-shard wire bytes
    all_to_all_bw: float        # bytes/s of per-shard wire bytes
    launch_s: float             # fixed per-collective launch cost
    source: str = "static"
    #: why a requested measurement fell back to the static numbers
    #: (``None`` when nothing was requested or the fit succeeded)
    fallback: Optional[str] = None

    def signature(self) -> Tuple:
        """Hashable tag for plan-cache keys / store envelopes. Static
        calibrations share one tag; measured ones carry their numbers, so
        plans costed under different link speeds never collide."""
        if self.source == "static":
            return ("static",)
        return (self.source, round(self.all_gather_bw),
                round(self.all_to_all_bw), round(self.launch_s, 9))


def static_calibration() -> Calibration:
    """The documented-constant cost model as a :class:`Calibration`."""
    from repro.plan.annotate import COLLECTIVE_LAUNCH_S
    ici_bw = PEAKS[V5E].ici_bw
    return Calibration(all_gather_bw=ici_bw, all_to_all_bw=ici_bw,
                       launch_s=COLLECTIVE_LAUNCH_S, source="static")


def _fallback(reason: str) -> Calibration:
    warnings.warn(f"collective calibration fell back to the static v5e "
                  f"numbers: {reason}", RuntimeWarning, stacklevel=3)
    return dataclasses.replace(static_calibration(), fallback=reason)


def _fit_line(wire_bytes: Sequence[float], seconds: Sequence[float]
              ) -> Tuple[float, float]:
    """Least-squares ``t = launch + bytes/bw`` -> (bw, launch)."""
    slope, intercept = np.polyfit(np.asarray(wire_bytes, dtype=np.float64),
                                  np.asarray(seconds, dtype=np.float64), 1)
    if not np.isfinite(slope) or slope <= 0.0:
        return float("nan"), float("nan")
    return 1.0 / float(slope), max(float(intercept), 0.0)


def _zeros(shape: Tuple[int, ...]):
    import jax.numpy as jnp  # deferred: see module docstring
    return jnp.zeros(shape, jnp.int32)


def _best_seconds(fn, x, repeats: int) -> float:
    fn(x)[0].block_until_ready()        # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(x)[0].block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def measure_collective_bandwidth(mesh: jax.sharding.Mesh, axis: str, *,
                                 payload_kib: Sequence[int] = (64, 256, 1024),
                                 repeats: int = 3) -> Calibration:
    """Microbenchmark ``all_gather`` / ``all_to_all`` over ``axis`` and fit
    the two-parameter model ``t = launch + wire_bytes / bw``.

    Wire bytes follow the cost model's convention — bytes *leaving one
    shard*: ``(n-1) · shard_bytes`` for all_gather, ``(n-1)/n · shard_bytes``
    for all_to_all. Degenerate fits (single-device axis, timer-noise-level
    payloads, non-monotone timings) fall back to the static datasheet
    calibration rather than poisoning the cost model with a garbage slope;
    the returned calibration then names the cause in ``fallback`` and a
    warning is issued, so the fallback is never passed off as a result.
    """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    n = int(mesh.shape[axis])
    if n < 2:
        return _fallback(f"axis {axis!r} has {n} device: nothing to measure")
    cols = 128

    def gather_body(x):
        return (lax.all_gather(x, axis, tiled=True),)

    def a2a_body(x):
        return (lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                               tiled=False),)

    gather = jax.jit(shard_map(gather_body, mesh, in_specs=P(axis),
                               out_specs=P(), check_vma=False))
    a2a = jax.jit(shard_map(a2a_body, mesh,
                            in_specs=P(axis, None, None),
                            out_specs=P(axis, None, None)))

    g_bytes, g_secs, a_bytes, a_secs = [], [], [], []
    for kib in payload_kib:
        shard_rows = max(1, (kib * 1024) // (cols * 4))
        x = _zeros((n * shard_rows, cols))
        g_bytes.append((n - 1) * shard_rows * cols * 4)
        g_secs.append(_best_seconds(gather, x, repeats))
        bucket_rows = max(1, shard_rows // n)
        xb = _zeros((n * n, bucket_rows, cols))
        a_bytes.append((n - 1) * bucket_rows * cols * 4)
        a_secs.append(_best_seconds(a2a, xb, repeats))

    g_bw, g_launch = _fit_line(g_bytes, g_secs)
    a_bw, a_launch = _fit_line(a_bytes, a_secs)
    if not (np.isfinite(g_bw) and np.isfinite(a_bw)):
        return _fallback(
            f"degenerate fit (all_gather {g_secs} s, all_to_all {a_secs} s "
            f"for payloads {list(payload_kib)} KiB)")
    return Calibration(all_gather_bw=g_bw, all_to_all_bw=a_bw,
                       launch_s=max(g_launch, a_launch), source="measured")


#: process-wide memo: one microbenchmark pass per (mesh population, axis)
_CALIBRATION_CACHE: Dict[Tuple, Calibration] = {}


def calibrate_mesh(mesh: jax.sharding.Mesh, axis: str, *,
                   payload_kib: Sequence[int] = (64, 256, 1024),
                   repeats: int = 3, force: bool = False) -> Calibration:
    """Session-start calibration entry point (memoized per process).

    Engines created with ``calibrate=True`` call this once per mesh; later
    engines on the same device population reuse the fit. When
    ``REPRO_CALIBRATION_OUT`` names a path, the fit is also dumped there as
    JSON (CI uploads it as a debugging artifact on failure).
    """
    devs = tuple(str(d) for d in np.ravel(mesh.devices))
    key = (axis, devs, tuple(payload_kib), repeats)
    if force or key not in _CALIBRATION_CACHE:
        _CALIBRATION_CACHE[key] = measure_collective_bandwidth(
            mesh, axis, payload_kib=payload_kib, repeats=repeats)
    cal = _CALIBRATION_CACHE[key]
    out = os.environ.get("REPRO_CALIBRATION_OUT")
    if out:
        payload = dict(dataclasses.asdict(cal), axis=axis,
                       n_shards=int(mesh.shape[axis]),
                       backend=jax.default_backend())
        with open(out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
    return cal
