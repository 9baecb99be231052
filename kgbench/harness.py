"""One run of one cell: set-up, a measured window, the check, one line.

``python3 kgbench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` (or ``python3 -m kgbench ...``) from the root of a
checkout. The run needs the cell's TPU chips and exits 3 without a result
when JAX finds fewer. It generates its data from ``--seed``, warms every
shape the window uses (set-up), measures for ``--seconds``, then compares
what the window produced with the plain reference and prints, as the last
line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics; with ``--trace 1``
its per-layer metrics instead), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit,
also printed as the last lines of standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional, Tuple

from kgbench import device, registry
from kgbench.spans import Spans

#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: is not set: a fixed path inside the checkout, so the next run hits it
CACHE_DIR = os.path.join(registry.ROOT, ".jax_cache")
#: where a traced run writes its profile (replaced by each traced run)
TRACE_DIR = os.path.join(registry.ROOT, ".kgbench_trace")


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Context:
    """What a loop and a metric reader see of the run."""

    cell: registry.Cell
    seed: int
    seconds: float
    spans: Spans = dataclasses.field(default_factory=Spans)
    counters: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    profile: Optional[object] = None      # devtrace.Profile of a traced run
    peaks: Optional[device.ChipPeaks] = None

    @property
    def cfg(self) -> Dict:
        return self.cell.config

    @property
    def traffic(self) -> Dict:
        return self.cell.traffic

    def count(self, name: str, value: float) -> None:
        self.counters.setdefault(name, []).append(value)


class CompileCounter:
    """Programs JAX compiled or loaded from its cache since start-up."""

    def __init__(self) -> None:
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1


def configure_jax() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however fast it compiled, so that set-up after
    # the first run of a cell loads and never compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def parse_args(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="kgbench", description=__doc__.split(
        "\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _metric(value: float, unit: str) -> Dict[str, object]:
    return {"value": float(value), "unit": unit}


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float) -> Tuple[Dict, List[Check]]:
    """Set-up, window and check of one cell; returns the result object
    (without ``checks``) and the checks."""
    from kgbench import devtrace as tr
    counter = CompileCounter()
    ctx = Context(cell=cell, seed=seed, seconds=seconds,
                  peaks=device.peaks_for(devices[0].device_kind))
    loop = cell.loop()
    state = loop.setup(ctx)
    setup_s = time.perf_counter() - t_start

    ctx.spans, ctx.counters = Spans(), {}
    compiles0 = counter.n
    recompiles0 = loop.recompiles(state)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = tr.Tracer(TRACE_DIR)
        tracer.start()
    with ctx.spans.span("window"):
        result = loop.window(ctx, state, seconds)
    if trace:
        tracer.stop()
    window = {"seconds": result["window_s"], "compiles": counter.n - compiles0,
              "recompiles": loop.recompiles(state) - recompiles0}
    window.update(result.get("notes", {}))
    memory = device.memory_peak_bytes(devices)

    with ctx.spans.span("check"):
        checks = loop.check(ctx, state, result)
    out: Dict[str, object] = {
        "correct": bool(checks) and all(c.ok for c in checks)
        and result["failed"] == 0,
        "attempted": int(result["attempted"]), "failed": int(result["failed"]),
        "metrics": {},
        "device": dict(device.describe(devices), memory_peak_bytes=memory),
        "window": window,
    }
    if not trace:
        values = dict(result["metrics"], setup_s=setup_s)
        out["metrics"] = {m["name"]: _metric(values[m["name"]], m["unit"])
                          for m in cell.end_to_end()}
        return out, checks
    ctx.profile = tr.Profile.load(TRACE_DIR, n_devices=len(devices))
    for m in cell.per_layer():
        value = registry.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out["metrics"][m["name"]] = _metric(value, m["unit"])
    out["device"].update(busy_s=ctx.profile.busy_s,
                         window_s=ctx.profile.window_s)
    out["breakdown"] = ctx.profile.breakdown()
    return out, checks


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(registry.ROOT, "src", "repro")):
        print("kgbench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    try:
        cell = registry.resolve(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"kgbench: {e}", file=sys.stderr)
        return 2
    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        devices = device.require_chips(int(cell.workload["chips"]))
    except device.NoChip as e:
        print(f"kgbench: no result: {e}", file=sys.stderr)
        return 3
    configure_jax()
    out, checks = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices, t_start)
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    sys.stdout.flush()
    for c in checks:
        print(f"check {c.name} = {c.value:g} (limit {c.limit:g}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
