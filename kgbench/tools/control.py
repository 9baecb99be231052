"""Readings that set a cell's limits: the program's numbers and the
control's, over many seeds, in one process.

``python3 kgbench/tools/control.py --workload <name> --seeds 1,2,3
--seconds 5`` runs, for each seed, the cell's set-up and a short window
at the cell's own size and load, then prints one JSON line: the numbers
the check compares for the program's output (``program``) and for the
control put in its place (``control``, each loop's ``control_output``:
the reference with one guarantee broken), both after the same window.
The benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

from kgbench import device, harness, registry  # noqa: E402


def readings(cell, seed: int, seconds: float, devices) -> dict:
    ctx = harness.Context(cell=cell, seed=seed, seconds=seconds,
                          peaks=device.peaks_for(devices[0].device_kind))
    loop = cell.loop()
    t0 = time.perf_counter()
    state = loop.setup(ctx)
    ctx.counters = {}
    result = loop.window(ctx, state, seconds)
    control_output = loop.control_output(state)
    checks = loop.check(ctx, state, result)
    control = loop.check(ctx, state, result, control_output)
    return {"seed": seed, "seconds": time.perf_counter() - t0,
            "attempted": result["attempted"],
            "program": {c.name: c.value for c in checks},
            "limits": {c.name: c.limit for c in checks},
            "control": {c.name: c.value for c in control}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = registry.resolve(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        devices = device.require_chips(int(cell.workload["chips"]))
    except device.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    harness.configure_jax()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, devices)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
