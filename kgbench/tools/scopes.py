"""Where a traced run's window went, by the system's own names: device
idle by the innermost span over it (``idle_by_span``), device self time by
plan-operator scope (``device_by_scope``, and by whole scope path), how
much of each the names cover, the longest idle gaps with the spans open
around each, and the counts of ops in the window.

``python3 kgbench/tools/scopes.py [trace dir] [--fixture out.json.gz]``
(default: the last traced run's ``.kgbench_trace``; a ``.json.gz``
fixture is read as well), from the root of a checkout, after a traced run:
it reads the trace file, not the chip. ``--fixture`` also writes the trace
reduced to what the readers need (``devtrace.extract`` plus
``progtrace.extract``'s keys), as the tests' recorded traces are. Prints
one JSON object.
"""
import argparse
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[0:1] = [ROOT]

from kgbench import devtrace, progtrace  # noqa: E402

#: the benchmark's spans around calls into the system
CALL_SPANS = ("create_kg", "ingest")


def summary(data) -> dict:
    """The numbers this tool prints, from an extract."""
    profile = devtrace.Profile.from_extract(
        data, n_devices=len(data["devices"]))
    prog = progtrace.Program(profile, data)
    lo, hi = profile.window
    idle = prog.idle_by_span()
    by_scope = prog.device_by_scope()
    busy_self = sum(by_scope.values())
    calls = prog.idle_by_span(within=[(s, s + d) for n, s, d in
                                      profile.spans if n in CALL_SPANS])
    inside = sum(calls.values())
    ops = [ev for evs in profile.devices.values() for ev in evs
           if lo <= ev[1] < hi]
    return {
        "window_s": profile.window_s, "busy_s": profile.busy_s,
        "idle_s": profile.window_s - profile.busy_s,
        "idle_by_span": idle,
        "device_by_scope": by_scope,
        "device_by_scope_path": prog.device_by_scope(depth=99),
        "busy_scoped_share": (1 - by_scope.get(progtrace.NO_SCOPE, 0.0)
                              / busy_self) if busy_self else None,
        "idle_named_share": _named(idle),
        "call_idle_s": inside,
        "call_idle_named_share": _named(calls),
        "longest_gaps": prog.longest_gaps(),
        "spans": {n: prog.count(n) for n in sorted({s[0]
                                                   for s in prog.spans})},
        "ops": len(ops),
        "fusion_ops": sum(devtrace.hlo_parts(ev[0])[2] == "fusion"
                          for ev in ops),
        "instructions": len({devtrace.hlo_parts(ev[0])[0] for ev in ops}),
    }


def _named(idle) -> float:
    """The share of idle seconds under one of the program's spans."""
    total = sum(idle.values())
    named = sum(v for k, v in idle.items() if k.startswith("repro."))
    return named / total if total else None


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?",
                    default=os.path.join(ROOT, ".kgbench_trace"))
    ap.add_argument("--fixture")
    args = ap.parse_args(argv)
    if os.path.isfile(args.trace):
        with gzip.open(args.trace, "rt") as f:
            data = json.load(f)
    else:
        data = progtrace.extract(args.trace)
    if args.fixture:
        with gzip.open(args.fixture, "wt") as f:
            json.dump(data, f)
    print(json.dumps(summary(data)))


if __name__ == "__main__":
    main()
