"""Benchmark entry point: one function per paper table/figure.

``python -m benchmarks.run [--scale S] [--smoke]`` runs:

  * group_a     — paper Fig. 8: volume x redundancy grid (2 engines)
  * group_b     — paper Fig. 9: join-condition scenarios
  * table1      — paper Table 1: source-size reduction by pre-processing
  * motivating  — paper Fig. 1: the duplicate blow-up
  * dedup       — δ operator sweep: lex vs hash-first vs distributed
  * partition   — local shard bucketization: sort path vs radix kernel
  * planner     — eager fixpoint vs optimizing planner (docs/planner.md)
  * engine      — KGEngine sessions: cold vs cached vs ingest (docs/engine.md)
  * query       — KGQuery BGPs: cold vs cached latency, queries/s
                  (docs/query.md)
  * serve       — multi-tenant front door: K-compiles-for-T-tenants,
                  typed backpressure, bit-identical isolation
                  (docs/serve.md)
  * roofline    — collated §Roofline table (from dry-run artifacts)

``--smoke`` exercises exactly one tiny cell per group (CI wiring: fast,
asserts all correctness invariants, skips nothing structurally).
Artifacts land in ``experiments/bench/*.json``.
"""
from __future__ import annotations

import argparse
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.25,
                    help="row-count multiplier for the paper grids "
                         "(1.0 = the scaled-down paper testbed)")
    ap.add_argument("--only", default="",
                    help="comma list: group_a,group_b,table1,motivating,"
                         "dedup,partition,planner,engine,query,serve,"
                         "roofline")
    ap.add_argument("--smoke", action="store_true",
                    help="one tiny cell per group (CI)")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()

    from . import dedup, engine, group_a, group_b, motivating, partition, \
        planner, query, roofline, serve, table1

    if args.smoke:
        from repro.configs.mapsdi_paper import CONFIG as PAPER

        from .common import print_csv, save_rows

        def _smoke(name, fn):
            rows = fn()
            save_rows(name, rows)
            print_csv(rows)
            return rows

        # engine first: its warm-start check starts child processes that
        # need the device, before this process touches JAX
        jobs = [
            ("engine", lambda: engine.main(["--smoke"])),
            ("group_a", lambda: _smoke("group_a", lambda: group_a.run(
                scale=0.02, volumes=PAPER.volumes[:1],
                redundancies=PAPER.redundancies[:1], engines=["sdm"]))),
            ("group_b", lambda: _smoke("group_b", lambda: group_b.run(
                scale=0.02, scenarios=PAPER.group_b_scenarios[:1]))),
            ("table1", lambda: _smoke("table1", lambda: table1.run(
                scale=0.02, volumes=PAPER.volumes[:1]))),
            ("motivating", lambda: motivating.main(["--rows", "120"])),
            ("dedup", lambda: dedup.main(["--smoke"])),
            ("partition", lambda: partition.main(["--smoke"])),
            ("planner", lambda: planner.main(["--smoke"])),
            ("query", lambda: query.main(["--smoke"])),
            ("serve", lambda: serve.main(["--smoke"])),
            ("roofline", lambda: roofline.main([])),
        ]
    else:
        jobs = [
            ("engine", lambda: engine.main(
                ["--scale", str(args.scale)])),
            ("group_a", lambda: group_a.main(["--scale", str(args.scale)])),
            ("group_b", lambda: group_b.main(["--scale", str(args.scale)])),
            ("table1", lambda: table1.main(["--scale", str(args.scale)])),
            ("motivating", lambda: motivating.main(
                ["--rows", str(max(200, int(4000 * args.scale)))])),
            ("dedup", lambda: dedup.main([])),
            ("partition", lambda: partition.main([])),
            ("planner", lambda: planner.main(
                ["--scale", str(args.scale)])),
            ("query", lambda: query.main(
                ["--scale", str(args.scale)])),
            ("serve", lambda: serve.main([])),
            ("roofline", lambda: roofline.main([])),
        ]
    for name, fn in jobs:
        if only and name not in only:
            continue
        print(f"\n===== {name} =====")
        t0 = time.perf_counter()
        fn()
        print(f"[{name}: {time.perf_counter() - t0:.1f}s]")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
