#!/usr/bin/env python3
"""Chip smoke test: the KG engine's main path, end to end, on one TPU chip.

One process drives the public API (``repro.api.KGEngine``, ``EngineConfig``,
``Query``) through the phases users depend on, and checks every result
against a reference that shares no compiled code with the path under test:

* device  — the platform must be ``tpu``; nothing continues on a CPU;
* kernels — the three Pallas kernels of the δ and exchange paths, compiled,
            against their ``ref.py`` oracles on random data;
* load    — paper Group A (3 sources x 500k rows, 8 noise attributes):
            ``create_kg`` equals the eager MapSDI reference
            (``apply_mapsdi_eager`` + RDFizer, lex δ) and the T-framework KG,
            both computed on the host CPU backend;
* ingest  — paper Group B (a ⋈ between two sources, 200k rows each), then
            3 ingests of 20k rows per source, each equal to a fresh
            ``create_kg`` over the accumulated sources;
* query   — BGP queries over the Group B KG (one pattern, a 2-hop join, a
            filter) against the NumPy ``bgp_oracle``;
* store   — a persistent ``PlanStore`` under ``.plan_store_smoke/``: after
            the in-process plan cache is cleared, a new engine must be
            served from disk with no rejects, with identical codes;
* paths   — which path each kernel dispatcher took (compiled Pallas,
            interpret mode, oracle); on a TPU interpret mode fails, and so
            does a ``rowhash`` or ``hash_neighbor_flags`` that never
            compiled.

``--chips 4`` runs only the fused mesh phase instead: Group B (20k rows per
source) on a 4-device ``("data",)`` mesh, ``create_kg`` + one ingest + the
queries under each ⋈ exchange (``gather``, ``repartition``, ``auto``),
compared bit for bit with the single-device path on device 0, and a
collective calibration that must come out ``measured``.

Any failed check exits non-zero. The last line of standard output is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.

Usage::

    python chip_smoke.py               # one chip, full sizes
    python chip_smoke.py --chips 4     # the mesh phase on four chips

Rehearsal without a chip (tiny sizes; kernels run in the Pallas
interpreter; ``--allow-cpu`` relaxes only the device check)::

    JAX_PLATFORMS=cpu REPRO_PALLAS_INTERPRET=1 \\
        python chip_smoke.py --allow-cpu --scale 0.002
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --allow-cpu --scale 0.002 --chips 4

JAX's persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or to ``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

#: full sizes (scaled by --scale)
GROUP_A_ROWS = 500_000        # per source; 3 sources
GROUP_B_ROWS = 200_000        # per source; 2 sources
INGEST_ROWS = 20_000          # per source and batch
N_INGESTS = 3
#: Group B rows per source in the four-chip phase: it checks bit-identity
#: of the fused mesh plan, compiled once per ⋈ exchange, and four chips
#: cost four times as much per second
MESH_GROUP_B_ROWS = 20_000


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def scaled(n: int, scale: float, floor: int = 16) -> int:
    return max(floor, int(round(n * scale)))


# ---------------------------------------------------------------------------
# helpers over the public API
# ---------------------------------------------------------------------------

def code_rows(kg):
    """The KG's valid code rows, lexicographically sorted."""
    import numpy as np
    codes = np.asarray(kg.to_codes())
    return codes[np.lexsort(codes.T[::-1])] if len(codes) else codes


def same_codes(a, b) -> bool:
    import numpy as np
    return np.array_equal(code_rows(a), code_rows(b))


def on_host_cpu(dis, sources):
    """A copy of ``dis`` over ``sources`` moved to the host CPU backend,
    and the context that runs JAX there. The references run on the CPU:
    independent of the device path under test, and cheap to compile."""
    import jax
    cpu = jax.devices("cpu")[0]
    acc = dis.copy()
    acc.sources = jax.device_put(dict(sources), cpu)
    return acc, jax.default_device(cpu)


def eager_reference(dis, sources):
    """MapSDI's eager fixpoint + the RDFizer emitters, lex δ throughout:
    the reference that uses none of the engine's plans or hash kernels."""
    from repro.core.rdfizer import RDFizer
    from repro.core.transform import apply_mapsdi_eager
    acc, on_cpu = on_host_cpu(dis, sources)
    with on_cpu:
        pre, _ = apply_mapsdi_eager(acc, dedup="lex")
        kg, _ = RDFizer(pre, "sdm", dedup="lex")()
    return kg


def t_framework_reference(dis):
    """The paper's baseline KG (blind RDFization, sink δ) on the CPU."""
    from repro.core.tframework import t_framework_create_kg
    acc, on_cpu = on_host_cpu(dis, dis.sources)
    with on_cpu:
        kg, _ = t_framework_create_kg(acc, engine="rmlmapper", dedup="lex")
    return kg


def queries_for(kg):
    """One pattern, a 2-hop join, and a filtered pattern."""
    import numpy as np
    from repro.api import Query, QueryFilter, TriplePattern
    codes = np.asarray(kg.to_codes())
    check(len(codes) > 0, "empty KG: nothing to query")
    # a predicate whose objects are subjects elsewhere: the join's hop
    subjects = {(int(r[0]), int(r[1])) for r in codes}
    hop = [r for r in codes if (int(r[3]), int(r[4])) in subjects]
    check(len(hop) > 0, "KG has no 2-hop path to query")
    p_hop = int(hop[0][2])
    return {
        "scan": Query(patterns=[TriplePattern("?s", "?p", "?o")]),
        "join": Query(patterns=[TriplePattern("?s", "?p", "?o"),
                                TriplePattern("?o", "?p2", "?o2")],
                      project=("?s", "?o2")),
        "filter": Query(patterns=[TriplePattern("?s", "?p", "?o")],
                        filters=[QueryFilter("?p", "eq", p_hop)]),
    }


def answer_rows(res):
    import numpy as np
    codes = np.asarray(res.to_codes())
    if not len(codes):
        return np.zeros((0, len(res.attrs)), np.int32)
    return np.unique(codes, axis=0)


def group_b_batch(seed: int, n: int, attrs, vocab):
    from repro.data.synthetic import make_group_b_extension_records
    from repro.relalg import Table
    recs = make_group_b_extension_records(n, seed=seed)
    return {name: Table.from_records(rows, attrs[name], vocab)
            for name, rows in sorted(recs.items())}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(args):
    import importlib.metadata as md

    import jax
    import jaxlib
    devices = jax.devices()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    dev = devices[0]
    log("device", devices=devices, platform=dev.platform,
        kind=repr(dev.device_kind), count=len(devices), jax=jax.__version__,
        jaxlib=jaxlib.__version__, libtpu=libtpu)
    if dev.platform != "tpu":
        if not args.allow_cpu:
            print(f"chip_smoke: no TPU found: JAX's first device is a "
                  f"{dev.platform!r} device ({dev.device_kind}). This "
                  f"smoke run needs a TPU chip.", file=sys.stderr)
            raise SystemExit(1)
        log("device", note="--allow-cpu: rehearsal on a non-TPU backend")
    check(len(devices) >= args.chips,
          f"need {args.chips} devices, JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def phase_kernels(scale: float):
    """Each compiled kernel against its oracle on random data."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import pallas_interpret
    from repro.kernels.radix_partition import (kernel_feasible,
                                               radix_partition_pallas,
                                               radix_partition_ref)
    from repro.kernels.rowhash import (hash_neighbor_flags_pallas,
                                       hash_neighbor_flags_ref,
                                       rowhash_pallas, rowhash_ref)
    interpret = pallas_interpret()
    rng = np.random.default_rng(0)
    n = scaled(1 << 20, scale, floor=3000) + 77     # off the 1024-row tile
    x = jnp.asarray(rng.integers(-2**31, 2**31 - 1, (n, 5)), jnp.int32)
    got, want = rowhash_pallas(x, interpret=interpret), rowhash_ref(x)
    check(bool(jnp.array_equal(got, want)), "rowhash kernel != ref")
    # duplicate runs (each row 3 times) across every tile boundary, and
    # distinct neighbours in between
    dup = jnp.repeat(x[:-(-n // 3)], 3, axis=0)[:n]
    got = hash_neighbor_flags_pallas(dup, interpret=interpret)
    for g, w, name in zip(got, hash_neighbor_flags_ref(dup),
                          ("hash", "keep", "collide")):
        check(bool(jnp.array_equal(g, w)),
              f"hash_neighbor_flags kernel != ref ({name})")
    kept = int(jnp.sum(got[1]))
    # radix partition at the largest row count the dispatcher admits for
    # 8 order-preserving buckets (the hash δ's partition stage)
    nb = 8
    rows = 1
    while kernel_feasible(rows * 2, 5, nb, -(-rows * 2 // nb) + 64):
        rows *= 2
    cap = -(-rows // nb) + 64
    data = jnp.asarray(rng.integers(-2**31, 2**31 - 1, (rows, 5)), jnp.int32)
    count = jnp.int32(rows - 13)
    for order in (False, True):
        got = radix_partition_pallas(data, count, n_buckets=nb,
                                     cap_bucket=cap, order_preserving=order,
                                     interpret=interpret)
        want = radix_partition_ref(data, count, n_buckets=nb,
                                   cap_bucket=cap, order_preserving=order)
        for g, w in zip(got, want):
            check(bool(jnp.array_equal(g, w)),
                  f"radix_partition kernel != ref (order_preserving={order})")
    jax.block_until_ready(got)
    log("kernels", rowhash_rows=n, flags_rows=n, flags_kept=kept,
        radix_rows=rows, radix_buckets=nb, radix_cap=cap,
        interpret=interpret, result="equal to ref")


def phase_load(scale: float):
    from repro.api import EngineConfig, KGEngine
    from repro.data.synthetic import make_group_a_dis
    from repro.relalg import host_int
    n = scaled(GROUP_A_ROWS, scale)
    t0 = time.perf_counter()
    dis = make_group_a_dis(n, 0.5, seed=0)
    gen_s = time.perf_counter() - t0
    records = sum(host_int(t.count) for t in dis.sources.values())
    check(records == 3 * n, f"Group A has {records} records, not {3 * n}")
    t0 = time.perf_counter()
    eng = KGEngine(dis, config=EngineConfig(engine="sdm", dedup="hash"))
    kg, stats = eng.create_kg()
    kg.data.block_until_ready()
    engine_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = eager_reference(dis, dis.sources)
    check(same_codes(kg, ref), "Group A KG != eager MapSDI reference")
    check(same_codes(kg, t_framework_reference(dis)),
          "Group A KG != T-framework KG")
    ref_s = time.perf_counter() - t0
    log("load", sources=len(dis.sources), records=records,
        kg_triples=stats["kg_triples"], raw_triples=stats["raw_triples"],
        generate_s=round(gen_s, 3), engine_s=round(engine_s, 3),
        reference_s=round(ref_s, 3),
        result="equal to eager reference and T-framework")


def phase_ingest(scale: float):
    from repro.api import EngineConfig, KGEngine
    from repro.data.synthetic import make_group_b_dis
    from repro.relalg import host_int
    config = EngineConfig(engine="sdm", dedup="hash")
    n = scaled(GROUP_B_ROWS, scale)
    t0 = time.perf_counter()
    dis = make_group_b_dis(n, seed=0)
    gen_s = time.perf_counter() - t0
    check(bool(dis.maps[0].poms) and dis.maps[0].poms[0].object.__class__
          .__name__ == "RefObjectMap", "Group B lost its join condition")
    t0 = time.perf_counter()
    eng = KGEngine(dis, config=config)
    kg, stats = eng.create_kg()
    kg.data.block_until_ready()
    engine_s = time.perf_counter() - t0
    check(same_codes(kg, eager_reference(dis, dis.sources)),
          "Group B KG != eager MapSDI reference")
    log("ingest", step="create", records=sum(
        host_int(t.count) for t in dis.sources.values()),
        kg_triples=stats["kg_triples"], generate_s=round(gen_s, 3),
        engine_s=round(engine_s, 3))
    attrs = {name: t.attrs for name, t in dis.sources.items()}
    batch_rows = scaled(INGEST_ROWS, scale, floor=8)
    for i in range(N_INGESTS):
        deltas = group_b_batch(100 + i, batch_rows, attrs, eng.vocab)
        t0 = time.perf_counter()
        kg, stats = eng.ingest(deltas)
        kg.data.block_until_ready()
        ingest_s = time.perf_counter() - t0
        acc = dis.copy()
        acc.sources = dict(eng.sources)
        fresh, _ = KGEngine(acc, config=config).create_kg()
        check(same_codes(kg, fresh),
              f"KG after ingest {i + 1} != fresh create_kg")
        check(same_codes(kg, eager_reference(dis, eng.sources)),
              f"KG after ingest {i + 1} != eager MapSDI reference")
        log("ingest", step=i + 1, batch_rows_per_source=batch_rows,
            records=sum(host_int(t.count) for t in eng.sources.values()),
            kg_triples=stats["kg_triples"], recompiles=stats["recompiles"],
            ingest_s=round(ingest_s, 3), result="equal to fresh create_kg")
    return dis, eng, kg


def phase_query(eng, kg):
    from repro.query.oracle import bgp_oracle
    import numpy as np
    for name, q in queries_for(kg).items():
        t0 = time.perf_counter()
        res = eng.query(q)
        got = answer_rows(res)
        query_s = time.perf_counter() - t0
        check(len(got) > 0, f"query {name!r} answered nothing")
        check(np.array_equal(got, bgp_oracle(kg, q)),
              f"query {name!r} != bgp_oracle")
        log("query", name=name, patterns=len(q.patterns), answers=len(got),
            query_s=round(query_s, 3), result="equal to bgp_oracle")


def phase_store(dis):
    from repro.api import (EngineConfig, KGEngine, PlanStore,
                           clear_plan_cache)
    root = os.path.join(HERE, ".plan_store_smoke")
    shutil.rmtree(root, ignore_errors=True)   # this run's own entries only
    store = PlanStore(root)
    config = EngineConfig(engine="sdm", dedup="hash", plan_store=store)
    clear_plan_cache()
    kg_w, st_w = KGEngine(dis, config=config).create_kg()
    check(store.writes >= 1 and store.write_errors == 0,
          f"store write-back failed: writes={store.writes} "
          f"write_errors={store.write_errors}")
    clear_plan_cache()
    t0 = time.perf_counter()
    kg_r, st_r = KGEngine(dis, config=config).create_kg()
    kg_r.data.block_until_ready()
    load_s = time.perf_counter() - t0
    check(st_r["store_hits"] == 1 and st_r["store_rejects"] == 0
          and store.write_errors == 0,
          f"plan store not served cleanly: hits={st_r['store_hits']} "
          f"rejects={st_r['store_rejects']} "
          f"write_errors={store.write_errors} "
          f"reasons={store.reject_reasons}")
    check(same_codes(kg_w, kg_r), "store-served KG != the compiled one")
    log("store", root=os.path.relpath(root, HERE), writes=store.writes,
        store_hits=st_r["store_hits"], store_rejects=st_r["store_rejects"],
        write_errors=store.write_errors, first_kg_s=round(load_s, 3),
        result="hit, codes identical")


def phase_paths(on_tpu: bool):
    from repro.kernels import DISPATCH_COUNTS
    counts = dict(DISPATCH_COUNTS)
    for kernel in ("rowhash", "hash_neighbor_flags", "radix_partition"):
        log("paths", kernel=kernel,
            **{path: counts.get((kernel, path), 0)
               for path in ("compiled", "interpret", "oracle")})
    if on_tpu:
        interp = {k: n for (k, path), n in counts.items()
                  if path == "interpret" and n}
        check(not interp, f"kernels took interpret mode on a TPU: {interp}")
        for kernel in ("rowhash", "hash_neighbor_flags"):
            check(counts.get((kernel, "compiled"), 0) > 0,
                  f"{kernel} never took the compiled Pallas path")


def phase_mesh(scale: float):
    """The fused shard_map plan on a 4-device mesh vs device 0 alone."""
    import jax
    import numpy as np
    from repro.api import EngineConfig, KGEngine
    from repro.data.synthetic import make_group_b_dis
    from repro.launch.mesh import calibrate_mesh, make_mesh
    from repro.query.oracle import bgp_oracle
    mesh = make_mesh((4,), ("data",))
    n = scaled(MESH_GROUP_B_ROWS, scale)
    dis = make_group_b_dis(n, seed=0)
    attrs = {name: t.attrs for name, t in dis.sources.items()}
    batch_rows = scaled(MESH_GROUP_B_ROWS // 10, scale, floor=8)

    def session(**mesh_cfg):
        t0 = time.perf_counter()
        eng = KGEngine(dis, config=EngineConfig(engine="sdm", dedup="hash",
                                                **mesh_cfg))
        kg, _ = eng.create_kg()
        kg_i, _ = eng.ingest(group_b_batch(100, batch_rows, attrs,
                                           eng.vocab))
        answers = {name: answer_rows(eng.query(q))
                   for name, q in queries_for(kg_i).items()}
        jax.block_until_ready(kg_i.data)
        return eng, kg, kg_i, answers, time.perf_counter() - t0

    with jax.default_device(jax.devices()[0]):
        _, kg1, kg1_i, ans1, single_s = session()
    for name, q in queries_for(kg1_i).items():
        check(np.array_equal(ans1[name], bgp_oracle(kg1_i, q)),
              f"single-device query {name!r} != bgp_oracle")
    log("mesh", path="single-device", device=jax.devices()[0],
        kg_triples=int(kg1_i.count), seconds=round(single_s, 3))
    for strategy in ("gather", "repartition", "auto"):
        eng, kg, kg_i, ans, secs = session(mesh=mesh, join_exchange=strategy)
        check(np.array_equal(np.asarray(kg.to_codes()),
                             np.asarray(kg1.to_codes())),
              f"{strategy}: create_kg differs from device 0")
        check(np.array_equal(np.asarray(kg_i.to_codes()),
                             np.asarray(kg1_i.to_codes())),
              f"{strategy}: ingest differs from device 0")
        for name in ans1:
            check(np.array_equal(ans[name], ans1[name]),
                  f"{strategy}: query {name!r} differs from device 0")
        log("mesh", join_exchange=strategy, devices=mesh.devices.size,
            kg_triples=int(kg_i.count), seconds=round(secs, 3),
            result="bit-identical to device 0 (create, ingest, 3 queries)")
    # payloads large enough that wire time, not launch noise, sets the fit
    cal = calibrate_mesh(mesh, "data", payload_kib=(256, 2048, 16384),
                         force=True)
    check(cal.source == "measured",
          f"calibration not measured: {cal.source} ({cal.fallback})")
    log("mesh", calibration=cal.source,
        all_gather_bw=f"{cal.all_gather_bw:.4g}",
        all_to_all_bw=f"{cal.all_to_all_bw:.4g}",
        launch_s=f"{cal.launch_s:.4g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the fused mesh phase on four chips")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="multiplier on every data size (1.0 = full)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal: let the run continue on a non-TPU "
                         "backend (relaxes only the device check)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: the repro package is not at {SRC}/repro; run "
              f"this script from a checkout of the repository",
              file=sys.stderr)
        return 2
    # libtpu logs under /tmp unless told otherwise: keep the run's writes
    # inside the checkout (errors still reach stderr)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import configure_compile_cache
    cache = configure_compile_cache()

    t_start = time.perf_counter()
    device = phase_device(args)
    log("device", compile_cache=cache)
    on_tpu = device["platform"] == "tpu"
    if args.chips == 4:
        phase_mesh(args.scale)
    else:
        phase_kernels(args.scale)
        phase_load(args.scale)
        dis_b, eng_b, kg_b = phase_ingest(args.scale)
        phase_query(eng_b, kg_b)
        phase_store(dis_b)
        phase_paths(on_tpu)
    log("done", seconds=round(time.perf_counter() - t_start, 3))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
