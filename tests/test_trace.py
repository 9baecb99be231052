"""The engine names its own work in a profiler trace: host spans of its
phases (``repro.trace.span``) and device scopes of its plan operators
(``jax.named_scope``), without changing what it computes."""
import glob
import os
import re

import jax
import numpy as np

from repro.api import EngineConfig, KGEngine, clear_plan_cache
from repro.data.synthetic import make_group_b_dis
from repro.plan.compile import abstract_sources
from repro.relalg import Table, count_transfers


def _session():
    return KGEngine(make_group_b_dis(64, 0.5, seed=1),
                    config=EngineConfig(engine="sdm"))


def _delta(engine, seed: int, rows: int = 12):
    """New rows for every source, encoded with the session's vocabulary."""
    other = make_group_b_dis(64, 0.5, seed=seed)
    return {name: Table.from_records(
                other.sources[name].to_records(other.vocab)[:rows],
                list(engine.sources[name].attrs), engine.vocab)
            for name in engine.sources}


def _host_events(path: str):
    """``(name, start_ns, end_ns, stats)`` of every ``repro.*`` host event
    of the one trace under ``path``."""
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    data = jax.profiler.ProfileData.from_file(files[0])
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in data.planes for line in plane.lines
            for e in line.events if e.name.startswith("repro.")]


def test_closure_hlo_names_plan_operators():
    engine = _session()
    engine.create_kg()
    entry = engine._last["entry"]
    text = entry.fn.lower(abstract_sources(engine.sources)).compile() \
        .as_text()
    paths = {"/".join(p for p in name.split("/")[:-1]
                      if not p.startswith("jit("))
             for name in re.findall(r'op_name="([^"]*)"', text)}
    for scope in ("compact", "sink.union", "distinct"):
        assert any(scope in p.split("/") for p in paths), scope
    assert any(p.startswith("sink.union/compact") for p in paths)
    assert any(p.startswith("sink.distinct") for p in paths)


def test_ingest_trace_names_its_phases_and_every_sync(tmp_path):
    engine = _session()
    engine.create_kg()
    engine.ingest(_delta(engine, seed=2))        # compiles outside the trace
    delta = _delta(engine, seed=3)
    with count_transfers() as ledger, jax.profiler.trace(str(tmp_path)):
        engine.ingest(delta)
    events = _host_events(str(tmp_path))
    ingest = [e for e in events if e[0] == "repro.engine.ingest"]
    assert len(ingest) == 1
    _, lo, hi, stats = ingest[0]
    assert int(stats["step"]) == engine.stats()["executions"]
    inside = [e for e in events if lo <= e[1] and e[2] <= hi and e != ingest[0]]
    names = {e[0] for e in inside}
    for child in ("engine.append", "engine.run", "engine.key",
                  "engine.lookup", "engine.execute", "engine.overflow_check",
                  "engine.stats", "sync"):
        assert "repro." + child in names, child
    syncs = [e for e in events if e[0] == "repro.sync"]
    assert ledger.device_to_host > 0
    assert len(syncs) == ledger.device_to_host
    assert all(lo <= e[1] and e[2] <= hi for e in syncs)


def test_session_spans_and_table_spans(tmp_path):
    dis = make_group_b_dis(64, 0.5, seed=1)
    codes = {name: t.to_codes() for name, t in dis.sources.items()}
    clear_plan_cache()                  # so that the session builds its plan
    with jax.profiler.trace(str(tmp_path)):
        dis.sources = {name: Table.from_codes(c, dis.sources[name].attrs)
                       for name, c in codes.items()}
        engine = KGEngine(dis, config=EngineConfig(engine="sdm"))
        engine.create_kg()
    names = [e[0] for e in _host_events(str(tmp_path))]
    for name in ("table.from_codes", "table.pad", "table.put", "engine.open",
                 "engine.create_kg", "engine.run", "engine.build"):
        assert "repro." + name in names, name
    assert names.count("repro.table.pad") == len(codes)


def test_results_do_not_depend_on_the_profiler(tmp_path):
    def run():
        engine = _session()
        kg0, _ = engine.create_kg()
        kg1, stats = engine.ingest(_delta(engine, seed=4))
        return kg0.to_codes(), kg1.to_codes(), stats["kg_triples"]

    plain = run()
    with jax.profiler.trace(str(tmp_path)):
        traced = run()
    assert all(np.array_equal(a, b) for a, b in zip(plain[:2], traced[:2]))
    assert plain[2] == traced[2]
