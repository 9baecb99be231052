"""The readers of the system's own spans and scopes (``progtrace``): on a
made-up extract whose numbers can be worked out by hand, on the HLO of a
trace recorded here, and on traced windows recorded on a v5e chip."""
import glob
import gzip
import json
import os
import types

import pytest

from kgbench import devtrace, progtrace, registry

MS = 1_000_000  # ns
FUSION = ("%fusion.16 = s32[16,5]{0,1:T(8,128)} fusion(s32[16,5]{0,1:T(8,"
          "128)} %b), kind=kLoop, calls=%f.16")
SORT = ("%sort.3 = (u32[8,1024]{1,0:T(8,128)}, s32[8,1024]{1,0:T(8,128)}) "
        "sort(u32[8,1024]{1,0:T(8,128)} %a, s32[8,1024]{1,0:T(8,128)} %b), "
        "dimensions={1}, to_apply=%region_8.20")
COPY = "%copy.4 = s32[16,5]{0,1:T(8,128)} copy(s32[16,5]{1,0} %x)"
DATA = os.path.join(os.path.dirname(__file__), "data")


def _extract(program: bool = True):
    """One device; window [0, 100 ms); benchmark span ``ingest`` [40,
    100). Ops: fusion.16 [50, 55) and sort.3 [55, 60) in a run of
    ``jit_fn(1)`` [48, 62), copy.4 [70, 80) in that program's next run
    [70, 80), and fusion.16 [85, 90) in ``jit_other(2)``, whose HLO the
    trace does not hold. Program spans: engine.ingest [42, 98) holding
    append [42, 46), run [46, 95) (execute [46, 48), overflow_check [48,
    82) holding sync [49, 81)) and stats [95, 98)."""
    data = {
        "devices": {"0": [[FUSION, 50 * MS, 5 * MS], [SORT, 55 * MS, 5 * MS],
                          [COPY, 70 * MS, 10 * MS],
                          [FUSION, 85 * MS, 5 * MS]]},
        "spans": [["window", 0, 100 * MS], ["ingest", 40 * MS, 60 * MS]],
    }
    if program:
        data.update(
            program_spans=[["engine.ingest", 42 * MS, 56 * MS],
                           ["engine.append", 42 * MS, 4 * MS],
                           ["engine.run", 46 * MS, 49 * MS],
                           ["engine.execute", 46 * MS, 2 * MS],
                           ["engine.overflow_check", 48 * MS, 34 * MS],
                           ["sync", 49 * MS, 32 * MS],
                           ["engine.stats", 95 * MS, 3 * MS]],
            modules={"0": [["jit_fn(1)", 48 * MS, 14 * MS],
                           ["jit_fn(1)", 70 * MS, 10 * MS],
                           ["jit_other(2)", 84 * MS, 10 * MS]]},
            scopes={"jit_fn(1)": {"fusion.16": "sink.union/compact",
                                  "sort.3": "distinct", "copy.4": None}})
    return data


def _run(data):
    profile = devtrace.Profile.from_extract(data, n_devices=1)
    return types.SimpleNamespace(profile=profile,
                                 program=progtrace.Program(profile, data))


def _read(metric: str, run):
    return registry.metric_reader(metric).read(run)


def test_scope_path_keeps_the_known_scopes():
    assert progtrace.scope_path("jit(fn)/sink.union/compact/scatter") \
        == "sink.union/compact"
    assert progtrace.scope_path(
        "jit(fn)/distinct/cond/branch_1_fun/while/body/closed_call/sort") \
        == "distinct"
    assert progtrace.scope_path("jit(fn)/sort") is None     # no scope
    assert progtrace.scope_path("jit(fn)/distinct/xor;jit(fn)/distinct/"
                                "compact/broadcast_in_dim") == "distinct"
    assert progtrace.scope_path("jit(fn)/emit/add;jit(fn)/union/add") \
        is None
    assert progtrace.scope_path("compact") is None          # an op's name
    assert progtrace.scope_path("") is None


def test_program_spans_and_modules_are_kept_apart():
    run = _run(_extract())
    prog = run.program
    assert prog.count("engine.ingest") == 1 and prog.count("sync") == 1
    assert prog.scoped
    # the ops' programs: two runs of jit_fn(1), one of jit_other(2)
    assert prog.paths["0"] == ["sink.union/compact", "distinct", None, None]


def test_an_unmatched_module_or_instruction_has_no_scope():
    data = _extract()
    del data["scopes"]["jit_fn(1)"]["sort.3"]
    data["modules"]["0"] = data["modules"]["0"][1:]    # first run unknown
    prog = _run(data).program
    assert prog.paths["0"] == [None, None, None, None]


def test_idle_by_innermost_span():
    idle = _run(_extract()).program.idle_by_span()
    want = {"kgbench.window": 40, "kgbench.ingest": 4, "repro.engine.append": 4,
            "repro.engine.execute": 2, "repro.engine.overflow_check": 2,
            "repro.sync": 12, "repro.engine.run": 8, "repro.engine.stats": 3}
    assert idle == {k: pytest.approx(v * 1e-3) for k, v in want.items()}
    assert sum(idle.values()) == pytest.approx(0.075)    # the window's idle
    inside = _run(_extract()).program.idle_by_span(within=[(40 * MS,
                                                             100 * MS)])
    assert sum(inside.values()) == pytest.approx(0.035)
    assert sum(v for k, v in inside.items() if k.startswith("repro.")) \
        == pytest.approx(0.031)


def test_longest_gaps_name_the_spans_around_them():
    gaps = _run(_extract()).program.longest_gaps(top=3)
    assert gaps[0] == (pytest.approx(0.050), "kgbench.window")
    inside = "kgbench.window > kgbench.ingest > repro.engine.ingest > "
    assert sorted(gaps[1:]) == [
        (pytest.approx(0.010), inside + "repro.engine.run > "
         "repro.engine.overflow_check > repro.sync"),
        (pytest.approx(0.010), inside + "repro.engine.stats")]


def test_idle_inside_a_named_span():
    prog = _run(_extract()).program
    assert prog.idle_inside(["engine.ingest"]) == pytest.approx(0.031)
    assert prog.idle_inside(["sync"]) == pytest.approx(0.012)
    assert prog.idle_inside(["engine.append", "engine.stats"]) \
        == pytest.approx(0.007)


def test_device_time_by_scope():
    prog = _run(_extract()).program
    assert prog.device_by_scope() == {
        progtrace.NO_SCOPE: pytest.approx(0.015),
        "sink.union": pytest.approx(0.005), "distinct": pytest.approx(0.005)}
    assert prog.device_by_scope(depth=2)["sink.union/compact"] \
        == pytest.approx(0.005)
    assert prog.scope_seconds(progtrace.has_compact) == pytest.approx(0.005)


def test_metric_readers():
    run = _run(_extract())
    assert _read("compact_share.create", run) == pytest.approx(20.0)
    assert _read("compact_share.ingest", run) == pytest.approx(20.0)
    assert _read("engine_idle_ms.ingest", run) == pytest.approx(31.0)
    # the window holds no engine.create_kg span
    assert _read("engine_idle_ms.create", run) is None


def test_readers_read_nothing_without_the_programs_names():
    run = _run(_extract(program=False))
    assert not run.program.scoped
    for metric in ("compact_share.create", "compact_share.ingest",
                   "engine_idle_ms.create", "engine_idle_ms.ingest"):
        assert _read(metric, run) is None
    untraced = types.SimpleNamespace(profile=None)
    assert progtrace.of(untraced) is None
    assert _read("compact_share.ingest", untraced) is None
    assert _read("engine_idle_ms.ingest", untraced) is None


def test_hlo_scopes_of_a_trace_recorded_here(tmp_path):
    """The protobuf reader on the HLO that a real profile holds: a
    session's closure, traced on this host's CPU backend."""
    import jax
    from repro.api import EngineConfig, KGEngine
    from repro.data.synthetic import make_group_b_dis
    engine = KGEngine(make_group_b_dis(64, 0.5, seed=1),
                      config=EngineConfig(engine="sdm"))
    engine.create_kg()
    with jax.profiler.trace(str(tmp_path)):
        engine.run()[0].data.block_until_ready()
    [file] = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                       recursive=True)
    with open(file, "rb") as f:
        hlos = progtrace.module_hlos(memoryview(f.read()))
    # the profile holds the HLO of every loaded program; the one that ran
    # is named by its CPU op events' program id
    ran = {dict(e.stats).get("program_id")
           for plane in jax.profiler.ProfileData.from_file(file).planes
           for line in plane.lines for e in line.events
           if dict(e.stats).get("hlo_module") == "jit_fn"}
    [pid] = ran
    scopes = progtrace.hlo_scopes(hlos[f"jit_fn({pid})"])
    paths = {p for p in scopes.values() if p}
    assert {"sink.union/compact", "sink.distinct", "distinct", "emit",
            "project", "join"} <= {p if p.endswith("compact") else
                                   p.split("/")[0] for p in paths}
    prog = progtrace.extract(str(tmp_path))
    assert any(n == "engine.run" for n, _, _ in prog["program_spans"])


@pytest.mark.parametrize("cell,compact,idle_ms", [
    ("groupA-create", 59.6115, 24.8571), ("groupA-ingest", 61.4395, 38.7677)])
def test_recorded_chip_window(cell, compact, idle_ms):
    """A short traced window of each cell recorded on one v5e chip (3
    rebuilds; 10 batches): the numbers its run printed, recomputed, and
    the names covering what the window did."""
    with gzip.open(os.path.join(DATA, f"{cell}.program.json.gz"), "rt") as f:
        data = json.load(f)
    run = _run(data)
    kind = cell.split("-")[1]
    assert _read(f"compact_share.{kind}", run) == pytest.approx(compact,
                                                                 abs=1e-3)
    assert _read(f"engine_idle_ms.{kind}", run) == pytest.approx(idle_ms,
                                                                 abs=1e-3)
    prog = run.program
    by_scope = prog.device_by_scope()
    assert set(by_scope) <= set(progtrace.PLAN_SCOPES) | {progtrace.NO_SCOPE}
    assert by_scope[progtrace.NO_SCOPE] < 0.05 * sum(by_scope.values())
    idle = prog.idle_by_span()
    assert sum(idle.values()) == pytest.approx(
        run.profile.window_s - run.profile.busy_s, rel=1e-6)
    calls = prog.idle_by_span(within=[(s, s + d) for n, s, d in
                                      run.profile.spans
                                      if n in ("create_kg", "ingest")])
    named = sum(v for k, v in calls.items() if k.startswith("repro."))
    assert named >= 0.9 * sum(calls.values())
