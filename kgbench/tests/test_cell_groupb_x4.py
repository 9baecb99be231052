"""``groupB-create-x4`` on the CPU at a test size: the reference with join
object maps against the system's eager fixpoint, and the cell driven end
to end through the harness on four virtual devices, past its look for a
chip: a sound run is correct; the control, and the program's KG with one
shard's rows left out, are not."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from kgbench import devtrace, meshscopes, progtrace, refkg, refkg_join, registry
from kgbench.tests.test_reference import _eager_kg

WL = "groupB-create-x4"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the test size: about 2,000 rows per source, a small sample pool
SMALL = {"rows_per_source_at_volume_1": 2000, "volume": 1.0,
         "sample_values": 5000}


def small_cell(**sizes) -> registry.Cell:
    cell = registry.resolve(WL)
    cell.config.update(SMALL, **sizes)
    return cell


@pytest.mark.parametrize("rows,redundancy", [(300, 0.75), (500, 0.3)])
def test_join_reference_equals_the_eager_fixpoint(rows, redundancy):
    cell = small_cell(rows_per_source_at_volume_1=rows,
                      redundancy=redundancy)
    dep = cell.shape().deployment(cell.config, [2**34 + 3, 2**34 + 4])
    for d in (0, 1):
        want = refkg_join.reference_kg(dep.maps, dep.datasets[d], dep.attrs,
                                       dep.constant_codes)
        got, repeats = refkg.triples_of_rows(_eager_kg(dep, d))
        assert repeats == 0
        assert refkg.triples_diff(got, want) == 0
        # the KG grows with the rows: a type and about one ⋈ triple each
        assert refkg.count_triples(want) > 1.5 * rows


def test_join_reference_equals_the_plain_reference_without_joins():
    cell = registry.resolve("groupA-create")
    cell.config.update(rows_per_source_at_volume_1=3000, redundancy=0.5)
    dep = cell.shape().deployment(cell.config, [11])
    args = (dep.maps, dep.datasets[0], dep.attrs, dep.constant_codes)
    want = refkg.reference_kg(*args)
    assert refkg.triples_diff(refkg_join.reference_kg(*args), want) == 0
    assert refkg.count_triples(want) > 0


def test_join_keys_match_every_equal_pair():
    got = refkg_join.join_keys(np.array([1, 2, 3, 3]), np.array([7, 8, 9, 9]),
                               np.array([10, 11, 12, 13]),
                               np.array([7, 7, 9, 5]))
    want = np.array([(1 << 32) | 10, (1 << 32) | 11, (3 << 32) | 12])
    np.testing.assert_array_equal(got, want)
    # in blocks of two rows, a child row meets only the parent rows of its
    # own block: child 0 no longer meets parent 2, nor child 3 parent 0
    child_s, child_k = np.array([1, 2, 3, 4]), np.array([7, 8, 9, 7])
    parent_s, parent_k = np.array([10, 11, 12, 13]), np.array([7, 8, 7, 5])
    whole = refkg_join.join_keys(child_s, child_k, parent_s, parent_k)
    assert len(whole) == 5
    got = refkg_join.join_keys(child_s, child_k, parent_s, parent_k,
                               blocks=2)
    np.testing.assert_array_equal(got, [(1 << 32) | 10, (2 << 32) | 11,
                                        (4 << 32) | 12])


MS = 1_000_000  # ns


def _op(i: int) -> str:
    return (f"%fusion.{i} = s32[16,5]{{0,1:T(8,128)}} fusion(s32[16,5]"
            f"{{0,1:T(8,128)}} %b), kind=kLoop, calls=%f.{i}")


def _mesh_run():
    """Two devices, window [0, 100 ms), one ``engine.create_kg`` [0, 90).
    Device 0: exchange op [10, 20), re-δ op [40, 60); device 1: exchange op
    [10, 20). Spans ``engine.shard`` [0, 10), ``engine.unshard`` [20, 40),
    ``engine.redistinct`` [40, 60)."""
    data = {
        "devices": {"0": [[_op(1), 10 * MS, 10 * MS],
                          [_op(2), 40 * MS, 20 * MS]],
                    "1": [[_op(1), 10 * MS, 10 * MS]]},
        "spans": [["window", 0, 100 * MS]],
        "program_spans": [["engine.create_kg", 0, 90 * MS],
                          ["engine.shard", 0, 10 * MS],
                          ["engine.unshard", 20 * MS, 20 * MS],
                          ["engine.redistinct", 40 * MS, 20 * MS]],
        "modules": {"0": [["jit_plan(1)", 5 * MS, 20 * MS],
                          ["jit_redistinct(2)", 40 * MS, 20 * MS]],
                    "1": [["jit_plan(1)", 5 * MS, 20 * MS]]},
        "scopes": {"jit_plan(1)": {"fusion.1": "exchange/compact"},
                   "jit_redistinct(2)": {"fusion.2": "redistinct/compact"}},
    }
    profile = devtrace.Profile.from_extract(data, n_devices=2)
    prog = progtrace.Program(profile, data)
    return types.SimpleNamespace(profile=profile, program=prog,
                                 mesh_program=prog, counters={})


def test_mesh_readers_on_a_made_up_window():
    """Busy 30 and 10 ms (mean 20); exchange 10 + 10 ms, re-δ 20 + 0 ms
    (means 10 each); idle in the host spans 10 + 20 + (0 + 20) / 2 ms."""
    run = _mesh_run()
    read = lambda m: registry.metric_reader(m).read(run)  # noqa: E731
    assert read("exchange_share.x4") == pytest.approx(50.0)
    assert read("redistinct_share.x4") == pytest.approx(50.0)
    assert read("mesh_host_ms.x4") == pytest.approx(40.0)
    assert read("redistinct_builds.x4") is None
    run.counters["redistinct_builds"] = [0.0]
    assert read("redistinct_builds.x4") == 0.0


def test_mesh_readers_read_nothing_without_the_programs_names():
    run = _mesh_run()
    run.mesh_program.paths = {d: [None] * len(p)
                              for d, p in run.mesh_program.paths.items()}
    assert registry.metric_reader("exchange_share.x4").read(run) is None
    assert registry.metric_reader("redistinct_share.x4").read(run) is None


def test_the_redistinct_scope_is_read_only_where_asked():
    name = "jit(redistinct)/redistinct/compact/sort"
    assert progtrace.scope_path(name) == "compact"
    with meshscopes.scopes_read():
        assert progtrace.scope_path(name) == "redistinct/compact"
    assert progtrace.scope_path(name) == "compact"


_RUNS = """
import json, sys, time
sys.path[0:0] = [{root!r}, {src!r}]
from kgbench import harness
from kgbench.tests.helpers import fake_chips
from kgbench.tests.test_cell_groupb_x4 import small_cell
import repro.core.distributed as dist


def run(cell):
    out, checks = harness.run_cell(cell, 2**33 + 9, 0.5, False, fake_chips(4),
                                   time.perf_counter())
    out["checks"] = {{c.name: c.value for c in checks}}
    return out


results = {{"sound": run(small_cell())}}
cell = small_cell()
loop = cell.loop()
program_output = loop.program_output
loop.program_output = loop.control_output
results["control"] = run(cell)
loop.program_output = program_output
unshard_rows = dist.unshard_rows


def lose_last_shard(data, counts, cap_local):
    n = int(counts[-1])
    rows = unshard_rows(data, counts, cap_local)
    return rows[:len(rows) - n]


dist.unshard_rows = lose_last_shard
results["shard_lost"] = run(small_cell())
print(json.dumps(results))
"""


@pytest.fixture(scope="module")
def runs():
    """The cell's three runs, in one process that sees four devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _RUNS.format(root=ROOT,
                                            src=os.path.join(ROOT, "src"))],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_reports_its_metrics(runs):
    out = runs["sound"]
    assert out["correct"], out
    assert set(out["metrics"]) == {"create_records_per_s", "setup_s"}
    assert out["device"]["count"] == 4
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["window"]["compiles"] == 0
    assert out["window"]["recompiles"] == 0
    assert out["checks"]["kg_triples_diff"] == 0


@pytest.mark.parametrize("fault", ["control", "shard_lost"])
def test_control_and_a_lost_shard_are_not_correct(runs, fault):
    out = runs[fault]
    assert not out["correct"]
    assert out["checks"]["kg_triples_diff"] > 0
