"""``groupA-ingest`` driven end to end on the CPU at a test size, past the
harness's look for a chip: a sound run is correct and reports the cell's
metrics; with the timed path broken underneath, ``correct`` is false."""
import dataclasses

import pytest

from kgbench.tests.helpers import run, tiny_cell

WL = "groupA-ingest"


def test_sound_run_is_correct_and_reports_its_metrics():
    cell = tiny_cell(WL)
    out, checks = run(cell)
    assert out["correct"], checks
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["window"]["compiles"] == 0
    assert out["window"]["recompiles"] == 0
    assert {c.value for c in checks.values()} == {0}


def test_window_stops_at_the_capacity_bucket():
    out, checks = run(tiny_cell(WL, batch_rows=30), seconds=60.0)
    assert out["window"]["stopped"]
    assert out["window"]["applied"] == out["window"]["room"]
    assert out["correct"] and out["window"]["recompiles"] == 0


def _unchanged(monkeypatch):
    """ingest acknowledges the batch and returns the session unchanged."""
    from repro.api import KGEngine

    def ingest(self, deltas):
        return self._kg, {}
    monkeypatch.setattr(KGEngine, "ingest", ingest)


def _not_rerun(monkeypatch):
    """ingest appends the batch and returns the previous KG without
    re-running the plan."""
    from repro.api import KGEngine
    from repro.relalg.ops import append_rows

    def ingest(self, deltas):
        for name, delta in deltas.items():
            self.sources[name] = append_rows(self.sources[name], delta)
        return self._kg, {}
    monkeypatch.setattr(KGEngine, "ingest", ingest)


def _half(monkeypatch):
    """ingest takes the first half of each batch's rows."""
    from repro.api import KGEngine
    real = KGEngine.ingest

    def ingest(self, deltas):
        return real(self, {n: dataclasses.replace(t, count=t.count // 2)
                           for n, t in deltas.items()})
    monkeypatch.setattr(KGEngine, "ingest", ingest)


def _token(monkeypatch):
    """encode writes one wrong code in each batch."""
    from repro.relalg import Table
    real = Table.from_records.__func__

    def from_records(cls, records, attrs, vocab, capacity=None):
        t = real(cls, records, attrs, vocab, capacity)
        return dataclasses.replace(t, data=t.data.at[0, 1].add(1))
    monkeypatch.setattr(Table, "from_records", classmethod(from_records))


@pytest.mark.parametrize("fault, caught_by", [
    (_unchanged, "source_rows_diff"), (_not_rerun, "kg_triples_diff"),
    (_half, "source_rows_diff"), (_token, "source_rows_diff")],
    ids=["state-unchanged", "plan-not-rerun", "half-left-out",
         "token-altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, caught_by):
    fault(monkeypatch)
    out, checks = run(tiny_cell(WL))
    assert not out["correct"]
    assert checks[caught_by].value > 0


def test_control_fails_at_a_test_size(monkeypatch):
    """The control (the state one acknowledged batch behind), put where
    the program's output would be, through the harness: every row of that
    batch is missing, and so are the batch's new triples."""
    cell = tiny_cell(WL)
    loop = cell.loop()
    monkeypatch.setattr(loop, "program_output", loop.control_output)
    out, checks = run(cell)
    assert not out["correct"]
    assert checks["source_rows_diff"].value == 3 * cell.traffic["batch_rows"]
    assert checks["kg_triples_diff"].value > 0
