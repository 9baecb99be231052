"""``groupA-create`` driven end to end on the CPU at a test size, past the
harness's look for a chip: a sound run is correct and reports the cell's
metrics; with the timed path broken underneath, ``correct`` is false."""
import dataclasses

import numpy as np
import pytest

from kgbench.tests.helpers import run, tiny_cell

WL = "groupA-create"


def test_sound_run_is_correct_and_reports_its_metrics():
    cell = tiny_cell(WL)
    out, checks = run(cell)
    assert out["correct"], checks
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["window"]["compiles"] == 0
    assert out["window"]["recompiles"] == 0
    assert checks["kg_triples_diff"].value == 0


def _stale(monkeypatch):
    """create_kg returns the first KG it ever made."""
    from repro.api import KGEngine
    real, first = KGEngine.create_kg, []

    def create_kg(self):
        kg, stats = real(self)
        first.append(kg)
        return first[0], stats
    monkeypatch.setattr(KGEngine, "create_kg", create_kg)


def _half(monkeypatch):
    """create_kg over half of every source's rows."""
    from repro.api import KGEngine
    real = KGEngine.create_kg

    def create_kg(self):
        for name, t in list(self.sources.items()):
            self.sources[name] = dataclasses.replace(t, count=t.count // 2)
        return real(self)
    monkeypatch.setattr(KGEngine, "create_kg", create_kg)


def _altered(monkeypatch):
    """one triple of the KG altered where it is produced."""
    from repro.api import KGEngine
    real = KGEngine.create_kg

    def create_kg(self):
        kg, stats = real(self)
        return dataclasses.replace(kg, data=kg.data.at[0, 1].add(1)), stats
    monkeypatch.setattr(KGEngine, "create_kg", create_kg)


@pytest.mark.parametrize("fault", [_stale, _half, _altered],
                         ids=["state-unchanged", "half-left-out",
                              "answer-altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out, checks = run(tiny_cell(WL))
    assert not out["correct"]
    assert checks["kg_triples_diff"].value > checks["kg_triples_diff"].limit


#: two type triples ``(2, a, 0, 1, 1)`` whose 32-bit hashes
#: (``refkg.hash32_rows``) are equal
COLLIDING = (63616, 99373)


def _with_colliding_pair(monkeypatch, cell):
    """Every dataset of ``cell`` holds the colliding pair as the concepts
    of its first source's first two rows."""
    shape = cell.shape()
    real = shape.deployment

    def deployment(cfg, seeds):
        dep = real(cfg, seeds)
        assert len(dep.values) <= min(COLLIDING)
        dep.values += [f"X{i}" for i in range(len(dep.values),
                                              max(COLLIDING) + 1)]
        for codes in dep.datasets:
            codes["src0"][:2, 1] = COLLIDING
        return dep
    monkeypatch.setattr(shape, "deployment", deployment)


def _run_on_colliding_pair(monkeypatch, control: bool):
    from kgbench.refkg import hash32_rows
    keys = (np.asarray(COLLIDING, np.int64) << 32) | 1
    assert len(set(hash32_rows(2, 0, 1, keys).tolist())) == 1
    cell = tiny_cell(WL)
    _with_colliding_pair(monkeypatch, cell)
    loop = cell.loop()
    if control:
        monkeypatch.setattr(loop, "program_output", loop.control_output)
    return run(cell)


def test_control_fails_at_a_test_size(monkeypatch):
    """The control (a δ trusting a 32-bit hash), put where the program's
    output would be, through the harness, on data that holds a colliding
    pair: it loses a triple."""
    out, checks = _run_on_colliding_pair(monkeypatch, control=True)
    assert not out["correct"]
    assert checks["kg_triples_diff"].value > 0


def test_program_is_correct_on_the_colliding_pair(monkeypatch):
    """The program's exact δ keeps both triples of the colliding pair."""
    out, checks = _run_on_colliding_pair(monkeypatch, control=False)
    assert out["correct"]
    assert checks["kg_triples_diff"].value == 0
