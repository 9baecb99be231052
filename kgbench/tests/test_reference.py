"""The plain reference against a second witness, the system's own eager
MapSDI fixpoint (``apply_mapsdi_eager`` + the RDFizer, lex δ), on small
deployments of each cell; the reference's encoding of a stream against the
system's; and the controls it is set against."""
import numpy as np
import pytest

from kgbench import refkg
from kgbench.tests.helpers import tiny_cell


def _eager_kg(dep, d=0):
    from repro.core.rdfizer import RDFizer
    from repro.core.rml import parse_dis
    from repro.core.transform import apply_mapsdi_eager
    from repro.relalg import Table, Vocab
    vocab = Vocab()
    vocab.intern_many(dep.values)
    dis = parse_dis({"sources": {n: {"attrs": a, "records": []}
                                 for n, a in dep.attrs.items()},
                     "maps": dep.maps}, vocab=vocab)
    dis.sources = {n: Table.from_codes(c, dep.attrs[n])
                   for n, c in dep.datasets[d].items()}
    pre, _ = apply_mapsdi_eager(dis, dedup="lex")
    kg, _ = RDFizer(pre, "sdm", dedup="lex")()
    return kg.to_codes()


@pytest.mark.parametrize("workload", ["groupA-create", "groupA-ingest"])
def test_reference_equals_the_eager_fixpoint(workload):
    cell = tiny_cell(workload)
    dep = cell.shape().deployment(cell.config, [5, 6])
    for d in (0, 1):
        want = refkg.reference_kg(dep.maps, dep.datasets[d], dep.attrs,
                                  dep.constant_codes)
        got, repeats = refkg.triples_of_rows(_eager_kg(dep, d))
        assert repeats == 0
        assert refkg.triples_diff(got, want) == 0
        assert refkg.count_triples(want) > 0


def test_reference_encodes_a_stream_as_the_system_does():
    """Base codes plus batches encoded in arrival order, as the reference
    writes them, equal what the system's vocabulary makes of the same
    records; and they reject a reordered stream."""
    import types

    from kgbench import session
    from kgbench.loops import ingest
    from repro.relalg import Table
    cell = tiny_cell("groupA-ingest")
    shape = cell.shape()
    dep = shape.deployment(cell.config, [2**35 + 1])
    batches = shape.stream(cell.config, 2**35 + 1, 0, 3, 40)
    vocab = session.vocabulary(dep)
    got = {n: [c] for n, c in dep.datasets[0].items()}
    for batch in batches:
        for n, recs in batch.items():
            got[n].append(Table.from_records(recs, dep.attrs[n],
                                             vocab).to_codes())
    state = types.SimpleNamespace(dep=dep, batches=batches)
    want = ingest.reference_sources(state, 3)
    for n in want:
        assert np.array_equal(np.concatenate(got[n]), want[n])
    state.batches = batches[1:2] + batches[:1] + batches[2:]
    swapped = ingest.reference_sources(state, 3)
    assert any(not np.array_equal(np.concatenate(got[n]), swapped[n])
               for n in want)
    kg = refkg.reference_kg(dep.maps, want, dep.attrs, dep.constant_codes)
    base = refkg.reference_kg(dep.maps, dep.datasets[0], dep.attrs,
                              dep.constant_codes)
    assert refkg.count_triples(kg) > refkg.count_triples(base)


def test_seed_decides_the_data():
    cell = tiny_cell("groupA-create")
    shape = cell.shape()
    a = shape.deployment(cell.config, [2**40 + 3])
    b = shape.deployment(cell.config, [2**40 + 3])
    c = shape.deployment(cell.config, [2**40 + 4])
    assert all(np.array_equal(a.datasets[0][k], b.datasets[0][k])
               for k in a.datasets[0])
    assert any(not np.array_equal(a.datasets[0][k], c.datasets[0][k])
               for k in a.datasets[0])


def test_rows_of_triples_round_trips():
    rows = np.array([[2, 5, 0, 1, 1], [2, 6, 0, 1, 1], [3, 5, 7, 0, 9]])
    triples, _ = refkg.triples_of_rows(rows)
    back = refkg.rows_of_triples(triples)
    assert back.dtype == np.int32
    assert sorted(map(tuple, back)) == sorted(map(tuple, rows))


def test_triples_diff_counts_each_difference():
    rows = np.array([[2, 5, 0, 1, 1], [2, 6, 0, 1, 1], [3, 5, 7, 0, 9]])
    want, _ = refkg.triples_of_rows(rows)
    got, rep = refkg.triples_of_rows(np.concatenate([rows[:2], rows[:1]]))
    assert rep == 1
    assert refkg.triples_diff(got, want) == 1
    assert refkg.rows_diff(rows, rows[:2]) == 1
    assert refkg.rows_diff(rows, rows[[0, 2, 1]]) == 2


def test_hash_only_control_loses_the_colliding_triples():
    """At 300k distinct triples a 32-bit hash collides ~10 times: the
    control loses exactly one triple per extra row sharing a hash."""
    keys = np.unique(np.random.default_rng(0).integers(
        0, 2**62, 300_000, dtype=np.int64))
    want = {(2, 0, 1): keys}
    h = refkg.hash32_rows(2, 0, 1, keys)
    expect = len(keys) - len(np.unique(h))
    assert expect > 0
    got = refkg.hash_only_distinct(want)
    assert refkg.triples_diff(got, want) == expect
