"""Group A of the MapSDI paper (Jozashoori & Vidal 2019, §4): several
sources carry one concept under different attribute names, each with an
``ID`` and integer noise attributes, and one triples map per source with
identical heads, so Rule 3 merges them.

The shape follows the repository's own ``make_group_a_dis``, vectorised:
source ``i`` has ``rows`` rows, ``ID`` = the row number, the concept drawn
uniformly from a pool of ``round(rows * (1 - redundancy))`` strings
``<prefix>%08d``, and each noise attribute drawn uniformly from
``0 .. noise_values - 1``.

Codes: the mapping's constants, then the integers ``0 .. max(rows,
noise_values) - 1`` (IDs and noise share them), then the concept pool. The
vocabulary does not depend on the seed, so datasets made from different
seeds are coded against one vocabulary, as two dumps of one database are.

A stream of micro-batches extends the sources: batch ``b`` holds ``rows``
new records per source, IDs continuing the base (the same ID in every
source, as in the base), and concepts drawn uniformly from
``round(rows * (1 - redundancy))`` new transcripts of the batch's own, so
the stream keeps the dump's redundancy and every batch adds to the KG.
Batches are plain records, for the system to encode.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Sequence

import numpy as np

from kgbench.data import Deployment, rng
from kgbench.refkg import constants_of


def rows_per_source(cfg: Mapping) -> int:
    return max(1, int(round(cfg["rows_per_source_at_volume_1"]
                            * cfg["volume"])))


def attrs_of(cfg: Mapping) -> Dict[str, List[str]]:
    noise = [f"noise{k}" for k in range(cfg["noise_attrs"])]
    return {s["name"]: ["ID", s["concept"]] + noise for s in cfg["sources"]}


def _sizes(cfg: Mapping):
    n = rows_per_source(cfg)
    pool = max(1, int(round(n * (1.0 - cfg["redundancy"]))))
    n_int = max(n, int(cfg["noise_values"]))
    return n, pool, n_int


def vocabulary(cfg: Mapping) -> List[object]:
    n, pool, n_int = _sizes(cfg)
    prefix = cfg["concept_prefix"]
    return (constants_of(cfg["maps"]) + list(range(n_int))
            + [f"{prefix}{j:08d}" for j in range(pool)])


def dataset(cfg: Mapping, seed: int, n_constants: int
            ) -> Dict[str, np.ndarray]:
    n, pool, n_int = _sizes(cfg)
    attrs = attrs_of(cfg)
    out = {}
    for si, spec in enumerate(cfg["sources"]):
        r = rng(seed, 0, si)
        k = len(attrs[spec["name"]])
        codes = np.empty((n, k), np.int32)
        codes[:, 0] = np.arange(n_constants, n_constants + n, dtype=np.int32)
        codes[:, 1] = n_constants + n_int + r.integers(0, pool, n,
                                                       dtype=np.int32)
        noise = r.integers(0, cfg["noise_values"], size=(n, k - 2),
                           dtype=np.uint8)
        np.add(noise, np.int32(n_constants), out=codes[:, 2:],
               casting="unsafe")
        out[spec["name"]] = codes
    return out


def deployment(cfg: Mapping, seeds: Sequence[int]) -> Deployment:
    values = vocabulary(cfg)
    c = len(constants_of(cfg["maps"]))
    return Deployment(maps=list(cfg["maps"]), attrs=attrs_of(cfg),
                      values=values,
                      datasets=[dataset(cfg, s, c) for s in seeds],
                      n_constants=c)


def stream(cfg: Mapping, seed: int, start: int, count: int, rows: int
           ) -> List[Dict[str, List[dict]]]:
    """Micro-batches ``start .. start + count - 1`` of the stream."""
    n, pool, _ = _sizes(cfg)
    fresh = max(1, int(round(rows * (1.0 - cfg["redundancy"]))))
    prefix = cfg["concept_prefix"]
    attrs = attrs_of(cfg)
    out = []
    for b in range(start, start + count):
        ids = list(range(n + b * rows, n + (b + 1) * rows))
        first = pool + b * fresh
        batch = {}
        for si, spec in enumerate(cfg["sources"]):
            r = rng(seed, 1, b, si)
            names = [f"{prefix}{first + j:08d}"
                     for j in r.integers(0, fresh, rows).tolist()]
            noise = r.integers(0, cfg["noise_values"],
                               size=(len(attrs[spec["name"]]) - 2, rows))
            batch[spec["name"]] = [
                dict(zip(attrs[spec["name"]], row))
                for row in zip(ids, names, *noise.tolist())]
        out.append(batch)
    return out
