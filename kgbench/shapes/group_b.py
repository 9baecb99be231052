"""Group B of the MapSDI paper (Jozashoori & Vidal 2019, §4): two sources
joined by a referencing object map.

The shape follows the repository's own ``make_group_b_dis``, vectorised.
Both sources share a pool of ``round(rows * (1 - redundancy))`` genes
``GENE%08d``; each row draws its gene uniformly from the pool.

* ``gene(ID, Genename, HGNC, enst, Biotype)``: ``ID`` = the row number,
  ``HGNC`` uniform in ``1 .. hgnc_values - 1``, ``enst`` = ``ENST%08d`` of
  a uniform draw below ``10**8``, ``Biotype`` a function of the gene
  (``crc32`` of its name modulo the biotypes).
* ``chrom(ID, Genename, Chromosome, Sample)``: ``ID`` = the row number,
  ``Chromosome`` a function of the gene (``crc32`` modulo the
  chromosomes), ``Sample`` = ``S%06d`` of a uniform draw below
  ``sample_values``.

Codes: the mapping's constants, then the integers ``0 .. max(rows,
hgnc_values) - 1`` (IDs and HGNC share them), the genes, the biotypes, the
chromosomes, the samples, and last the transcripts that the datasets draw,
sorted. The datasets made from every seed are drawn first and coded
against that one vocabulary, as two dumps of one database are.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Mapping, Sequence

import numpy as np

from kgbench.data import Deployment, formatted, rng, sorted_pool
from kgbench.refkg import constants_of

ENST_DRAWS = 10**8


def rows_per_source(cfg: Mapping) -> int:
    return max(1, int(round(cfg["rows_per_source_at_volume_1"]
                            * cfg["volume"])))


def attrs_of(cfg: Mapping) -> Dict[str, List[str]]:
    return {s["name"]: list(s["attrs"]) for s in cfg["sources"]}


def _by_gene(names: Sequence[str], n: int) -> np.ndarray:
    """Index into ``n`` values of each gene, by ``crc32`` of its name."""
    return np.fromiter((zlib.crc32(s.encode()) % n for s in names),
                       np.int32, len(names))


def _draws(cfg: Mapping, seed: int, n: int, n_genes: int) -> Dict:
    gene, chrom = (rng(seed, 0, si) for si in range(2))
    return {"gene": gene.integers(0, n_genes, n, dtype=np.int32),
            "hgnc": gene.integers(1, cfg["hgnc_values"], n, dtype=np.int32),
            "enst": gene.integers(0, ENST_DRAWS, n, dtype=np.int64),
            "chrom_gene": chrom.integers(0, n_genes, n, dtype=np.int32),
            "sample": chrom.integers(0, cfg["sample_values"], n,
                                     dtype=np.int32)}


def deployment(cfg: Mapping, seeds: Sequence[int]) -> Deployment:
    n = rows_per_source(cfg)
    n_genes = max(1, int(round(n * (1.0 - cfg["redundancy"]))))
    n_int = max(n, int(cfg["hgnc_values"]))
    draws = [_draws(cfg, s, n, n_genes) for s in seeds]
    ensts = sorted_pool([d["enst"] for d in draws])
    genes = formatted("GENE%08d", np.arange(n_genes))
    biotypes, chromosomes = list(cfg["biotypes"]), list(cfg["chromosomes"])

    constants = constants_of(cfg["maps"])
    base_int = len(constants)
    base_gene = base_int + n_int
    base_bio = base_gene + n_genes
    base_chrom = base_bio + len(biotypes)
    base_sample = base_chrom + len(chromosomes)
    base_enst = base_sample + cfg["sample_values"]
    values = (constants + list(range(n_int)) + genes + biotypes
              + chromosomes
              + formatted("S%06d", np.arange(cfg["sample_values"]))
              + formatted("ENST%08d", ensts))
    bio_of = base_bio + _by_gene(genes, len(biotypes))
    chrom_of = base_chrom + _by_gene(genes, len(chromosomes))

    ids = np.arange(base_int, base_int + n, dtype=np.int32)
    datasets = []
    for d in draws:
        gene_cols = {
            "ID": ids, "Genename": base_gene + d["gene"],
            "HGNC": base_int + d["hgnc"],
            "enst": (base_enst + np.searchsorted(ensts, d["enst"])
                     ).astype(np.int32),
            "Biotype": bio_of[d["gene"]]}
        chrom_cols = {
            "ID": ids, "Genename": base_gene + d["chrom_gene"],
            "Chromosome": chrom_of[d["chrom_gene"]],
            "Sample": base_sample + d["sample"]}
        cols = {"gene": gene_cols, "chrom": chrom_cols}
        datasets.append({
            name: np.stack([cols[name][a] for a in attrs], axis=1
                           ).astype(np.int32)
            for name, attrs in attrs_of(cfg).items()})
    return Deployment(maps=list(cfg["maps"]), attrs=attrs_of(cfg),
                      values=values, datasets=datasets,
                      n_constants=len(constants))
