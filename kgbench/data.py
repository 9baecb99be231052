"""What the shapes generate: a deployment's vocabulary and coded sources.

The benchmark hands the system what a user would: a vocabulary of values
and int32 code matrices (or, for streams, plain records). The code of a
value is its position in ``Deployment.values``: the shapes list the
mapping's constants first, then every data value once, so the codes are
known to the benchmark without asking the system.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Sequence

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one purpose of one run: the same ``seed`` and
    ``stream`` give the same numbers; any whole number is a seed."""
    return np.random.default_rng(
        np.random.SeedSequence(int(seed) % 2**64,
                               spawn_key=tuple(int(s) for s in stream)))


@dataclasses.dataclass
class Deployment:
    """One vocabulary and the datasets coded against it (one per seed)."""

    maps: List[Mapping]
    attrs: Dict[str, List[str]]
    values: List[object]                     # code -> value
    datasets: List[Dict[str, np.ndarray]]    # per seed: source -> codes
    n_constants: int

    @property
    def constant_codes(self) -> Dict[str, int]:
        return {v: i for i, v in enumerate(self.values[:self.n_constants])}

    def records(self, index: int = 0) -> int:
        return int(sum(len(c) for c in self.datasets[index].values()))


def sorted_pool(draws: Sequence[np.ndarray]) -> np.ndarray:
    """The distinct values of several draws, sorted."""
    return np.unique(np.concatenate([np.asarray(d) for d in draws]))


def formatted(fmt: str, ints: np.ndarray) -> List[str]:
    return [fmt % int(i) for i in ints]
