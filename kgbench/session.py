"""Opening a session of the system under test the way a configuration
states it: its vocabulary, its mapping and its engine options."""
from __future__ import annotations

from typing import Mapping

from kgbench.data import Deployment


def vocabulary(dep: Deployment):
    """The system's vocabulary holding ``dep.values``, each at its index."""
    from repro.relalg import Vocab
    vocab = Vocab()
    vocab.intern_many(dep.values)
    if len(vocab) != len(dep.values):
        raise ValueError("the generated values repeat: codes would shift")
    return vocab


def mapping(dep: Deployment, vocab):
    """The deployment's mapping as a DIS with empty sources."""
    from repro.core.rml import parse_dis
    return parse_dis({"sources": {name: {"attrs": attrs, "records": []}
                                  for name, attrs in dep.attrs.items()},
                      "maps": dep.maps}, vocab=vocab)


def engine_config(cfg: Mapping):
    """``EngineConfig`` of ``cfg["engine"]``."""
    from repro.api import EngineConfig
    return EngineConfig(**cfg["engine"])
