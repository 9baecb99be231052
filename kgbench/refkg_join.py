"""The plain reference with join object maps: the knowledge graph of a
mapping whose predicate-object maps may reference a parent triples map,
in NumPy, independent of the system under test.

Maps are read as in ``refkg.py``, whose term conventions this module
keeps. A referencing object map with the join condition ``child = parent``
yields, for each row of the child map's source and every row of the
parent map's source whose ``parent`` value equals the child row's
``child`` value, the triple (child subject, predicate, parent subject).
The knowledge graph is the set of all triples. Selections are outside the
subset, as in ``refkg.py``.

``blocks`` is there for the control alone: with ``blocks = n``, each
source is cut into ``n`` consecutive blocks of ``ceil(rows / n)`` rows,
and a child row is matched only against the parent rows of its own block,
which is what a mesh that joined shard by shard, without exchanging
rows, would give.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np

from kgbench.refkg import (Triples, _key, _term, reference_kg as plain_kg,
                           template_ids)


def _is_join(pom: Mapping) -> bool:
    return "parentTriplesMap" in pom["object"]


def _block_of(n: int, blocks: int) -> np.ndarray:
    per = -(-max(1, n) // blocks)
    return (np.arange(n) // per).astype(np.int64)


def _pairs(value: np.ndarray, key: np.ndarray, block: np.ndarray
           ) -> Tuple[np.ndarray, np.ndarray]:
    """The distinct ``(block, join key, value)`` rows, as the join key
    widened by the block and the value, sorted by the widened key."""
    wide = (block << 32) | key.astype(np.int64)
    value = value.astype(np.int64)
    order = np.lexsort((value, wide))
    wide, value = wide[order], value[order]
    first = np.ones(len(wide), bool)
    first[1:] = (wide[1:] != wide[:-1]) | (value[1:] != value[:-1])
    return wide[first], value[first]


def join_keys(child_s: np.ndarray, child_key: np.ndarray,
              parent_s: np.ndarray, parent_key: np.ndarray,
              blocks: int = 1) -> np.ndarray:
    """Sorted unique ``s_v << 32 | o_v`` keys of (child subject, parent
    subject) over every pair of rows whose join values are equal."""
    ck, cs = _pairs(child_s, child_key, _block_of(len(child_s), blocks))
    pk, ps = _pairs(parent_s, parent_key, _block_of(len(parent_s), blocks))
    lo = np.searchsorted(pk, ck, side="left")
    hi = np.searchsorted(pk, ck, side="right")
    n = hi - lo
    child = np.repeat(cs, n)
    # the parent rows lo[i] .. hi[i] - 1 of every child pair i, in a row
    starts = np.repeat(lo - np.cumsum(n) + n, n)
    parent = ps[starts + np.arange(int(n.sum()))]
    return np.unique(_key(child, parent))


def reference_kg(maps: Sequence[Mapping], sources: Mapping[str, np.ndarray],
                 attrs: Mapping[str, Sequence[str]], code: Mapping[str, int],
                 blocks: int = 1) -> Triples:
    """The knowledge graph of ``maps`` over the code matrices ``sources``;
    the maps' plain terms as ``refkg.reference_kg`` reads them, their
    referencing object maps as this module's docstring says."""
    plain = [dict(m, poms=[p for p in m.get("poms", ()) if not _is_join(p)])
             for m in maps]
    out = plain_kg(plain, sources, attrs, code)
    tids = template_ids(maps)
    by_name = {m["name"]: m for m in maps}
    joined: Dict[Tuple[int, int, int], List[np.ndarray]] = {}
    for m in maps:
        for pom in m.get("poms", ()):
            if not _is_join(pom):
                continue
            parent = by_name[pom["object"]["parentTriplesMap"]]
            cond = pom["object"]["joinCondition"]
            ct, cols = sources[m["source"]], attrs[m["source"]]
            pt, pcols = sources[parent["source"]], attrs[parent["source"]]
            s_t, s_v = _term(m["subject"], ct, cols, tids, code)
            o_t, o_v = _term(parent["subject"], pt, pcols, tids, code)
            keys = join_keys(s_v, ct[:, list(cols).index(cond["child"])],
                             o_v, pt[:, list(pcols).index(cond["parent"])],
                             blocks)
            joined.setdefault((s_t, code[pom["predicate"]], o_t),
                              []).append(keys)
    for k, parts in joined.items():
        out[k] = np.unique(np.concatenate(parts + [out.get(
            k, np.zeros(0, np.int64))]))
    return out
