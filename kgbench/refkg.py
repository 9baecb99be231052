"""The plain reference: the knowledge graph a mapping defines over coded
sources, in NumPy, and the comparison of two triple tables.

It implements the RML subset the configurations use, straight from its
meaning and independent of the system under test: every row of a triples
map's source yields its subject term, a type triple when the subject map
names a class, and one triple per predicate-object map. The knowledge
graph is the set of all those triples. (Join object maps and selections
are outside the subset: no configuration uses them yet.)

Terms are written as the system's output states them (a triple is five
int32 codes ``s_t, s_v, p, o_t, o_v``): a literal is ``(0, value)``, a
constant IRI ``(1, code)``, and a template IRI ``(2 + i, value)``, where
``i`` numbers the distinct templates (placeholder removed) in the order
the maps name them, subject before objects. Predicates and classes are the
codes of their strings in the vocabulary that the benchmark built.

Sets of triples are handled as groups that share ``(s_t, p, o_t)``, each a
sorted array of ``s_v << 32 | o_v`` keys, so no step sorts five-wide rows.
"""
from __future__ import annotations

import re
from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np

LITERAL, CONSTANT, TEMPLATE_BASE = 0, 1, 2
RDF_TYPE = "rdf:type"
_PLACEHOLDER = re.compile(r"\{([^{}]+)\}")

#: a triple set: ``{(s_t, p, o_t): sorted unique int64 keys}``
Triples = Dict[Tuple[int, int, int], np.ndarray]


def template_ids(maps: Sequence[Mapping]) -> Dict[str, int]:
    """Template string (placeholder removed) -> term type code."""
    ids: Dict[str, int] = {}

    def register(term: Mapping) -> None:
        if "template" in term:
            ids.setdefault(_PLACEHOLDER.sub("{}", term["template"]),
                           TEMPLATE_BASE + len(ids))

    for m in maps:
        register(m["subject"])
        for pom in m.get("poms", ()):
            register(pom["object"])
    return ids


def constants_of(maps: Sequence[Mapping]) -> List[str]:
    """Every constant string a mapping emits, in a fixed order: the codes
    the benchmark interns first, before any data value."""
    out = [RDF_TYPE]
    for m in maps:
        for value in (m["subject"].get("class"), m["subject"].get("constant")):
            if value is not None and value not in out:
                out.append(value)
        for pom in m.get("poms", ()):
            for value in (pom["predicate"], pom["object"].get("constant")):
                if value is not None and value not in out:
                    out.append(value)
    return out


def _key(s_v: np.ndarray, o_v: np.ndarray) -> np.ndarray:
    return (s_v.astype(np.int64) << 32) | o_v.astype(np.int64)


def _term(term: Mapping, table: np.ndarray, attrs: Sequence[str],
          tids: Mapping[str, int], code: Mapping[str, int]
          ) -> Tuple[int, np.ndarray]:
    if "constant" in term:
        return CONSTANT, np.full(len(table), code[term["constant"]],
                                 np.int32)
    if "template" in term:
        attr = _PLACEHOLDER.findall(term["template"])[0]
        tid = tids[_PLACEHOLDER.sub("{}", term["template"])]
        return tid, table[:, list(attrs).index(attr)]
    return LITERAL, table[:, list(attrs).index(term["reference"])]


def reference_kg(maps: Sequence[Mapping], sources: Mapping[str, np.ndarray],
                 attrs: Mapping[str, Sequence[str]],
                 code: Mapping[str, int]) -> Triples:
    """The knowledge graph of ``maps`` over the code matrices ``sources``
    (``code`` maps each constant string to its vocabulary code)."""
    tids = template_ids(maps)
    parts: Dict[Tuple[int, int, int], List[np.ndarray]] = {}

    def add(s_t: int, p: int, o_t: int, keys: np.ndarray) -> None:
        parts.setdefault((int(s_t), int(p), int(o_t)), []).append(keys)

    for m in maps:
        if m.get("selections") or any("parentTriplesMap" in pom["object"]
                                       for pom in m.get("poms", ())):
            raise NotImplementedError("the reference has no joins or "
                                      "selections")
        table = sources[m["source"]]
        cols = attrs[m["source"]]
        s_t, s_v = _term(m["subject"], table, cols, tids, code)
        if m["subject"].get("class"):
            cls = np.full(len(s_v), code[m["subject"]["class"]], np.int32)
            add(s_t, code[RDF_TYPE], CONSTANT, _key(s_v, cls))
        for pom in m.get("poms", ()):
            obj = pom["object"]
            p = code[pom["predicate"]]
            o_t, o_v = _term(obj, table, cols, tids, code)
            add(s_t, p, o_t, _key(s_v, o_v))
    return {k: np.unique(np.concatenate(v)) for k, v in parts.items()}


def triples_of_rows(rows: np.ndarray) -> Tuple[Triples, int]:
    """A ``[n, 5]`` triple table as a triple set, and how many of its rows
    repeat another (a knowledge graph is a set: each repeat is an error)."""
    rows = np.asarray(rows, np.int32).reshape(-1, 5)
    out: Triples = {}
    repeats = 0
    if not len(rows):
        return out, 0
    order = np.lexsort((rows[:, 3], rows[:, 2], rows[:, 0]))
    group = rows[order][:, [0, 2, 3]]
    bounds = np.flatnonzero(np.any(group[1:] != group[:-1], axis=1)) + 1
    for idx in np.split(order, bounds):
        r = rows[idx]
        keys = np.sort(_key(r[:, 1], r[:, 4]))
        uniq = np.unique(keys)
        repeats += len(keys) - len(uniq)
        out[(int(r[0, 0]), int(r[0, 2]), int(r[0, 3]))] = uniq
    return out, repeats


def rows_of_triples(triples: Triples) -> np.ndarray:
    """A triple set as a ``[n, 5]`` triple table."""
    parts = [np.stack([np.full(len(v), k[0]), v >> 32, np.full(len(v), k[1]),
                       np.full(len(v), k[2]), v & 0xFFFFFFFF], axis=1)
             for k, v in sorted(triples.items())]
    return (np.concatenate(parts) if parts
            else np.zeros((0, 5), np.int64)).astype(np.int32)


def count_triples(triples: Triples) -> int:
    return int(sum(len(v) for v in triples.values()))


def triples_diff(got: Triples, want: Triples) -> int:
    """Size of the symmetric difference of two triple sets."""
    diff = 0
    for k in set(got) | set(want):
        a = got.get(k, np.zeros(0, np.int64))
        b = want.get(k, np.zeros(0, np.int64))
        common = len(np.intersect1d(a, b, assume_unique=True))
        diff += len(a) + len(b) - 2 * common
    return diff


def rows_diff(got: np.ndarray, want: np.ndarray) -> int:
    """Rows that differ position by position, plus the rows one table has
    beyond the other: how far an ordered table is from the one expected."""
    got = np.asarray(got).reshape(len(got), -1)
    want = np.asarray(want).reshape(len(want), -1)
    if got.shape[1:] != want.shape[1:]:
        return max(len(got), len(want))
    n = min(len(got), len(want))
    return int(np.any(got[:n] != want[:n], axis=1).sum()) + abs(
        len(got) - len(want))


def hash32_rows(s_t: int, p: int, o_t: int, keys: np.ndarray) -> np.ndarray:
    """A 32-bit hash of whole triples (the splitmix64 finalizer over the
    five codes, folded to 32 bits), for the hash-only control."""
    def mix(z: np.ndarray) -> np.ndarray:
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))

    with np.errstate(over="ignore"):
        head = np.uint64((s_t * 0x9E3779B97F4A7C15 + p * 0xC2B2AE3D27D4EB4F
                          + o_t * 0x165667B19E3779F9) & 0xFFFFFFFFFFFFFFFF)
        h = mix(mix(keys.astype(np.uint64)) ^ head)
    return ((h >> np.uint64(32)) ^ (h & np.uint64(0xFFFFFFFF))).astype(
        np.uint32)


def hash_only_distinct(triples: Triples) -> Triples:
    """The control of the exact δ: a set that keeps one triple per 32-bit
    hash value, as a δ without the full-row check would. Two distinct
    triples whose hashes agree lose one of them."""
    groups = list(triples.items())
    hashes = [hash32_rows(*k, v) for k, v in groups]
    allh = np.concatenate(hashes) if hashes else np.zeros(0, np.uint32)
    _, first = np.unique(allh, return_index=True)
    keep = np.zeros(len(allh), bool)
    keep[first] = True
    out: Triples = {}
    at = 0
    for (k, v), h in zip(groups, hashes):
        out[k] = v[keep[at:at + len(v)]]
        at += len(v)
    return out
