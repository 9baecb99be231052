"""The session plan cache: content-keyed compiled-closure reuse.

MapSDI's amortization story is "extract knowledge from the mapping rules
once, semantify many extensions cheaply". The cache makes *once* literal
across sessions: a compiled plan is keyed by

* the **structural fingerprint** of the optimized IR
  (:func:`repro.plan.ir.fingerprint` — node structure, σ predicate codes,
  π/⋈ wiring, full triple maps),
* the **emitter signature** (every dictionary code the closure embeds:
  predicates, classes, constants, templates, null code — two DISes whose
  codes differ must not share a closure even if their plans look alike),
* engine × dedup × annotate mode/slack, and
* the **capacity-bucket signature** of the source extensions
  (:func:`repro.relalg.bucket_cap` of each source's row count, plus its
  buffer capacity) — the quantization that lets *ranges* of extension
  sizes share one jitted program, and that turns a growing source into
  O(log n) recompiles.

Entries are replaced in place when the engine recompiles on overflow (the
bigger capacities serve every smaller extension of the same bucket), and
evicted LRU beyond ``maxsize``.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, Dict, Optional, Tuple

from repro.plan.ir import Node


@dataclasses.dataclass
class CachedPlan:
    """One compiled execution plan: the jitted closure plus everything the
    session needs to report stats without re-planning.

    Mesh entries (``compile_mesh_plan`` closures) additionally carry the
    shard layout the closure was traced for: ``cap_locals`` (per-source
    per-shard row-block capacity — part of the cache key, so a source
    crossing its shard-local bucket gets a fresh closure), ``out_cap_local``
    (per-shard capacity of the returned KG block, what ``unshard_rows``
    needs), ``sink_slack`` (the fused sink δ's bucket headroom; grown on
    bucket overflow), ``exchanges`` (the resolved per-⋈
    :class:`repro.plan.annotate.JoinExchange` decisions the closure was
    compiled with — what ``explain`` and the bench gates inspect) and
    ``safe_exchange`` (True after an overflow recompile escalated every
    exchange bucket/post-exchange cap to its hard-safe bound).
    ``caps``/``counts`` for mesh entries are the shard-local capacities /
    global counts of ``annotate_local``."""

    key: Tuple
    plan: object                 # repro.plan.lower.LogicalPlan
    emitter: object              # repro.core.rdfizer.RDFizer
    counts: Dict[Node, int]      # plan-time row counts (exact or bound)
    caps: Dict[Node, int]        # plan-time buffer capacities
    fn: Callable                 # sources -> (kg, raw, overflowed)
    engine: str
    dedup: Optional[str]
    mode: str
    build_seconds: float = 0.0
    cap_locals: Optional[Dict[str, int]] = None   # mesh: per-shard source caps
    out_cap_local: Optional[int] = None           # mesh: per-shard KG capacity
    sink_slack: float = 1.0                       # mesh: sink δ bucket slack
    exchanges: Optional[Dict[Node, object]] = None  # mesh: per-⋈ decisions
    safe_exchange: bool = False                   # mesh: hard-safe buckets
    #: where the closure came from: ``"build"`` (compiled in this process)
    #: or ``"store"`` (rehydrated from the persistent plan store — the
    #: engine treats a call-time failure of such a closure as one more
    #: store-reject and rebuilds fresh instead of crashing)
    origin: str = "build"


class PlanCache:
    """Tiny LRU keyed on the tuple above; shared across sessions."""

    def __init__(self, maxsize: int = 128):
        self.maxsize = maxsize
        self._entries: "OrderedDict[Tuple, CachedPlan]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key: Tuple) -> Optional[CachedPlan]:
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return entry

    def put(self, key: Tuple, entry: CachedPlan) -> None:
        self._entries[key] = entry
        self._entries.move_to_end(key)
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        self._entries.clear()
        self.hits = 0
        self.misses = 0

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "size": len(self._entries)}


#: process-wide cache shared by every :class:`~repro.api.KGEngine` session
PLAN_CACHE = PlanCache()

#: the jitted δ that ends every mesh run over the gathered rows
#: (``KGEngine._redistinct``): one program per ``(capacity, width, dedup)``,
#: shared by every session. Never evicted, so its size is the number of
#: programs built since the last :func:`clear_plan_cache`.
REDISTINCT: Dict[Tuple[int, int, Optional[str]], Callable] = {}


def clear_plan_cache() -> None:
    """Drop every cached plan and re-δ program (benchmarks use this to
    measure cold paths)."""
    PLAN_CACHE.clear()
    REDISTINCT.clear()


def plan_cache_stats() -> Dict[str, int]:
    """The plan cache's hits, misses and size, and ``redistinct_builds``:
    the mesh re-δ programs built since the last :func:`clear_plan_cache`."""
    return dict(PLAN_CACHE.stats(), redistinct_builds=len(REDISTINCT))
