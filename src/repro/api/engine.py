"""``KGEngine`` — the stateful session front door to the MapSDI pipeline.

The paper's framework amortizes: extract knowledge from the mapping rules
once, then semantify large and *growing* sources cheaply. The repo's
historical entry points (``mapsdi_create_kg``, ``make_planned_fn``,
``make_mapsdi_fn``, ``rdfize``) each re-planned, re-annotated and re-jitted
from scratch, and silently truncated when an extension outgrew its
plan-time capacities. ``KGEngine`` replaces them with one session object::

    engine = KGEngine(dis, config=EngineConfig(engine="sdm", dedup="hash"))
    kg, stats = engine.create_kg()           # plan + compile (or cache hit)
    kg, stats = engine.ingest(delta_sources) # micro-batch extension
    ans = engine.query(q)                    # jitted BGP over the KG
    engine.stats()                           # session counters

Three mechanisms (see ``docs/engine.md``):

* **Plan cache** — compiled closures are keyed by the structural
  fingerprint of the optimized IR × the emitter's dictionary codes ×
  engine × dedup × the capacity *bucket* of every source extension
  (:data:`repro.api.cache.PLAN_CACHE`). A structurally-identical DIS — or
  the same session re-executing after a within-bucket ingest — reuses one
  jitted closure with zero re-trace.
* **Overflow-safe re-execution** — capacities are sized per bucket
  (``annotate`` in ``"exact"`` or ``"bound"`` mode ×
  :func:`repro.relalg.bucket_cap`); the closure reports a truncation flag,
  and the engine transparently recompiles into the next capacity bucket
  and re-runs, counting ``recompiles``. The KG is never silently wrong.
* **Fully device-resident distributed plans** — with a ``mesh``, the
  WHOLE pipeline (Scan over shard-local row blocks, π/σ/δ, ⋈ with
  gathered parents, semantification, and the global sink δ as a fused
  hash-repartition collective) runs inside one ``shard_map`` closure
  (:func:`repro.plan.mesh.compile_mesh_plan`). Intermediate triples never
  touch the host: the engine shards the session sources once per ingest,
  re-executes the cached mesh closure, and only reads back the final
  deduplicated KG. Capacities are annotated *per shard*
  (:func:`repro.plan.annotate.annotate_local`) and the cache key extends
  to (mesh shape, axis, device ids, per-source shard-local capacity
  bucket), so recompile-on-overflow and bucket-crossing ingests work
  exactly as on one device.
"""
from __future__ import annotations

import time
import warnings
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rdfizer import RDFizer
from repro.core.schema import DIS, TRIPLE_ATTRS
from repro.core.transform import TransformStats, plan_mapsdi
from repro.plan.annotate import annotate, annotate_local
from repro.plan.compile import abstract_sources, compile_plan, input_names
from repro.plan.ir import fingerprint
from repro.plan.lower import LogicalPlan, lower
from repro.query import (KG_SOURCE, Query, annotate_query,
                         annotate_query_local, compile_query, lower_query,
                         query_session_key)
from repro.relalg import (PAD_ID, Table, append_rows, bucket_cap, dedup_rows,
                          host_int)
from repro.trace import span, traced

from .cache import PLAN_CACHE, REDISTINCT, CachedPlan
from .config import EngineConfig
from .store import (NATIVE, STABLEHLO, compile_for_store, deserialize_native,
                    deserialize_stablehlo, pack_entry_meta, resolve_store,
                    serialize_native, serialize_stablehlo, store_envelope,
                    store_key, unpack_entry_meta)

#: sentinel distinguishing "kwarg not passed" from every real value — a
#: bare ``KGEngine(dis)`` must not warn; an explicit legacy kwarg must
_UNSET = object()
_WARNED_LEGACY: set = set()


def _warn_legacy_kwargs(names: Tuple[str, ...]) -> None:
    """One ``DeprecationWarning`` per distinct legacy-kwarg combination
    per process — enough to steer migrations without drowning loops."""
    if names in _WARNED_LEGACY:
        return
    _WARNED_LEGACY.add(names)
    warnings.warn(
        "KGEngine keyword configuration (" + ", ".join(names) + ") is "
        "deprecated; pass config=EngineConfig(...) instead — the legacy "
        "kwargs will be removed once out-of-tree callers have migrated",
        DeprecationWarning, stacklevel=3)


def _to_bucket(table: Table) -> Table:
    """Pad a table's buffer up to its geometric capacity bucket (device
    concat, no host read) — the headroom that keeps small ingests
    shape-stable."""
    cap = bucket_cap(table.capacity)
    if cap == table.capacity:
        return table
    pad = jnp.full((cap - table.capacity, table.n_attrs), jnp.int32(PAD_ID))
    return Table(data=jnp.concatenate([table.data, pad], axis=0),
                 count=table.count, attrs=table.attrs)


def _redistinct_program(dedup: Optional[str]):
    """The jitted δ of a gathered mesh result, under the device scope
    ``redistinct``."""
    def redistinct(data: jax.Array, count: jax.Array):
        with jax.named_scope("redistinct"):
            return dedup_rows(data, count, dedup)
    return jax.jit(redistinct)


def _emitter_signature(emitter: RDFizer) -> Tuple:
    """Every dictionary code the compiled closure embeds, read off the
    emitter's pre-interned tables: two plans may only share a closure if
    these match (same strings under different vocabs get different codes —
    and different programs). Reading the tables — instead of re-interning —
    keeps the engine's vocab-growth order identical to the historical
    RDFizer paths, so old- and new-API outputs stay bit-identical."""
    return (emitter.dis.null_code, emitter.rdf_type_code,
            tuple(sorted(emitter._pred.items())),
            tuple(sorted(emitter._class.items())),
            tuple(sorted((str(k), v) for k, v in emitter._const.items())),
            tuple(sorted((str(k), v)
                         for k, v in emitter._subj_const.items())),
            tuple(sorted((str(k), v) for k, v in emitter._sel.items())),
            tuple(sorted(emitter._subject_tmpl.items())),
            tuple(sorted((repr(k), v)
                         for k, v in emitter._tmpl_ids.items())))


class KGEngine:
    """Stateful MapSDI session: cached plans, incremental ingestion,
    overflow-safe re-execution.

    Parameters
    ----------
    dis
        The data integration system. The engine owns a session *view* of
        its sources (``dis`` itself is never mutated); ``ingest`` appends
        to the view.
    config
        An :class:`~repro.api.EngineConfig` holding every knob below —
        the canonical spelling::

            KGEngine(dis, config=EngineConfig(engine="sdm", dedup="hash"))

        The individual keyword arguments still work but are deprecated
        (one-time ``DeprecationWarning``); passing both raises
        ``ValueError``. All validation lives in ``EngineConfig`` — bad
        values raise named errors at construction, before any planning.
    engine
        ``"sdm"`` (duplicate-aware per-map δ) or ``"rmlmapper"`` (blind
        generation, sink δ only).
    dedup
        δ strategy (``"lex"`` | ``"hash"`` | None = engine default).
    optimize
        Run the Rule 1–3 + σ + CSE fixpoint (default). ``False`` compiles
        the un-rewritten plan — the T-framework/``rdfize`` semantics, where
        ``raw_triples`` counts blind generation.
    mode
        ``annotate`` mode: ``"exact"`` (host pass per bucket change, tight
        buffers) or ``"bound"`` (structural upper bounds, zero host reads —
        for huge sources where exact counting doubles host work).
    slack
        Multiplier on annotated counts before bucketing — headroom that
        absorbs extension growth without recompiling.
    mesh / mesh_axis
        When given, the whole plan — per-map pipeline AND the global sink
        δ — compiles into one mesh-resident ``shard_map`` closure over
        row-sharded sources (:func:`repro.plan.mesh.compile_mesh_plan`);
        intermediate triples never leave the devices, and only the final
        deduplicated KG is gathered back (then canonically re-ordered so
        the output is bit-identical to the single-device path).
    join_exchange
        ⋈ exchange strategy inside the fused mesh closure (ignored without
        a mesh): ``"gather"`` all_gathers the parent side to every shard,
        ``"repartition"`` hash-partitions both sides by join key with one
        ``all_to_all`` each, ``"auto"`` (default) lets the per-join cost
        model pick whichever moves fewer estimated wire bytes
        (:func:`repro.plan.annotate.join_exchange_cost`). All three
        produce bit-identical KGs; the knob is part of the plan-cache key.
        ``"auto"`` decisions are resolved at compile time from the
        plan-time counts, so they re-resolve on every capacity-bucket
        crossing.
    plan_store
        Persistent second tier behind the in-process LRU
        (``docs/plan_store.md``): ``None`` (default) disables it; ``True``
        or ``"default"`` uses ``$REPRO_PLAN_STORE`` /
        ``~/.cache/repro-plans``; a path or a
        :class:`repro.api.store.PlanStore` uses that store. With a store,
        compiles go through AOT lowering, the executable is serialized to
        disk keyed by the plan-cache key × a runtime compatibility
        envelope, and an LRU-missing session in a *fresh process*
        rehydrates it without re-tracing or re-compiling. Every load
        failure (corruption, envelope mismatch, deserialization error)
        silently degrades to a fresh compile — counted in ``stats()`` as
        ``store_rejects``, never a crash, never a wrong KG. Requires
        ``jit=True`` (eager sessions skip the store).
    calibrate
        Measured-bandwidth cost model (ignored without a mesh). ``True``
        microbenchmarks ``all_gather``/``all_to_all`` over the mesh axis
        once at session start (memoized per process and mesh) and prices
        every ⋈ exchange with the fitted bandwidths and launch constant
        instead of the static v5e datasheet numbers; a
        :class:`repro.launch.mesh.Calibration` instance injects known
        numbers. The calibration signature joins the plan-cache key and
        the persistent-store envelope, so calibrated and static plans
        (or plans measured under different link speeds) never collide.
        ``explain()`` shows the provenance as each ⋈ line's ``cost=`` bit.
    """

    def __init__(self, dis: DIS, engine: str = _UNSET,
                 dedup: Optional[str] = _UNSET, *,
                 config: Optional[EngineConfig] = None,
                 optimize: bool = _UNSET, mode: str = _UNSET,
                 slack: float = _UNSET, mesh=_UNSET, mesh_axis: str = _UNSET,
                 jit: bool = _UNSET, join_exchange: str = _UNSET,
                 plan_store=_UNSET, calibrate=_UNSET, verify: str = _UNSET):
        legacy = {name: value for name, value in (
            ("engine", engine), ("dedup", dedup), ("optimize", optimize),
            ("mode", mode), ("slack", slack), ("mesh", mesh),
            ("mesh_axis", mesh_axis), ("jit", jit),
            ("join_exchange", join_exchange), ("plan_store", plan_store),
            ("calibrate", calibrate), ("verify", verify))
            if value is not _UNSET}
        if config is not None:
            if legacy:
                raise ValueError(
                    "pass either config=EngineConfig(...) or the legacy "
                    "keyword arguments, not both (got config plus "
                    f"{sorted(legacy)})")
            if not isinstance(config, EngineConfig):
                raise TypeError("config must be an EngineConfig, got "
                                f"{type(config).__name__}")
        else:
            if legacy:
                _warn_legacy_kwargs(tuple(sorted(legacy)))
            config = EngineConfig(**legacy)   # validates every field
        self.config = config
        engine, dedup = config.engine, config.dedup
        optimize, mode, slack = config.optimize, config.mode, config.slack
        mesh, mesh_axis, jit = config.mesh, config.mesh_axis, config.jit
        join_exchange = config.join_exchange
        plan_store, calibrate = config.plan_store, config.calibrate
        verify = config.verify
        # static verification level: "plan" (default) gates every rewrite
        # with its soundness contract and verifies each annotated plan
        # before compiling (and every store-rehydrated entry before
        # adoption); "full" additionally audits the lowered closure's
        # jaxpr (collectives vs the exchange plan, zero host
        # callbacks/transfers, dtype stability); "off" disables all of it
        self.verify = verify
        self._verify_plan_checks = 0
        self._verify_audits = 0
        self._verify_store_checks = 0
        self.join_exchange = join_exchange
        # measured-bandwidth cost model: ``True`` runs the session-start
        # collective microbenchmark once per mesh (memoized process-wide);
        # a Calibration instance injects known numbers (tests/replays);
        # False (default) keeps the static datasheet constants. The
        # calibration signature joins the plan-cache key and the store
        # envelope, so plans priced under different link speeds never
        # collide.
        self.calibration = None
        if mesh is not None and calibrate is not False:
            from repro.launch.mesh import Calibration, calibrate_mesh
            self.calibration = (calibrate if isinstance(calibrate,
                                                        Calibration)
                                else calibrate_mesh(mesh, mesh_axis))
        self.engine = engine
        self.dedup = dedup
        self._store = resolve_store(plan_store)
        self._store_hits = 0
        self._store_misses = 0
        self._store_rejects = 0
        self.optimize = optimize
        self.mode = mode
        self.slack = float(slack)
        self.mesh, self.mesh_axis = mesh, mesh_axis
        self.jit = jit
        with span("engine.open", step=0):
            self._dis = dis.copy()
            # session view of the extensions, re-buffered into geometric
            # capacity buckets so within-bucket ingests never change shapes
            self._dis.sources = {name: _to_bucket(t)
                                 for name, t in dis.sources.items()}
            self.sources: Dict[str, Table] = self._dis.sources
            self._tstats = TransformStats()
            t0 = time.perf_counter()
            self._plan = (plan_mapsdi(self._dis, stats=self._tstats,
                                      gate=self._rewrite_gate())
                          if optimize else lower(self._dis))
            # the session emitter is built here, over the rewritten maps, in
            # the same order the historical paths did — vocab growth (and so
            # every embedded code) stays bit-compatible with the old API
            view = self._dis.copy()
            view.maps = list(self._plan.maps)
            self._emitter = RDFizer(view, engine, join_caps={}, dedup=dedup)
            view.sources = {}   # the emitter never reads extensions; dropping
            # them keeps cached closures from pinning device tables for the
            # lifetime of the process-wide plan cache
            self._ir_fp = fingerprint(self._plan.emits())
            self._emit_sig = _emitter_signature(self._emitter)
            self._plan_seconds = time.perf_counter() - t0
        # mesh sessions keep the sharded source blocks device-resident
        # between runs, keyed by the source Table object's identity — any
        # replacement (ingest's append_rows, direct assignment) re-shards
        self._shard_cache: Dict[str, Tuple] = {}
        self._scan_names_cache: Optional[Tuple[str, ...]] = None
        # the mesh's identity is fixed for the session: key prefix once
        self._mesh_static = None if mesh is None else (
            tuple(mesh.shape.items()), mesh_axis,
            tuple(int(d.id) for d in np.asarray(mesh.devices).flat))
        self._have_plan = False     # a closure has been obtained (any way)
        self._builds = 0            # closures actually compiled HERE (not
        # LRU hits, not store rehydrations) — what the serving layer's
        # compile-dedup ratio counts across tenant sessions
        # sticky per-session escalation: once adversarial key/hash skew
        # forced a safe-capacity rebuild, later builds (e.g. after a
        # bucket-crossing ingest of the same skewed stream) start safe
        # instead of re-paying a Poisson-then-safe double compile
        self._safe_exchange = False
        self._recompiles = 0        # compiles beyond the session's first
        self._executions = 0
        self._ingests = 0
        self._ingested_rows = 0
        self._cache_hits = 0
        self._cache_misses = 0
        self._last: Dict[str, object] = {}
        # query tier (KGEngine.query): the session KG the BGP engine reads,
        # its capacity-bucketed view and sharded device blocks (both
        # identity-keyed — a new KG from run()/ingest() re-buckets and
        # re-shards), the query-side sticky safe-exchange escalation, and
        # the per-session query counters surfaced as ``stats()["query"]``
        self._kg: Optional[Table] = None
        self._kg_bucket: Optional[Tuple[Table, Table]] = None
        self._kg_shard: Optional[Tuple] = None
        self._q_safe_exchange = False
        self._q_executions = 0
        self._q_cache_hits = 0
        self._q_cache_misses = 0
        self._q_recompiles = 0
        self._q_store_hits = 0
        self._q_store_misses = 0
        self._q_store_rejects = 0
        self._q_last: Dict[str, object] = {}

    # -- plan cache ----------------------------------------------------------
    @property
    def plan(self):
        """The optimized :class:`~repro.plan.lower.LogicalPlan`."""
        return self._plan

    @property
    def plan_signature(self) -> Tuple:
        """The session's *shape*: structural IR fingerprint × emitter
        dictionary codes × static config signature — every plan-cache key
        component except the (data-dependent) source/mesh capacity
        buckets. Two sessions with equal signatures share compiled
        closures bucket-for-bucket; the serving layer's session registry
        (:mod:`repro.serve`) keys tenants on it to assert the
        K-compiles-for-T-tenants dedup."""
        return (self._ir_fp, self._emit_sig) + self.config.cache_sig()

    @property
    def builds(self) -> int:
        """Closures compiled *by this session* (plan-cache hits and
        plan-store rehydrations excluded) — the denominator of the serve
        layer's compile-dedup ratio."""
        return self._builds

    @property
    def recompiles(self) -> int:
        """Compiles beyond the session's first (capacity-bucket crossings,
        overflow ladders) — the serve layer's admission controller watches
        this to detect recompile storms."""
        return self._recompiles

    def explain(self) -> str:
        """Annotated plan tree over the session's current sources. On a
        mesh session every ⋈ line additionally shows the cost model's
        exchange decision under the session's ``join_exchange`` knob plus
        the estimated per-device wire bytes of both strategies. Once a
        closure has been compiled, the tree renders the *compiled* entry's
        counts/caps/exchanges — exactly what the cached closure was built
        with (an ``"auto"`` decision near the crossover could otherwise
        differ from a fresh estimate); before the first execution it
        predicts with the session's own mode/slack/bucketing and sticky
        safe-exchange state."""
        from repro.plan.explain import dump_plan
        if self.mesh is None:
            counts, caps = annotate(self._plan)
            exchanges = None
        else:
            entry = self._last.get("entry") if self._last else None
            if entry is not None and entry.exchanges is not None:
                counts, caps = entry.counts, entry.caps
                exchanges = entry.exchanges
            else:
                counts, caps, exchanges = annotate_local(
                    self._plan,
                    n_shards=int(self.mesh.shape[self.mesh_axis]),
                    cap_locals=self._cap_locals(self.sources),
                    mode=self.mode, slack=self.slack, cap_fn=bucket_cap,
                    sources=self.sources,
                    join_exchange=self.join_exchange,
                    safe_exchange=self._safe_exchange,
                    calibration=self.calibration)
        schemas = verdict = None
        if self.verify != "off":
            from repro.analysis.verify import verify_plan
            report = verify_plan(
                self._plan, self.engine, counts=counts, caps=caps,
                sources=self.sources, shard_local=self.mesh is not None,
                slack=self.slack, check_canonical=self.optimize,
                check_cse=self.optimize)
            schemas, verdict = report.schemas, report.describe()
        return dump_plan(self._plan, self.engine, counts, caps, exchanges,
                         schemas=schemas, verdict=verdict)

    def _source_sig(self, sources: Mapping[str, Table]) -> Tuple:
        return tuple(sorted(
            (name, t.capacity, tuple(t.attrs), bucket_cap(host_int(t.count)))
            for name, t in sources.items()))

    def _cap_locals(self, sources: Mapping[str, Table]) -> Dict[str, int]:
        """Per-shard row-block capacity bucket per scanned source — the
        shard-local analogue of the source capacity bucket, and part of
        the mesh cache key (a source crossing its shard-local bucket must
        get a freshly-shaped closure)."""
        n = int(self.mesh.shape[self.mesh_axis])
        return {name: bucket_cap(-(-sources[name].capacity // n))
                for name in self._scan_names}

    @property
    def _scan_names(self) -> Tuple[str, ...]:
        """Source names the current plan scans — static per plan, cached
        so the per-run cache-key computation never re-walks the IR DAG."""
        if self._scan_names_cache is None:
            from repro.plan.mesh import plan_scans
            self._scan_names_cache = tuple(sorted(plan_scans(self._plan)))
        return self._scan_names_cache

    def _mesh_sig(self, sources: Mapping[str, Table]) -> Optional[Tuple]:
        """Mesh part of the cache key: shape, axis, device ids (static,
        computed once), per-source shard-local capacity bucket, the
        u16-packability of the vocab (baked into every exchange's
        all_to_all payload), and the ⋈ exchange knob (different strategies
        are different collective programs; ``"auto"``'s per-join
        resolution is a build-time perf decision, so within-bucket count
        drift never invalidates a cached closure)."""
        if self.mesh is None:
            return None
        cal_sig = (None if self.calibration is None
                   else self.calibration.signature())
        return self._mesh_static + (
            tuple(sorted(self._cap_locals(sources).items())),
            len(self._dis.vocab) < (1 << 16), self.join_exchange, cal_sig)

    @traced("engine.key")
    def _key(self, sources: Mapping[str, Table]) -> Tuple:
        # the static configuration component comes off the EngineConfig —
        # the one input to key derivation — never off loose attributes
        return (self._ir_fp, self._emit_sig) + self.config.cache_sig() + (
            self._mesh_sig(sources), self._source_sig(sources))

    def _rewrite_gate(self):
        """The optimizer's per-rewrite soundness hook (``None`` when
        verification is off)."""
        if self.verify == "off":
            return None
        from repro.analysis.soundness import soundness_gate
        return soundness_gate

    def _verify_built(self, counts, caps, sources,
                      shard_local: bool) -> None:
        """Statically verify the annotated plan before it is compiled;
        a failure raises :class:`repro.analysis.PlanVerificationError`
        (a malformed plan must never reach XLA, let alone a KG)."""
        if self.verify == "off":
            return
        from repro.analysis.verify import verify_plan
        verify_plan(self._plan, self.engine, counts=counts, caps=caps,
                    sources=sources, shard_local=shard_local,
                    slack=self.slack, check_canonical=self.optimize,
                    check_cse=self.optimize).raise_for_status()
        self._verify_plan_checks += 1

    @traced("engine.replan")
    def _replan(self) -> None:
        """Re-lower/re-optimize after a provenance change (e.g. σ-baked
        flags dropped by :meth:`ingest`); the cache key follows the new
        plan structure, so the next execution compiles fresh."""
        t0 = time.perf_counter()
        self._plan = (plan_mapsdi(self._dis, gate=self._rewrite_gate())
                      if self.optimize else lower(self._dis))
        self._ir_fp = fingerprint(self._plan.emits())
        self._scan_names_cache = None   # the new plan may scan differently
        self._plan_seconds += time.perf_counter() - t0

    def _slim_plan(self):
        """The plan as stored/captured by cache entries: same nodes and
        maps, but a DIS stub without the source extensions, so entries
        outliving this session never pin its device tables."""
        stub = self._dis.copy()
        stub.sources = {}
        return LogicalPlan(dis=stub, maps=list(self._plan.maps),
                           inputs=dict(self._plan.inputs),
                           names=dict(self._plan.names),
                           preprocessed=self._plan.preprocessed,
                           sigma_baked=self._plan.sigma_baked)

    @traced("engine.build")
    def _build(self, key: Tuple, sources: Mapping[str, Table],
               mode: Optional[str] = None,
               floor_caps: Optional[Mapping] = None,
               sink_slack: float = 1.0,
               safe_exchange: bool = False) -> CachedPlan:
        t0 = time.perf_counter()
        safe_exchange = safe_exchange or self._safe_exchange
        self._safe_exchange = safe_exchange
        plan = self._slim_plan()
        # with a persistent store, compiles go through explicit AOT
        # lowering so the SAME executable both serves this session
        # (entry.fn) and serializes to disk — never a second XLA compile
        # just to write the entry back
        aot = self._store is not None and self.jit
        if self.mesh is None:
            counts, caps = annotate(self._plan, mode=mode or self.mode,
                                    slack=self.slack, cap_fn=bucket_cap,
                                    sources=sources)
            if floor_caps:  # growth must be monotone or overflow ping-pongs
                caps = {n: max(c, floor_caps.get(n, 0))
                        for n, c in caps.items()}
            self._verify_built(counts, caps, sources, shard_local=False)
            fn = compile_plan(plan, self._emitter, engine=self.engine,
                              dedup=self.dedup, caps=caps, jit=self.jit,
                              report_overflow=True)
            abstract = ((abstract_sources(sources),)
                        if aot or self.verify == "full" else None)
            if self.verify == "full":
                from repro.analysis.audit import audit_closure
                audit_closure(fn, abstract, plan=self._plan,
                              engine=self.engine,
                              single_device=True).raise_for_status()
                self._verify_audits += 1
            entry = CachedPlan(key=key, plan=plan, emitter=self._emitter,
                               counts=counts, caps=caps, fn=fn,
                               engine=self.engine, dedup=self.dedup,
                               mode=mode or self.mode,
                               build_seconds=time.perf_counter() - t0)
        else:
            from repro.plan.mesh import compile_mesh_plan
            n = int(self.mesh.shape[self.mesh_axis])
            cap_locals = self._cap_locals(sources)
            counts, caps, exchanges = annotate_local(
                self._plan, n_shards=n, cap_locals=cap_locals,
                mode=mode or self.mode, slack=self.slack,
                cap_fn=bucket_cap, sources=sources,
                join_exchange=self.join_exchange,
                safe_exchange=safe_exchange,
                calibration=self.calibration)
            if floor_caps:
                caps = {n_: max(c, floor_caps.get(n_, 0))
                        for n_, c in caps.items()}
            self._verify_built(counts, caps, sources, shard_local=True)
            fn, out_cap_local = compile_mesh_plan(
                plan, self._emitter, self.mesh, self.mesh_axis,
                engine=self.engine, dedup=self.dedup, caps=caps,
                cap_locals=cap_locals, sink_slack=sink_slack,
                pack_u16=len(self._dis.vocab) < (1 << 16), jit=self.jit,
                exchanges=exchanges, safe_exchange=safe_exchange)
            if aot or self.verify == "full":
                from repro.plan.mesh import mesh_abstract_inputs
                abstract = mesh_abstract_inputs(self._plan, cap_locals, n,
                                                self.mesh, self.mesh_axis)
            if self.verify == "full":
                from repro.analysis.audit import audit_closure
                audit_closure(fn, abstract, plan=self._plan,
                              engine=self.engine, n_shards=n,
                              exchanges=exchanges).raise_for_status()
                self._verify_audits += 1
            entry = CachedPlan(key=key, plan=plan, emitter=self._emitter,
                               counts=counts, caps=caps, fn=fn,
                               engine=self.engine, dedup=self.dedup,
                               mode=mode or self.mode,
                               build_seconds=time.perf_counter() - t0,
                               cap_locals=cap_locals,
                               out_cap_local=out_cap_local,
                               sink_slack=sink_slack,
                               exchanges=exchanges,
                               safe_exchange=safe_exchange)
        if aot:
            try:
                entry.fn = compile_for_store(fn, abstract)
            except Exception:   # AOT unavailable: keep the jitted closure
                self._store.write_errors += 1
                aot = False
            entry.build_seconds = time.perf_counter() - t0
        PLAN_CACHE.put(key, entry)
        self._builds += 1
        if aot:
            self._store_save(entry, fn, abstract)
        if self._have_plan:
            self._recompiles += 1
        return entry

    def _store_save(self, entry: CachedPlan, fn_jit, abstract) -> None:
        """Write the AOT-compiled entry back to the persistent store —
        best-effort: any serialization/IO failure is counted, never
        raised (a full disk must not take the session down)."""
        store = self._store
        try:
            env = store_envelope(self.calibration)
            skey = store_key(entry.key, env)
            payloads = {NATIVE: serialize_native(entry.fn)}
            if store.portable:
                payloads[STABLEHLO] = serialize_stablehlo(fn_jit, abstract)
            store.save(skey, env, pack_entry_meta(entry, entry.plan),
                       payloads)
        except Exception:
            store.write_errors += 1

    @traced("engine.store_load")
    def _store_load(self, key: Tuple,
                    sources: Mapping[str, Table]) -> Optional[CachedPlan]:
        """Second-tier lookup: validate, deserialize, and rehydrate a
        :class:`CachedPlan` without re-tracing. Returns ``None`` (and
        counts a miss or reject) whenever anything is off — the caller
        then compiles fresh, so a bad store can delay but never corrupt
        a session."""
        store = self._store
        if store is None or not self.jit:
            return None
        try:
            env = store_envelope(self.calibration)
            skey = store_key(key, env)
        except TypeError:       # a non-canonical key component: no store
            self._store_rejects += 1
            return None
        res = store.load(skey, env)
        if res.status == "miss":
            self._store_misses += 1
            return None
        if res.status == "reject":
            self._store_rejects += 1
            return None
        t0 = time.perf_counter()
        try:
            meta = res.header["meta"]
            if (meta.get("engine") != self.engine
                    or meta.get("dedup") != self.dedup):
                raise ValueError("entry engine/dedup mismatch")
            unpacked = unpack_entry_meta(meta, self._plan)
            if ("cap_locals" in unpacked) != (self.mesh is not None):
                raise ValueError("mesh/single-device entry mismatch")
            if self.verify != "off":
                # the rehydrated node-index lists mapped onto THIS
                # process's freshly lowered DAG must still describe a
                # well-formed plan — a colliding or corrupted entry that
                # slipped past the checksums rejects here, before its
                # executable is adopted
                from repro.analysis.verify import verify_plan
                report = verify_plan(
                    self._plan, self.engine, counts=unpacked["counts"],
                    caps=unpacked["caps"], sources=sources,
                    shard_local="cap_locals" in unpacked,
                    slack=self.slack, check_canonical=self.optimize,
                    check_cse=self.optimize)
                if not report.ok:
                    raise ValueError("stored plan metadata failed static "
                                     "verification: "
                                     + "; ".join(str(d) for d in
                                                 report.diagnostics[:3]))
                self._verify_store_checks += 1
            fn = None
            if NATIVE in res.payloads:
                try:          # fast tier: zero-recompile executable
                    fn = deserialize_native(res.payloads[NATIVE])
                except Exception:
                    fn = None
            if fn is None and STABLEHLO in res.payloads:
                fn = deserialize_stablehlo(res.payloads[STABLEHLO])
            if fn is None:
                raise ValueError("no loadable payload")
        except Exception as e:  # rehydration failure degrades to compile
            self._store_rejects += 1
            store._reject(f"rehydrate: {type(e).__name__}: {e}")
            return None
        self._store_hits += 1
        if unpacked.get("safe_exchange"):
            self._safe_exchange = True   # keep the sticky escalation
        entry = CachedPlan(key=key, plan=self._slim_plan(),
                           emitter=self._emitter,
                           counts=unpacked["counts"], caps=unpacked["caps"],
                           fn=fn, engine=self.engine, dedup=self.dedup,
                           mode=unpacked["mode"],
                           build_seconds=time.perf_counter() - t0,
                           cap_locals=unpacked.get("cap_locals"),
                           out_cap_local=unpacked.get("out_cap_local"),
                           sink_slack=unpacked.get("sink_slack", 1.0),
                           exchanges=unpacked.get("exchanges"),
                           safe_exchange=unpacked.get("safe_exchange",
                                                      False),
                           origin="store")
        PLAN_CACHE.put(key, entry)
        return entry

    def _ensure(self, sources: Mapping[str, Table]) -> Tuple[CachedPlan, bool]:
        key = self._key(sources)
        with span("engine.lookup"):
            entry = PLAN_CACHE.get(key)
        hit = entry is not None
        if hit:
            self._cache_hits += 1
        else:
            self._cache_misses += 1
            entry = self._store_load(key, sources)
            if entry is None:
                entry = self._build(key, sources)
        self._have_plan = True
        return entry, hit

    @staticmethod
    def _execute(entry: CachedPlan, *args):
        """Dispatch the entry's compiled closure (returns before the device
        finishes)."""
        with span("engine.execute"):
            return entry.fn(*args)

    @staticmethod
    def _overflowed(flag: jax.Array) -> int:
        """Wait for a closure's truncation flag and read it."""
        with span("engine.overflow_check"):
            return host_int(flag)

    # -- execution -----------------------------------------------------------
    def run(self, sources: Optional[Mapping[str, Table]] = None
            ) -> Tuple[Table, jax.Array]:
        """Execute the (cached) plan over ``sources`` (default: the session
        sources); transparently recompiles into bigger capacities when the
        closure reports truncation. Returns ``(kg, raw_count)``."""
        sources = self.sources if sources is None else sources
        with span("engine.run", step=self._executions + 1):
            return self._run(sources)

    __call__ = run

    def _run(self, sources: Mapping[str, Table]) -> Tuple[Table, jax.Array]:
        first = not self._have_plan
        t0 = time.perf_counter()
        entry, hit = self._ensure(sources)
        plan_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        if self.mesh is not None:
            kg, raw, entry, hit = self._run_mesh(entry, sources, hit)
        else:
            try:
                kg, raw, over = self._execute(entry, sources)
            except Exception:
                # a store-loaded executable that slipped past envelope
                # validation but cannot actually execute here is one more
                # store reject: rebuild fresh, never crash the session
                if entry.origin != "store":
                    raise
                self._store_rejects += 1
                hit = False
                entry = self._build(entry.key, sources)
                kg, raw, over = self._execute(entry, sources)
            if self._overflowed(over):
                # some buffer was truncated: re-annotate exactly against the
                # *current* extension, grow caps monotonically, re-run — the
                # one recompile per capacity-bucket crossing
                hit = False   # the hit did not actually serve this execution
                entry = self._build(entry.key, sources, mode="exact",
                                    floor_caps=entry.caps)
                kg, raw, over = self._execute(entry, sources)
                if self._overflowed(over):  # exact caps cannot under-size
                    raise RuntimeError("capacity overflow persisted after "
                                       "recompile — please report")
        exec_s = time.perf_counter() - t1
        self._executions += 1
        self._last = {"entry": entry, "cache_hit": hit, "first": first,
                      "plan_seconds": plan_s, "exec_seconds": exec_s,
                      "sources": sources}
        self._kg = kg          # the device-resident KG the query tier reads
        return kg, raw

    def create_kg(self) -> Tuple[Table, Dict[str, object]]:
        """Plan (or reuse) + execute; returns ``(KG, stats)`` with the
        Table-1-style sizes of ``mapsdi_create_kg`` plus the session's
        cache/recompile counters. ``source_rows_after`` is recounted
        against the *current* extension (a cache hit's plan-time counts
        may stem from a different same-bucket extension)."""
        with span("engine.create_kg", step=self._executions + 1):
            before = {k: host_int(v.count) for k, v in self.sources.items()}
            kg, raw = self.run()
            return kg, self._run_stats(kg, raw, source_rows_before=before,
                                       exact_rows=True)

    def ingest(self, deltas: Mapping[str, Table]
               ) -> Tuple[Table, Dict[str, object]]:
        """Append extension rows and re-execute (micro-batch/streaming).

        ``deltas`` maps source names to tables of *new* rows (columns
        aligned by name; encode them with the session's vocab, e.g. via
        ``Table.from_records(..., vocab=engine.vocab)``). Appends are
        shape-stable inside a capacity bucket — re-execution reuses the
        cached closure with zero re-trace; crossing a bucket (or
        overflowing an interior buffer) triggers exactly one transparent
        recompile. Returns ``(KG, stats)`` over the *accumulated* sources
        (the stats' ``source_rows_after`` are the cached plan-time counts —
        the steady-state path never re-reads the data; call
        :meth:`create_kg` when you need them recounted).
        """
        with span("engine.ingest", step=self._executions + 1):
            return self._ingest(deltas)

    def _ingest(self, deltas: Mapping[str, Table]
                ) -> Tuple[Table, Dict[str, object]]:
        # validate the whole batch before touching any session state, so a
        # bad name can never leave the session half-mutated
        unknown = sorted(set(deltas) - set(self.sources))
        if unknown:
            raise KeyError(f"unknown source(s) {unknown}")
        # σ-baked provenance only certifies the *materialized* rows; raw
        # delta rows may violate the owning maps' selections, so the flag
        # must be dropped (re-instating the join-parent re-select) before
        # the appended rows can reach a child join unfiltered
        tainted = {name for name in deltas
                   if name in self._dis.sigma_baked}
        if tainted:
            self._dis.sigma_baked -= tainted
            self._replan()
        with span("engine.append"):
            for name, delta in deltas.items():
                self.sources[name] = append_rows(self.sources[name], delta)
                self._ingested_rows += host_int(delta.count)
        # (the appended rows are fresh Table objects, which invalidates the
        # identity-keyed device-resident shard blocks — and, via the cache
        # key's shard-local capacity buckets, any cached closure whose
        # per-shard annotations a grown source outran)
        self._ingests += 1
        kg, raw = self.run()
        return kg, self._run_stats(kg, raw)

    # -- fused distributed execution -----------------------------------------
    @traced("engine.shard")
    def _shard_sources(self, sources: Mapping[str, Table],
                       cap_locals: Mapping[str, int]) -> Tuple[Dict, Dict]:
        """Row-shard the scanned sources onto the mesh (the input
        distribution step — the one place source rows cross the host
        boundary). Session sources are cached device-side keyed on the
        Table object's identity, so any replacement — an ingest's
        ``append_rows`` or a direct ``engine.sources[name] = ...`` — and
        any shard-bucket growth re-shards, while untouched sources reuse
        their resident blocks."""
        from repro.core.distributed import shard_table
        own = sources is self.sources
        datas: Dict[str, jax.Array] = {}
        counts: Dict[str, jax.Array] = {}
        for name in sorted(cap_locals):
            cap, table = cap_locals[name], sources[name]
            if own:
                hit = self._shard_cache.get(name)
                if hit is not None and hit[0] == cap and hit[1] is table:
                    datas[name], counts[name] = hit[2], hit[3]
                    continue
            d, c, _ = shard_table(table, self.mesh, self.mesh_axis,
                                  cap_local=cap)
            if own:
                self._shard_cache[name] = (cap, table, d, c)
            datas[name], counts[name] = d, c
        return datas, counts

    def _run_mesh(self, entry: CachedPlan, sources: Mapping[str, Table],
                  hit: bool):
        """Execute the fused mesh closure: shard inputs, run on device,
        recompile on (shard-local) capacity/exchange overflow or sink-δ
        bucket overflow, gather ONLY the final deduplicated KG and
        canonicalize its row order (one δ over the result — both paths end
        in the same δ kernel, so the output is bit-identical to the
        single-device plan)."""
        from repro.core.distributed import unshard_rows
        datas, counts = self._shard_sources(sources, entry.cap_locals)
        try:
            kg_d, kg_c, raw, over, sink_over = self._execute(entry, datas,
                                                             counts)
        except Exception:
            # store-loaded mesh executable failed at call time (see run())
            if entry.origin != "store":
                raise
            self._store_rejects += 1
            hit = False
            entry = self._build(entry.key, sources)
            kg_d, kg_c, raw, over, sink_over = self._execute(entry, datas,
                                                             counts)
        for _ in range(2):   # ≤1 capacity recompile + ≤1 sink-slack growth
            grow_caps = self._overflowed(over)
            grow_sink = self._overflowed(sink_over)
            if not (grow_caps or grow_sink):
                break
            hit = False   # the hit did not actually serve this execution
            # floors are ALWAYS the current entry's caps (growth must be
            # monotone or overflow ping-pongs), and a sink-only rebuild
            # must keep the mode a previous capacity rebuild escalated to.
            # A capacity/exchange overflow escalates to safe_exchange:
            # exact global counts as post-exchange caps and hard-safe
            # exchange buckets (cap_bucket = cap_local) are true bounds
            # even under adversarial key skew, so ONE recompile suffices.
            entry = self._build(
                entry.key, sources,
                mode="exact" if grow_caps else entry.mode,
                floor_caps=entry.caps,
                sink_slack=entry.sink_slack * (4.0 if grow_sink else 1.0),
                safe_exchange=bool(grow_caps) or entry.safe_exchange)
            kg_d, kg_c, raw, over, sink_over = self._execute(entry, datas,
                                                             counts)
        if self._overflowed(over):   # exact shard-local caps cannot under-size
            raise RuntimeError("mesh capacity overflow persisted after "
                               "recompile — please report")
        if self._overflowed(sink_over):
            raise RuntimeError("distributed δ bucket overflow at "
                               f"slack={entry.sink_slack:g}")
        with span("engine.unshard"):
            rows = unshard_rows(kg_d, kg_c, entry.out_cap_local)  # final KG
        kg = self._redistinct(rows, TRIPLE_ATTRS)
        return kg, raw, entry, hit

    def _redistinct(self, rows: np.ndarray, attrs: Tuple[str, ...]
                    ) -> Table:
        """δ of a mesh result gathered on the host: puts its rows in the
        canonical order of the single-device plan, which ends in the same
        δ. The rows are padded to their capacity bucket, so one jitted
        program per ``(capacity, width, dedup)``
        (:data:`repro.api.cache.REDISTINCT`) serves every run in a bucket.
        (The mesh closure's output capacity, ``out_cap_local`` per shard,
        is the bound-mode sink's, several times the KG: padding to it
        would multiply the δ's work.)"""
        with span("engine.redistinct"):
            table = Table.from_codes(rows, attrs,
                                     capacity=bucket_cap(len(rows)))
            key = (table.capacity, table.n_attrs, self.dedup)
            fn = REDISTINCT.get(key)
            if fn is None:
                fn = REDISTINCT[key] = _redistinct_program(self.dedup)
            data, count = fn(table.data, table.count)
        return Table(data=data, count=count, attrs=table.attrs)

    # -- queries -------------------------------------------------------------
    def _kg_table(self, kg: Optional[Table]) -> Table:
        """Resolve + bucket the KG table a query reads: the session KG by
        default (materialized on first use), an explicit ``kg=`` override
        otherwise. The bucketed view is cached on the KG object's identity,
        so repeated queries over one KG share a buffer (and, on a mesh,
        the resident shard blocks)."""
        if kg is None:
            if self._kg is None:
                self.run()          # materialize the session KG first
            kg = self._kg
        if tuple(kg.attrs) != TRIPLE_ATTRS:
            raise ValueError("query target must be a coded KG table with "
                             f"attrs {TRIPLE_ATTRS}, got {tuple(kg.attrs)}")
        hit = self._kg_bucket
        if hit is not None and hit[0] is kg:
            return hit[1]
        bucketed = _to_bucket(kg)
        self._kg_bucket = (kg, bucketed)
        return bucketed

    def _kg_cap_local(self, kg: Table) -> int:
        n = int(self.mesh.shape[self.mesh_axis])
        return bucket_cap(-(-kg.capacity // n))

    def _query_mesh_sig(self, kg: Table) -> Optional[Tuple]:
        """Query analogue of :meth:`_mesh_sig`: same static mesh identity
        and exchange/calibration components, with the KG's shard-local
        capacity bucket as the (single) source term."""
        if self.mesh is None:
            return None
        cal_sig = (None if self.calibration is None
                   else self.calibration.signature())
        return self._mesh_static + (
            self._kg_cap_local(kg), len(self._dis.vocab) < (1 << 16),
            self.join_exchange, cal_sig)

    def _query_key(self, query: Query, kg: Table) -> Tuple:
        c = self.config
        return query_session_key(query, dedup=c.dedup, mode=c.mode,
                                 slack=c.slack, jit=c.jit,
                                 kg_bucket_cap=kg.capacity,
                                 mesh_sig=self._query_mesh_sig(kg))

    def _verify_query_built(self, qplan, counts, caps, sources,
                            shard_local: bool) -> None:
        if self.verify == "off":
            return
        from repro.analysis.verify import verify_query_plan
        verify_query_plan(qplan, counts=counts, caps=caps, sources=sources,
                          shard_local=shard_local,
                          slack=self.slack).raise_for_status()
        self._verify_plan_checks += 1

    def _build_query(self, key: Tuple, qplan, kg: Table,
                     mode: Optional[str] = None,
                     floor_caps: Optional[Mapping] = None,
                     safe_exchange: bool = False) -> CachedPlan:
        """Query sibling of :meth:`_build`: annotate (globally or
        shard-locally), statically verify, compile (single-device or fused
        mesh), optionally audit and AOT-serialize to the plan store."""
        t0 = time.perf_counter()
        safe_exchange = safe_exchange or self._q_safe_exchange
        self._q_safe_exchange = safe_exchange
        sources = {KG_SOURCE: kg}
        aot = self._store is not None and self.jit
        abstract = None
        if self.mesh is None:
            counts, caps = annotate_query(qplan, sources,
                                          mode=mode or self.mode,
                                          slack=self.slack,
                                          cap_fn=bucket_cap)
            if floor_caps:  # growth must be monotone or overflow ping-pongs
                caps = {n: max(c, floor_caps.get(n, 0))
                        for n, c in caps.items()}
            self._verify_query_built(qplan, counts, caps, sources,
                                     shard_local=False)
            fn = compile_query(qplan, dedup=self.dedup, caps=caps,
                               jit=self.jit, report_overflow=True)
            if aot or self.verify == "full":
                abstract = (abstract_sources(sources),)
            if self.verify == "full":
                from repro.analysis.audit import audit_closure
                audit_closure(fn, abstract,
                              expected_counts={"all_gather": 0,
                                               "all_to_all": 0},
                              single_device=True).raise_for_status()
                self._verify_audits += 1
            entry = CachedPlan(key=key, plan=qplan, emitter=None,
                               counts=counts, caps=caps, fn=fn,
                               engine=self.engine, dedup=self.dedup,
                               mode=mode or self.mode,
                               build_seconds=time.perf_counter() - t0)
        else:
            from repro.query.mesh import (compile_query_mesh,
                                          query_mesh_abstract_inputs)
            n = int(self.mesh.shape[self.mesh_axis])
            cap_local = self._kg_cap_local(kg)
            counts, caps, exchanges = annotate_query_local(
                qplan, n_shards=n, cap_locals={KG_SOURCE: cap_local},
                mode=mode or self.mode, slack=self.slack,
                cap_fn=bucket_cap, sources=sources,
                join_exchange=self.join_exchange,
                safe_exchange=safe_exchange, calibration=self.calibration)
            if floor_caps:
                caps = {n_: max(c, floor_caps.get(n_, 0))
                        for n_, c in caps.items()}
            self._verify_query_built(qplan, counts, caps, sources,
                                     shard_local=True)
            fn, out_cap_local = compile_query_mesh(
                qplan, self.mesh, self.mesh_axis, dedup=self.dedup,
                caps=caps, cap_local=cap_local,
                pack_u16=len(self._dis.vocab) < (1 << 16), jit=self.jit,
                exchanges=exchanges, safe_exchange=safe_exchange)
            if aot or self.verify == "full":
                abstract = query_mesh_abstract_inputs(
                    cap_local, n, self.mesh, self.mesh_axis)
            if self.verify == "full":
                from repro.analysis.audit import (
                    audit_closure, expected_query_collectives)
                audit_closure(
                    fn, abstract, n_shards=n,
                    expected_counts=expected_query_collectives(
                        qplan, n, exchanges=exchanges)).raise_for_status()
                self._verify_audits += 1
            entry = CachedPlan(key=key, plan=qplan, emitter=None,
                               counts=counts, caps=caps, fn=fn,
                               engine=self.engine, dedup=self.dedup,
                               mode=mode or self.mode,
                               build_seconds=time.perf_counter() - t0,
                               cap_locals={KG_SOURCE: cap_local},
                               out_cap_local=out_cap_local,
                               exchanges=exchanges,
                               safe_exchange=safe_exchange)
        if aot:
            try:
                entry.fn = compile_for_store(fn, abstract)
            except Exception:   # AOT unavailable: keep the jitted closure
                self._store.write_errors += 1
                aot = False
            entry.build_seconds = time.perf_counter() - t0
        PLAN_CACHE.put(key, entry)
        self._builds += 1
        if aot:
            self._store_save(entry, fn, abstract)
        return entry

    def _query_store_load(self, key: Tuple, qplan,
                          sources: Mapping[str, Table]
                          ) -> Optional[CachedPlan]:
        """Query sibling of :meth:`_store_load`: the stored node-index
        metadata rehydrates against THIS process's freshly lowered query
        DAG (lowering is deterministic, so node_order matches); every
        failure degrades to a fresh compile."""
        store = self._store
        if store is None or not self.jit:
            return None
        try:
            env = store_envelope(self.calibration)
            skey = store_key(key, env)
        except TypeError:       # a non-canonical key component: no store
            self._q_store_rejects += 1
            return None
        res = store.load(skey, env)
        if res.status == "miss":
            self._q_store_misses += 1
            return None
        if res.status == "reject":
            self._q_store_rejects += 1
            return None
        t0 = time.perf_counter()
        try:
            meta = res.header["meta"]
            if (meta.get("engine") != self.engine
                    or meta.get("dedup") != self.dedup):
                raise ValueError("entry engine/dedup mismatch")
            unpacked = unpack_entry_meta(meta, qplan)
            if ("cap_locals" in unpacked) != (self.mesh is not None):
                raise ValueError("mesh/single-device entry mismatch")
            if self.verify != "off":
                from repro.analysis.verify import verify_query_plan
                report = verify_query_plan(
                    qplan, counts=unpacked["counts"],
                    caps=unpacked["caps"], sources=sources,
                    shard_local="cap_locals" in unpacked, slack=self.slack)
                if not report.ok:
                    raise ValueError("stored query metadata failed static "
                                     "verification: "
                                     + "; ".join(str(d) for d in
                                                 report.diagnostics[:3]))
                self._verify_store_checks += 1
            fn = None
            if NATIVE in res.payloads:
                try:          # fast tier: zero-recompile executable
                    fn = deserialize_native(res.payloads[NATIVE])
                except Exception:
                    fn = None
            if fn is None and STABLEHLO in res.payloads:
                fn = deserialize_stablehlo(res.payloads[STABLEHLO])
            if fn is None:
                raise ValueError("no loadable payload")
        except Exception as e:  # rehydration failure degrades to compile
            self._q_store_rejects += 1
            store._reject(f"rehydrate: {type(e).__name__}: {e}")
            return None
        self._q_store_hits += 1
        if unpacked.get("safe_exchange"):
            self._q_safe_exchange = True
        entry = CachedPlan(key=key, plan=qplan, emitter=None,
                           counts=unpacked["counts"], caps=unpacked["caps"],
                           fn=fn, engine=self.engine, dedup=self.dedup,
                           mode=unpacked["mode"],
                           build_seconds=time.perf_counter() - t0,
                           cap_locals=unpacked.get("cap_locals"),
                           out_cap_local=unpacked.get("out_cap_local"),
                           exchanges=unpacked.get("exchanges"),
                           safe_exchange=unpacked.get("safe_exchange",
                                                      False),
                           origin="store")
        PLAN_CACHE.put(key, entry)
        return entry

    def query(self, q: Query, kg: Optional[Table] = None) -> Table:
        """Evaluate a BGP :class:`~repro.query.Query` over the
        device-resident KG; returns the answer :class:`Table`
        (``SELECT DISTINCT`` semantics, one ``v__t``/``v__v`` column pair
        per term variable, ``v__p`` per predicate variable).

        The query goes through the same machinery as creation: lowered to
        the relational IR (:func:`repro.query.lower_query`), annotated with
        capacities, statically verified per the session's ``verify`` level,
        compiled to one jitted device-resident closure (fused ``shard_map``
        on a mesh session), cached in the process-wide plan cache under its
        own structural-fingerprint key tier, and AOT-persisted to the plan
        store when one is configured. A truncation flag triggers the same
        transparent recompile-with-exact-caps ladder as :meth:`run`.

        ``kg`` defaults to the session KG (materialized via :meth:`run` on
        first use); pass an explicit coded triple table to query something
        else (it shares the session's vocab codes by construction)."""
        with span("engine.query", step=self._executions):
            return self._query(q, kg)

    def _query(self, q: Query, kg: Optional[Table]) -> Table:
        table = self._kg_table(kg)
        qplan = lower_query(q)
        sources = {KG_SOURCE: table}
        key = self._query_key(q, table)
        entry = PLAN_CACHE.get(key)
        hit = entry is not None
        if hit:
            self._q_cache_hits += 1
        else:
            self._q_cache_misses += 1
            entry = self._query_store_load(key, qplan, sources)
            if entry is None:
                entry = self._build_query(key, qplan, table)
        if self.mesh is not None:
            result, entry, hit = self._run_query_mesh(entry, qplan, table,
                                                      hit)
        else:
            try:
                result, over = self._execute(entry, sources)
            except Exception:
                # store-loaded executable failed at call time (see run())
                if entry.origin != "store":
                    raise
                self._q_store_rejects += 1
                hit = False
                entry = self._build_query(key, qplan, table)
                result, over = self._execute(entry, sources)
            if self._overflowed(over):
                hit = False   # the hit did not actually serve this query
                self._q_recompiles += 1
                entry = self._build_query(key, qplan, table, mode="exact",
                                          floor_caps=entry.caps)
                result, over = self._execute(entry, sources)
                if self._overflowed(over):  # exact caps cannot under-size
                    raise RuntimeError("query capacity overflow persisted "
                                       "after recompile — please report")
        self._q_executions += 1
        self._q_last = {"entry": entry, "cache_hit": hit}
        return result

    def _run_query_mesh(self, entry: CachedPlan, qplan, table: Table,
                        hit: bool):
        """Execute the fused mesh query closure; mirrors :meth:`_run_mesh`:
        shard the (bucketed) KG once per KG object, run, recompile on
        overflow with exact caps + hard-safe exchange buckets, gather only
        the final rows and δ them canonically — which is what makes the
        mesh answer bit-identical to the single-device one."""
        from repro.core.distributed import unshard_rows
        datas, counts = self._shard_kg(table, entry.cap_locals[KG_SOURCE])
        try:
            out_d, out_c, over = self._execute(entry, datas, counts)
        except Exception:
            if entry.origin != "store":
                raise
            self._q_store_rejects += 1
            hit = False
            entry = self._build_query(entry.key, qplan, table)
            out_d, out_c, over = self._execute(entry, datas, counts)
        if self._overflowed(over):
            hit = False
            self._q_recompiles += 1
            entry = self._build_query(entry.key, qplan, table, mode="exact",
                                      floor_caps=entry.caps,
                                      safe_exchange=True)
            out_d, out_c, over = self._execute(entry, datas, counts)
            if self._overflowed(over):  # exact caps + safe buckets cannot
                # under-size
                raise RuntimeError("mesh query capacity overflow persisted "
                                   "after recompile — please report")
        with span("engine.unshard"):
            rows = unshard_rows(out_d, out_c, entry.out_cap_local)
        result = self._redistinct(rows, entry.plan.out_attrs)
        return result, entry, hit

    @traced("engine.shard")
    def _shard_kg(self, table: Table, cap_local: int) -> Tuple:
        """Shard the bucketed KG onto the mesh, cached on the table
        object's identity (a fresh KG from run()/ingest() re-shards)."""
        hit = self._kg_shard
        if hit is not None and hit[0] is table and hit[1] == cap_local:
            return hit[2], hit[3]
        from repro.core.distributed import shard_table
        d, c, _ = shard_table(table, self.mesh, self.mesh_axis,
                              cap_local=cap_local)
        self._kg_shard = (table, cap_local, d, c)
        return d, c

    def explain_query(self, q: Query, kg: Optional[Table] = None) -> str:
        """Annotated query-plan tree — the query analogue of
        :meth:`explain`: per-node rows/caps from the session's annotation
        mode, per-⋈ exchange decisions and wire-byte estimates on a mesh,
        and the static verifier's schema/verdict when verification is on."""
        from repro.plan.explain import dump_root
        table = self._kg_table(kg)
        qplan = lower_query(q)
        sources = {KG_SOURCE: table}
        exchanges = None
        if self.mesh is None:
            counts, caps = annotate_query(qplan, sources, mode=self.mode,
                                          slack=self.slack,
                                          cap_fn=bucket_cap)
        else:
            counts, caps, exchanges = annotate_query_local(
                qplan, n_shards=int(self.mesh.shape[self.mesh_axis]),
                cap_locals={KG_SOURCE: self._kg_cap_local(table)},
                mode=self.mode, slack=self.slack, cap_fn=bucket_cap,
                sources=sources, join_exchange=self.join_exchange,
                safe_exchange=self._q_safe_exchange,
                calibration=self.calibration)
        schemas = verdict = None
        if self.verify != "off":
            from repro.analysis.verify import verify_query_plan
            report = verify_query_plan(qplan, counts=counts, caps=caps,
                                       sources=sources,
                                       shard_local=self.mesh is not None,
                                       slack=self.slack)
            schemas, verdict = report.schemas, report.describe()
        return dump_root(qplan.root, counts=counts, caps=caps,
                         exchanges=exchanges, schemas=schemas,
                         verdict=verdict)

    # -- stats ---------------------------------------------------------------
    @property
    def vocab(self):
        return self._dis.vocab

    @traced("engine.stats")
    def _run_stats(self, kg: Table, raw, source_rows_before=None,
                   exact_rows: bool = False) -> Dict[str, object]:
        entry: CachedPlan = self._last["entry"]
        names = input_names(entry.plan)
        counts = entry.counts   # plan-time: exact for the extension the
        # entry was annotated against, an upper bound in "bound" mode
        if exact_rows and entry.mode == "exact" \
                and (self._last["cache_hit"] or entry.origin == "store"):
            # a hit reuses counts from whichever same-bucket extension
            # built the entry; recount for honest Table-1 reduced sizes
            counts, _ = annotate(entry.plan, mode="exact",
                                 sources=self._last["sources"])
        rows_after = {names[tm.name]: counts[entry.plan.inputs[tm.name]]
                      for tm in entry.plan.maps}
        pre_s = self._last["plan_seconds"]
        if self._last["first"]:
            pre_s += self._plan_seconds  # symbolic fixpoint, paid once
        return {
            "raw_triples": host_int(raw),
            "kg_triples": host_int(kg.count),
            "preprocess_seconds": pre_s,
            "semantify_seconds": self._last["exec_seconds"],
            "source_rows_before": (source_rows_before if source_rows_before
                                   is not None else
                                   {k: host_int(v.count)
                                    for k, v in self.sources.items()}),
            "source_rows_after": rows_after,
            "rule1": self._tstats.rule1_applications,
            "rule2": self._tstats.rule2_applications,
            "rule3": self._tstats.rule3_merges,
            "sigma": self._tstats.sigma_pushdowns,
            "cse_shared": self._tstats.cse_shared_subplans,
            "recompiles": self._recompiles,
            "plan_cache_hit": self._last["cache_hit"],
            "plan_cache_hits": self._cache_hits,
            "plan_cache_misses": self._cache_misses,
            "store_hits": self._store_hits,
            "store_misses": self._store_misses,
            "store_rejects": self._store_rejects,
        }

    def stats(self) -> Dict[str, object]:
        """Session-level counters (no execution side effects)."""
        out = {
            "engine": self.engine, "dedup": self.dedup, "mode": self.mode,
            "slack": self.slack, "optimize": self.optimize,
            "join_exchange": self.join_exchange,
            "verify": {"mode": self.verify,
                       "plan_checks": self._verify_plan_checks,
                       "audits": self._verify_audits,
                       "store_checks": self._verify_store_checks},
            "cost_model": ("static" if self.calibration is None
                           else self.calibration.source),
            "calibration": (None if self.calibration is None else {
                "all_gather_bw": self.calibration.all_gather_bw,
                "all_to_all_bw": self.calibration.all_to_all_bw,
                "launch_s": self.calibration.launch_s,
                "source": self.calibration.source,
            }),
            "executions": self._executions, "ingests": self._ingests,
            "ingested_rows": self._ingested_rows,
            "builds": self._builds,
            "recompiles": self._recompiles,
            "plan_cache_hits": self._cache_hits,
            "plan_cache_misses": self._cache_misses,
            "plan_cache": PLAN_CACHE.stats(),
            "store_hits": self._store_hits,
            "store_misses": self._store_misses,
            "store_rejects": self._store_rejects,
            "plan_store": (None if self._store is None
                           else self._store.stats()),
            "plan_seconds": self._plan_seconds,
            "source_buckets": {k: v.capacity
                               for k, v in self.sources.items()},
            "rule1": self._tstats.rule1_applications,
            "rule2": self._tstats.rule2_applications,
            "rule3": self._tstats.rule3_merges,
            "sigma": self._tstats.sigma_pushdowns,
            "cse_shared": self._tstats.cse_shared_subplans,
            "query": {
                "executions": self._q_executions,
                "cache_hits": self._q_cache_hits,
                "cache_misses": self._q_cache_misses,
                "recompiles": self._q_recompiles,
                "store_hits": self._q_store_hits,
                "store_misses": self._q_store_misses,
                "store_rejects": self._q_store_rejects,
            },
        }
        if self._q_last:
            out["query"]["last_cache_hit"] = self._q_last["cache_hit"]
        return out
