"""Compiling the optimized logical plan to device execution.

Two consumers:

* :func:`compile_plan` — the full pipeline: one jitted
  ``sources -> (KG, raw)`` closure executing pre-processing *and*
  semantification as a single XLA program. Shared subplans (CSE'd nodes,
  join parents) are evaluated once per call; nothing touches the host.
* :func:`materialize_plan` — the ``apply_mapsdi`` path: evaluate just the
  per-map relation inputs (one jitted call, shared subtrees computed once)
  and shrink the results into a concrete ``DIS'`` — the *only* host sync of
  the whole transformation, at the very end.

Execution is memoized on the structurally-hashable node itself, so equal
subtrees collapse even if a rewrite produced them as separate objects.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Tuple

import dataclasses
import jax
import jax.numpy as jnp

from repro.core.schema import DIS
from repro.relalg import (PAD_ID, Table, distinct, equi_join, project,
                          project_as, round_cap, select_mask, shrink_to_fit)
from repro.relalg.guard import host_int
from repro.relalg.ops import _masked_data, compact

from .ir import (ColEq, Distinct, EmitTriples, EquiJoin, Node, Project,
                 Scan, Select, Union, iter_nodes)
from .lower import LogicalPlan, selection_preds


def _fit(table: Table, cap: Optional[int]) -> Table:
    """Re-buffer a compacted table at a plan-time capacity (device only)."""
    if cap is None or cap == table.capacity:
        return table
    if cap < table.capacity:
        return Table(data=table.data[:cap],
                     count=jnp.minimum(table.count, jnp.int32(cap)),
                     attrs=table.attrs)
    pad = jnp.full((cap - table.capacity, table.n_attrs), jnp.int32(PAD_ID))
    return Table(data=jnp.concatenate([table.data, pad], axis=0),
                 count=table.count, attrs=table.attrs)


def _pred_mask(table: Table, preds) -> jax.Array:
    mask = jnp.ones((table.capacity,), dtype=bool)
    for p in preds:
        col = table.column(p.attr)
        if p.op == "eq":
            mask &= col == jnp.int32(p.code)
        else:  # 'neq' / 'notnull' both exclude one code
            mask &= col != jnp.int32(p.code)
    return mask


def execute_node(node: Node, sources: Mapping[str, Table],
                 memo: Dict[Node, Table], emitter=None,
                 dedup: Optional[str] = None,
                 caps: Optional[Mapping[Node, int]] = None,
                 overflow: Optional[List[jax.Array]] = None, *,
                 join_exchange=None, distinct_global=None) -> Table:
    """Evaluate one DAG node (and, via ``memo``, each shared subtree once).

    When ``overflow`` is a list, every capped operator appends a scalar
    bool flag — "this node needed more rows than its plan-time capacity and
    was truncated" — exactly once per unique node. ``KGEngine`` reduces the
    flags to its recompile-on-overflow signal.

    ``join_exchange`` and ``distinct_global`` are the mesh hooks
    (:mod:`repro.plan.mesh`); single-device execution leaves them ``None``:

    * ``join_exchange(node, left, right) -> (left, right)`` runs before
      every ⋈ — the fused distributed plan either all_gathers the
      (shard-local) parent rows so a row-sharded child joins against the
      full parent relation, or hash-repartitions *both* sides by join key
      so each shard joins only its key range.
    * ``distinct_global(node, child) -> table`` replaces the local δ of a
      ``Distinct`` node — the mesh makes it a global hash-repartition δ,
      so every interior relation stays an exact multiset partition of its
      single-device value (what keeps the mesh ``raw`` count exact). The
      returned table is still fitted to the node's plan-time capacity and
      flagged on truncation here.

    Each operator's device work runs under a ``jax.named_scope`` of its
    kind (``select``, ``coleq``, ``project``, ``union``, ``distinct``,
    ``join``, ``emit``); a ⋈'s exchange runs before, outside ``join``.
    """
    hit = memo.get(node)
    if hit is not None:
        return hit
    caps = caps or {}
    kw = dict(join_exchange=join_exchange, distinct_global=distinct_global)

    def child(n: Node) -> Table:
        return execute_node(n, sources, memo, emitter, dedup, caps, overflow,
                            **kw)

    def flag(count: jax.Array, cap: Optional[int]) -> None:
        if overflow is not None and cap is not None:
            overflow.append(count > jnp.int32(cap))

    # children are evaluated outside the node's scope, so each operation
    # carries the scope of the one operator that produced it
    if isinstance(node, Scan):
        out = sources[node.source]
    elif isinstance(node, Project):
        table = child(node.child)
        with jax.named_scope("project"):
            out = project_as(table, list(node.spec))
    elif isinstance(node, Select):
        table = child(node.child)
        with jax.named_scope("select"):
            sel = select_mask(table, _pred_mask(table, node.preds))
            cap = caps.get(node)
            flag(sel.count, cap)
            out = _fit(sel, cap)
    elif isinstance(node, ColEq):
        table = child(node.child)
        with jax.named_scope("coleq"):
            mask = (table.column(node.left_attr)
                    == table.column(node.right_attr))
            sel = select_mask(table, mask)
            cap = caps.get(node)
            flag(sel.count, cap)
            out = _fit(sel, cap)
    elif isinstance(node, Distinct):
        table = child(node.child)
        with jax.named_scope("distinct"):
            dd = (distinct(table, dedup=dedup) if distinct_global is None
                  else distinct_global(node, table))
            cap = caps.get(node)
            flag(dd.count, cap)
            out = _fit(dd, cap)
    elif isinstance(node, Union):
        parts = [child(c) for c in node.inputs]
        with jax.named_scope("union"):
            aligned = [parts[0]] + [project(p, parts[0].attrs)
                                    for p in parts[1:]]
            data = jnp.concatenate([_masked_data(p) for p in aligned],
                                   axis=0)
            keep = jnp.concatenate([p.valid_mask for p in aligned])
            data, count = compact(data, keep)
            out = Table(data=data, count=count, attrs=parts[0].attrs)
    elif isinstance(node, EquiJoin):
        left, right = child(node.left), child(node.right)
        if join_exchange is not None:
            left, right = join_exchange(node, left, right)
        with jax.named_scope("join"):
            cap = caps.get(node, round_cap(left.capacity * 4))
            out, total = equi_join(left, right, node.left_key,
                                   node.right_key, out_capacity=cap,
                                   right_suffix=node.right_suffix)
            if overflow is not None:
                overflow.append(total > jnp.int32(cap))
    elif isinstance(node, EmitTriples):
        if emitter is None:
            raise ValueError("EmitTriples node needs an emitter")
        table = child(node.input)
        joins = {i: child(j) for i, j in node.joins}
        with jax.named_scope("emit"):
            out = emitter.emit_triples(node.tm, table, joins)
    else:
        raise TypeError(f"cannot execute node {type(node).__name__}")
    memo[node] = out
    return out


def abstract_sources(sources: Mapping[str, Table]) -> Dict[str, Table]:
    """The :class:`jax.ShapeDtypeStruct` skeleton of a source mapping —
    same pytree (Tables with their static attrs), no device buffers.

    What AOT lowering (``compile_plan(...).lower(abstract).compile()``)
    and ``jax.export`` trace against: the compiled program depends only on
    shapes/dtypes, and the plan-cache/store key pins those exactly (source
    buffer capacities are part of the key), so an executable lowered from
    this skeleton serves every same-key extension."""
    return {name: Table(data=jax.ShapeDtypeStruct(t.data.shape,
                                                  t.data.dtype),
                        count=jax.ShapeDtypeStruct(t.count.shape,
                                                   t.count.dtype),
                        attrs=t.attrs)
            for name, t in sources.items()}


def compile_plan(plan: LogicalPlan, emitter, engine: str = "rmlmapper",
                 dedup: Optional[str] = None,
                 caps: Optional[Mapping[Node, int]] = None, jit: bool = True,
                 report_overflow: bool = False):
    """Lower the DAG to one ``sources -> (kg, raw)`` closure (jitted by
    default). Mirrors the engine semantics: ``"sdm"`` deduplicates each
    map's output as it is produced, ``"rmlmapper"`` only at the sink; the
    sink δ runs in either mode. ``raw`` is the engine's materialized triple
    count before the sink δ.

    Capacities in ``caps`` are sized for the planning-time extension;
    re-running the closure on extensions where more rows survive a node
    than planned truncates (the ``equi_join`` overflow convention). With
    ``report_overflow=True`` the closure returns ``(kg, raw, overflowed)``
    where ``overflowed`` is a scalar bool — True iff any capped node was
    truncated — which is what lets ``KGEngine`` re-execute safely instead
    of silently truncating: re-plan (or let the engine recompile) when it
    fires.

    The engine/sink semantics below (per-map δ under sdm, δδ = δ for a
    single map, sink δ) must stay in lockstep with
    :meth:`LogicalPlan.sink`, which is what ``dump_plan``/``explain``
    display. The distributed sibling is
    :func:`repro.plan.mesh.compile_mesh_plan` (same DAG, one shard_map
    body, the sink δ fused as a repartition collective).

    The closure's tail runs under the scopes ``sink.distinct_per_map``
    (sdm's per-map δ), ``sink.union`` (the merge of the maps' triples) and
    ``sink.distinct`` (the sink δ)."""
    emit_nodes = plan.emits()

    def fn(sources: Mapping[str, Table]):
        memo: Dict[Node, Table] = {}
        flags: Optional[List[jax.Array]] = [] if report_overflow else None
        per_map = [execute_node(e, sources, memo, emitter, dedup, caps,
                                flags)
                   for e in emit_nodes]
        if engine == "sdm":
            with jax.named_scope("sink.distinct_per_map"):
                per_map = [distinct(t, dedup=dedup) for t in per_map]
        raw = jnp.sum(jnp.stack([t.count for t in per_map]))

        def done(kg: Table):
            if not report_overflow:
                return kg, raw
            over = (jnp.any(jnp.stack(flags)) if flags
                    else jnp.zeros((), dtype=bool))
            return kg, raw, over

        if engine == "sdm" and len(per_map) == 1:
            return done(per_map[0])     # δδ = δ: per-map δ IS the sink δ
        with jax.named_scope("sink.union"):
            data = jnp.concatenate([t.data for t in per_map], axis=0)
            mask = jnp.concatenate([t.valid_mask for t in per_map])
            data, count = compact(data, mask)
            merged = Table(data=data, count=count, attrs=per_map[0].attrs)
        with jax.named_scope("sink.distinct"):
            kg = distinct(merged, dedup=dedup)
        return done(kg)

    return jax.jit(fn) if jit else fn


# ---------------------------------------------------------------------------
# materialization (the apply_mapsdi back end)
# ---------------------------------------------------------------------------

def input_names(plan: LogicalPlan) -> Dict[str, str]:
    """Deterministic materialization name per map: Rule-3 merges keep their
    recorded ``merged_*`` label, δπ(σ) chains derive ``src__pi_attrs`` (+
    ``__sigma``), untouched scans keep the source name."""
    names: Dict[str, str] = {}
    node_name: Dict[Node, str] = {}
    used: Dict[str, Node] = {}
    for tm in plan.maps:
        node = plan.inputs[tm.name]
        if node in node_name:
            names[tm.name] = node_name[node]
            continue
        if isinstance(node, Scan):
            name = node.source
        elif node in plan.names:
            name = plan.names[node]
        else:
            scans = sorted({n.source for n in iter_nodes(node)
                            if isinstance(n, Scan)})
            base = scans[0] if len(scans) == 1 else "plan"
            name = f"{base}__pi_" + "_".join(node.attrs)
            if any(isinstance(n, Select) for n in iter_nodes(node)):
                name += "__sigma"
        k, candidate = 0, name
        while candidate in used and used[candidate] != node:
            k += 1
            candidate = f"{name}_{k}"
        used[candidate] = node
        node_name[node] = candidate
        names[tm.name] = candidate
    return names


def materialize_plan(plan: LogicalPlan, dedup: Optional[str] = None
                     ) -> Tuple[DIS, Dict[str, int]]:
    """Evaluate the plan's relation inputs into a concrete ``DIS'``.

    All device work happens in ONE jitted call with shared subtrees
    evaluated once; the host syncs exactly once per new source, at the end
    (``shrink_to_fit``), mirroring the paper's pre-processed files.
    """
    dis = plan.dis
    names = input_names(plan)
    ordered: List[Node] = []
    for tm in plan.maps:
        node = plan.inputs[tm.name]
        if node not in ordered and not isinstance(node, Scan):
            ordered.append(node)

    tables: Dict[Node, Table] = {}
    if ordered:
        def run(sources):
            memo: Dict[Node, Table] = {}
            return [execute_node(n, sources, memo, dedup=dedup)
                    for n in ordered]
        for node, table in zip(ordered, jax.jit(run)(dis.sources)):
            tables[node] = table

    sources: Dict[str, Table] = {}
    preprocessed = set()
    sigma_baked: Dict[str, bool] = {}
    rows_after: Dict[str, int] = {}
    new_maps = []
    for tm in plan.maps:
        node, name = plan.inputs[tm.name], names[tm.name]
        if name not in sources:
            if isinstance(node, Scan):
                sources[name] = dis.sources[node.source]
                if node.source in plan.preprocessed:
                    preprocessed.add(name)
            else:
                sources[name] = shrink_to_fit(tables[node])  # the host sync
                preprocessed.add(name)
            rows_after[name] = host_int(sources[name].count)
        # σ-baked provenance: the materialized extension carries the map's
        # σ selections iff they were pushed into the materialized subtree
        # (or the source was already flagged). A source shared by several
        # maps is baked only if it is baked for every one of them.
        if isinstance(node, Scan):
            ok = node.source in plan.sigma_baked
        else:
            have = {p for n in iter_nodes(node)
                    if isinstance(n, Select) for p in n.preds}
            ok = all(p in have for p in selection_preds(dis, tm))
        sigma_baked[name] = sigma_baked.get(name, True) and ok
        new_maps.append(tm if tm.source == name
                        else dataclasses.replace(tm, source=name))

    out = dis.copy()
    out.sources = sources
    out.maps = new_maps
    out.preprocessed = preprocessed
    out.sigma_baked = {name for name, ok in sigma_baked.items() if ok}
    return out, rows_after
