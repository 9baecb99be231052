"""Fused distributed execution: the whole plan inside one ``shard_map``.

``compile_mesh_plan`` is the mesh-aware sibling of
:func:`repro.plan.compile.compile_plan`: it lowers the optimized DAG to ONE
jitted closure whose body runs entirely inside a ``shard_map`` over row-
sharded sources — Scan reads this shard's row block, π/σ/∪ run on the
block, every interior δ is a *global* hash-repartition δ, every ⋈ moves its
inputs with one of two cost-modeled exchange strategies, ``EmitTriples``
semantifies the shard's rows, and the global sink δ runs fused on device
instead of as a gather-to-host post-pass. A distributed
``KGEngine.create_kg()``/``.ingest()`` therefore never materializes
intermediate triples on the host: the only host reads are the overflow
flags and the final (already-deduplicated) KG rows.

**Exact partition invariant.** Every relation node inside the body is an
exact *multiset* partition of its single-device value: Scans partition
rows, π/σ are row-wise, ∪ concatenates partitions, and an interior δ
repartitions by full-row hash (:func:`repro.core.distributed
.repartition_by_key`) so every copy of a row lands on one shard and the
local δ after the exchange is globally exact. Join exchanges preserve the
invariant on both sides, so per-shard ⋈ outputs and emit counts sum to the
single-device values — the mesh ``raw`` count (global per-map δ under
``sdm``, blind generation under ``rmlmapper``) is bit-identical to
:func:`compile_plan`'s, not just an upper bound.

**⋈ exchange strategies** (picked per join at plan time by the cost model
in :mod:`repro.plan.annotate`, threaded through ``exchanges``):

* ``gather`` — the parent side is ``all_gather``'ed across the axis
  (:func:`gather_table`) and each shard joins its child block against the
  full parent relation. One collective, shared across every ⋈ on the same
  parent node; wire bytes grow with the whole parent.
* ``repartition`` — both child and parent rows are hashed on the join key
  and exchanged with one ``all_to_all`` per side
  (:func:`repro.core.distributed.repartition_by_key`), so each shard joins
  only its key range. Wire bytes are ``(child + parent) / n_shards`` —
  the strategy that scales past the all_gather memory/bandwidth wall when
  the parent is large relative to ICI bandwidth.

Buffers are sized by SHARD-LOCAL capacities (``caps`` from
``annotate_local``, including the post-exchange Poisson bounds for
repartitioned δ/⋈ outputs); every capped node still reports a truncation
flag, every exchange reports its bucket-overflow flag, and the sink reports
its own, so ``KGEngine``'s recompile-on-overflow works per shard exactly as
on one device (``safe_exchange=True`` rebuilds with hard-safe bucket
capacities — ``cap_bucket = cap_local`` cannot overflow).

Scopes: each ⋈'s exchange runs under ``exchange`` and each global δ under
``distinct_global``; the tail runs under ``sink.distinct_per_map``,
``sink.union`` and ``sink.distinct``, as in
:func:`repro.plan.compile.compile_plan`.
"""
from __future__ import annotations

from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.distributed import (repartition_by_key,
                                    repartition_distinct_local,
                                    sink_bucket_cap)
from repro.relalg import PAD_ID, Table
from repro.relalg.ops import _masked_data, compact, dedup_rows

from .compile import execute_node
from .ir import Node, Scan, iter_nodes
from .lower import LogicalPlan


def plan_scans(plan: LogicalPlan) -> Dict[str, Scan]:
    """The Scan node per source name reachable from the plan's emits —
    the sources the mesh closure must receive as sharded row blocks."""
    scans: Dict[str, Scan] = {}
    for emit in plan.emits():
        for node in iter_nodes(emit):
            if isinstance(node, Scan):
                scans[node.source] = node
    return scans


def gather_table(table: Table, axis: str, n_shards: int) -> Table:
    """All_gather a shard-local table into the full (replicated) relation.

    Concatenates every shard's valid rows and compacts. The slices are
    exact multiset partitions of the global relation (interior δ is a
    global repartition δ — see the module docstring), so the gathered
    table IS the single-device relation, duplicates included: no
    post-gather dedup, and ⋈ multiplicities (hence ``raw``) stay exact.
    Must run inside a ``shard_map`` body over ``axis``.
    """
    cap_local = table.capacity
    gdata = lax.all_gather(_masked_data(table), axis, axis=0, tiled=True)
    gcounts = lax.all_gather(table.count, axis)          # [n_shards]
    idx = jnp.arange(n_shards * cap_local, dtype=jnp.int32)
    valid = (idx % cap_local) < gcounts[idx // cap_local]
    data, count = compact(jnp.where(valid[:, None], gdata, jnp.int32(PAD_ID)),
                          valid)
    return Table(data=data, count=count, attrs=table.attrs)


def mesh_abstract_inputs(plan: LogicalPlan,
                         cap_locals: Mapping[str, int], n_shards: int,
                         mesh=None, axis: Optional[str] = None):
    """The abstract ``(datas, counts)`` input pytrees of a mesh closure —
    :class:`jax.ShapeDtypeStruct` leaves shaped exactly as
    :func:`repro.core.distributed.shard_table` lays the sources out.

    With ``mesh``/``axis`` given, every leaf additionally carries the
    ``NamedSharding`` the real shard blocks arrive with, so AOT lowering
    (``run.lower(*abstract).compile()``) bakes the same input layout the
    jitted path would infer — the persistent plan store serializes that
    executable with its shard layout (mesh shape/axis/device ids are part
    of the store key, so a different mesh can never rehydrate it)."""
    scans = plan_scans(plan)
    shard_d = shard_c = None
    if mesh is not None:
        from jax.sharding import NamedSharding
        shard_d = NamedSharding(mesh, P(axis, None))
        shard_c = NamedSharding(mesh, P(axis))
    datas = {name: jax.ShapeDtypeStruct(
                (n_shards * int(cap_locals[name]),
                 len(scans[name].scan_attrs)),
                jnp.int32, sharding=shard_d)
             for name in scans}
    counts = {name: jax.ShapeDtypeStruct((n_shards,), jnp.int32,
                                         sharding=shard_c)
              for name in scans}
    return datas, counts


def compile_mesh_plan(plan: LogicalPlan, emitter, mesh, axis: str,
                      engine: str = "rmlmapper", dedup: Optional[str] = None,
                      caps: Optional[Mapping[Node, int]] = None,
                      cap_locals: Optional[Mapping[str, int]] = None,
                      sink_slack: float = 1.0, pack_u16: bool = False,
                      jit: bool = True,
                      exchanges: Optional[Mapping[Node, object]] = None,
                      safe_exchange: bool = False):
    """Lower the DAG to one mesh-resident closure; returns
    ``(run, out_cap_local)``.

    ``run(datas, counts)`` takes the sharded sources —
    ``datas[name] [n_shards * cap_locals[name], k]`` placed ``P(axis,
    None)`` and ``counts[name] [n_shards]`` placed ``P(axis)`` (see
    :func:`repro.core.distributed.shard_table`) — and returns
    ``(kg_data, kg_counts, raw, overflowed, sink_overflowed)`` where
    ``kg_data [n_shards * out_cap_local, 5]`` / ``kg_counts [n_shards]``
    hold the globally-deduplicated KG still sharded over ``axis``, ``raw``
    is the total triple count before the sink δ (bit-identical to the
    single-device plan's — see the module docstring), ``overflowed`` is
    the any-shard capacity-truncation OR interior-exchange bucket-overflow
    flag (re-run a ``safe_exchange=True`` build) and ``sink_overflowed``
    the sink repartition bucket-overflow flag (re-run with more
    ``sink_slack``).

    ``caps`` are SHARD-LOCAL node capacities (``annotate_local``);
    ``exchanges`` maps ⋈ nodes to their strategy (a
    :class:`repro.plan.annotate.JoinExchange` or a plain
    ``"gather"``/``"repartition"`` string; unmapped joins gather);
    ``safe_exchange`` sizes every exchange bucket at the hard-safe
    ``cap_bucket = cap_local`` instead of the Poisson bound; ``pack_u16``
    asserts every dictionary code fits 16 bits so each all_to_all moves
    ceil(k/2) words per row.
    """
    n_shards = int(mesh.shape[axis])
    emit_nodes = plan.emits()
    scans = plan_scans(plan)
    cap_locals = {name: int(cap_locals[name]) for name in scans}
    strategies = {node: getattr(x, "strategy", x)
                  for node, x in (exchanges or {}).items()}

    def _bucket_cap(cap_local: int, slack: float = 1.0) -> int:
        if n_shards == 1 or safe_exchange:
            return cap_local    # a shard sends at most its own rows to one
            # target, so cap_bucket = cap_local can never overflow
        return min(cap_local, sink_bucket_cap(cap_local, n_shards, slack))

    def body(datas: Dict[str, jax.Array], counts: Dict[str, jax.Array]):
        sources = {name: Table(data=datas[name],
                               count=counts[name].reshape(()),
                               attrs=scan.scan_attrs)
                   for name, scan in scans.items()}
        gathered: Dict[Node, Table] = {}
        exchanged: Dict[Tuple[Node, str], Table] = {}
        flags = []
        sink_flags = []

        def exchange_table(side_node: Node, table: Table,
                           key_attr: str) -> Table:
            """Key-partition one ⋈ side (memoized per (node, key))."""
            hit = exchanged.get((side_node, key_attr))
            if hit is None:
                data, cnt, over = repartition_by_key(
                    _masked_data(table), table.count, axis=axis,
                    n_shards=n_shards,
                    cap_bucket=_bucket_cap(table.capacity),
                    key_cols=(table.attrs.index(key_attr),),
                    pack_u16=pack_u16)
                flags.append(over)
                hit = exchanged[(side_node, key_attr)] = Table(
                    data=data, count=cnt, attrs=table.attrs)
            return hit

        def join_exchange(node: Node, left: Table, right: Table):
            with jax.named_scope("exchange"):
                if strategies.get(node) == "repartition":
                    return (exchange_table(node.left, left, node.left_key),
                            exchange_table(node.right, right,
                                           node.right_key))
                hit = gathered.get(node.right)
                if hit is None:
                    hit = gathered[node.right] = gather_table(right, axis,
                                                              n_shards)
                return left, hit

        def global_distinct(table: Table, cap_bucket: int,
                            flag_list) -> Table:
            """Global δ: local δ -> rowhash repartition -> local δ.

            The pre-exchange δ minimizes wire traffic (Rule 1 applied to
            the ICI); the exchange co-locates every cross-shard copy, so
            the second local δ is globally exact and the output is an
            exact partition of the single-device relation. One shard needs
            no exchange; the bucket-overflow flag lands in ``flag_list``
            (``flags`` = safe-exchange rebuild, ``sink_flags`` =
            sink-slack rebuild)."""
            with jax.named_scope("distinct_global"):
                data, cnt = dedup_rows(_masked_data(table), table.count,
                                       dedup)
                if n_shards > 1:
                    data, cnt, over = repartition_by_key(
                        data, cnt, axis=axis, n_shards=n_shards,
                        cap_bucket=cap_bucket, key_cols=None,
                        pack_u16=pack_u16)
                    flag_list.append(over)
                    data, cnt = dedup_rows(data, cnt, dedup)
                return Table(data=data, count=cnt, attrs=table.attrs)

        def distinct_global(node: Node, child: Table) -> Table:
            return global_distinct(child, _bucket_cap(child.capacity),
                                   flags)

        memo: Dict[Node, Table] = {}
        per_map = [execute_node(e, sources, memo, emitter, dedup, caps,
                                flags, join_exchange=join_exchange,
                                distinct_global=distinct_global)
                   for e in emit_nodes]
        if engine == "sdm":
            # global per-map δ — the single-device raw semantics. Every
            # map's surviving rows end up partitioned by the SAME full-row
            # hash, so the sink δ below collapses to one local δ (no
            # second exchange).
            with jax.named_scope("sink.distinct_per_map"):
                per_map = [global_distinct(t, sink_bucket_cap(t.capacity,
                                                              n_shards,
                                                              sink_slack),
                                           sink_flags)
                           for t in per_map]
        raw = jnp.sum(jnp.stack([t.count for t in per_map]))

        with jax.named_scope("sink.union"):
            data = jnp.concatenate([_masked_data(t) for t in per_map],
                                   axis=0)
            mask = jnp.concatenate([t.valid_mask for t in per_map])
            data, count = compact(data, mask)
        with jax.named_scope("sink.distinct"):
            if engine == "sdm":
                # rows are rowhash-partitioned per map already: local δ =
                # global
                kg_data, kg_count = dedup_rows(data, count, dedup)
                kg_count = kg_count.reshape(1)
                sink_over = (jnp.any(jnp.stack(sink_flags)) if sink_flags
                             else jnp.zeros((), dtype=bool)).reshape(1)
            else:
                # the fused sink δ: this shard's triples repartitioned by
                # rowhash so one local δ per shard is globally correct
                cap_bucket = sink_bucket_cap(data.shape[0], n_shards,
                                             sink_slack)
                kg_data, kg_count, sink_over = repartition_distinct_local(
                    data, count, axis=axis, n_shards=n_shards,
                    cap_bucket=cap_bucket, pack_u16=pack_u16, dedup=dedup)
        over = (jnp.any(jnp.stack(flags)) if flags
                else jnp.zeros((), dtype=bool))
        return (kg_data, kg_count, raw.reshape(1), over.reshape(1),
                sink_over)

    specs_data = {name: P(axis, None) for name in scans}
    specs_count = {name: P(axis) for name in scans}
    fn = shard_map(body, mesh=mesh, in_specs=(specs_data, specs_count),
                   out_specs=(P(axis, None), P(axis), P(axis), P(axis),
                              P(axis)))

    def run(datas: Dict[str, jax.Array], counts: Dict[str, jax.Array]):
        kg_data, kg_counts, raw, over, sink_over = fn(datas, counts)
        return (kg_data, kg_counts, jnp.sum(raw), jnp.any(over),
                jnp.any(sink_over))

    if jit:
        run = jax.jit(run)

    abstract = mesh_abstract_inputs(plan, cap_locals, n_shards)
    out_shape = jax.eval_shape(run, *abstract)[0]
    out_cap_local = out_shape.shape[0] // n_shards
    return run, out_cap_local
