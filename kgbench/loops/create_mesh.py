"""Closed-loop batch rebuilds on a mesh: the loop of ``create.py``, with
every session opened on a one-axis mesh over the cell's chips.

Set-up generates ``traffic["datasets"]`` datasets (seeds ``seed``,
``seed + 1``, ...) coded against one vocabulary and rebuilds once from
each, which compiles the mesh plan and the re-δ of its result (or loads
them from JAX's cache). Each rebuild of the window uploads the next
dataset (span ``upload``), opens a new ``KGEngine`` whose configuration
holds the mesh over ``jax.devices()[:chips]`` on the configuration's
``mesh_axis``, calls ``create_kg`` and waits for the KG (span
``create_kg``). A sample of the rebuilds, drawn from the seed, keeps its
KG for the check, which compares it with the reference with join object
maps (``refkg_join.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import numpy as np

from kgbench import refkg_join
from kgbench.loops import create
from kgbench.loops.create import program_output, rebuild
from kgbench.refkg import rows_of_triples, triples_diff, triples_of_rows


def _redistinct_builds():
    """The system's count of mesh re-δ programs built, or ``None`` where
    the system keeps no such count."""
    from repro.api import plan_cache_stats
    return plan_cache_stats().get("redistinct_builds")


def setup(ctx) -> create.State:
    state = create.State(ctx)
    devices = np.array(jax.devices()[:int(ctx.cfg["chips"])])
    mesh = jax.sharding.Mesh(devices, (ctx.cfg["mesh_axis"],))
    state.config = dataclasses.replace(state.config, mesh=mesh,
                                       mesh_axis=ctx.cfg["mesh_axis"])
    for d in range(len(state.dep.datasets)):
        rebuild(ctx, state, d)
    return state


def recompiles(state: create.State) -> int:
    return create.recompiles(state) + int(_redistinct_builds() or 0)


def window(ctx, state: create.State, seconds: float) -> Dict:
    """``create.window``; also counts the re-δ programs built in the
    window per rebuild (counter ``redistinct_builds``)."""
    before = _redistinct_builds()
    result = create.window(ctx, state, seconds)
    if before is not None and result["attempted"]:
        ctx.count("redistinct_builds", (_redistinct_builds() - before)
                  / result["attempted"])
    return result


def reference(state: create.State, d: int, blocks: int = 1):
    dep = state.dep
    return refkg_join.reference_kg(dep.maps, dep.datasets[d], dep.attrs,
                                   dep.constant_codes, blocks=blocks)


def control_output(state: create.State):
    """The control, put in the program's place: each kept KG's reference
    with a shard-local join, each child row matched only against the
    parent rows of its own shard block (what a mesh plan that skipped the
    ⋈'s exchange would give)."""
    blocks = int(state.config.mesh.shape[state.config.mesh_axis])
    return [(d, rows_of_triples(reference(state, d, blocks)))
            for d, _ in state.kept]


def check(ctx, state: create.State, result: Dict, output=None):
    """Compares ``output`` (by default the program's) with the
    reference."""
    from kgbench.harness import Check
    got = program_output(state) if output is None else output
    refs = {}
    diff = 0
    for d, rows in got:
        if d not in refs:
            refs[d] = reference(state, d)
        triples, repeats = triples_of_rows(rows)
        diff += triples_diff(triples, refs[d]) + repeats
    return [Check("kg_triples_diff", diff, 0)]
