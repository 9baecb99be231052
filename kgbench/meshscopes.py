"""A mesh cell's traced window by the system's own names: device time
under the exchange's scopes and under the re-δ of the gathered result,
and device idle in the host steps around the mesh plan.

``progtrace`` knows the plan-operator scopes. The engine runs the re-δ of
a mesh result (``KGEngine._redistinct``) as a program of its own under the
scope ``redistinct``, which this module adds to those scopes when it reads
the trace. A program without that scope (the system before it named the
re-δ) reads ``None``.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence
from unittest import mock

from kgbench import progtrace

#: the plan-operator scopes and the re-δ of a mesh result
SCOPES = progtrace.SCOPES + ("redistinct",)
#: the exchanges of the ⋈ and the global δ (``plan/mesh.py``)
EXCHANGE = ("exchange", "distinct_global")
#: the program's host spans that put rows on the mesh and take the KG off
HOST_SPANS = ("engine.shard", "engine.unshard", "engine.redistinct")


@contextlib.contextmanager
def scopes_read():
    """Within: ``progtrace`` reads :data:`SCOPES` as the known scopes."""
    with mock.patch.object(progtrace, "SCOPES", SCOPES):
        yield


def program(run) -> Optional[progtrace.Program]:
    """The run's :class:`progtrace.Program`, its scopes read with
    :data:`SCOPES` (once, kept on the run, and handed to ``progtrace``'s
    readers too); ``None`` for a run without a trace."""
    if getattr(run, "profile", None) is None:
        return None
    prog = getattr(run, "mesh_program", None)
    if prog is None:
        from kgbench.harness import TRACE_DIR
        base = {"devices": run.profile.devices, "spans": run.profile.spans}
        with scopes_read():
            data = progtrace.extract(TRACE_DIR, base=base)
        prog = run.mesh_program = progtrace.Program(run.profile, data)
        if getattr(run, "program", None) is None:
            run.program = prog
    return prog


def scope_share_pct(prog: Optional[progtrace.Program], busy_s: float,
                    names: Sequence[str]) -> Optional[float]:
    """Device time of the window's ops under any of the scopes ``names``
    over busy time, both averaged over the devices, in %; ``None`` where
    no op has such a scope."""
    def match(path: str) -> bool:
        return any(s in names for s in path.split("/"))

    if prog is None or not any(p is not None and match(p)
                               for ps in prog.paths.values() for p in ps):
        return None
    return 100.0 * prog.scope_seconds(match) / busy_s


def share_pct(run, names: Sequence[str]) -> Optional[float]:
    prog = program(run)
    return None if prog is None else scope_share_pct(
        prog, run.profile.busy_s, names)


def host_ms(run) -> Optional[float]:
    """Device idle inside :data:`HOST_SPANS` per ``engine.create_kg`` that
    starts in the window, ms."""
    if program(run) is None:
        return None
    return progtrace.engine_idle_ms(run, HOST_SPANS, per="engine.create_kg")
