"""Spans of the engine's host phases, on the profiler's clock.

``span(name, **meta)`` is ``jax.profiler.TraceAnnotation("repro." +
name)``: inside a running ``jax.profiler`` trace it writes one host event
named ``repro.<name>`` (``meta`` as the event's arguments, e.g. ``step=``
the session's execution number), on the same clock as the device's
operations. With no profiler active it records nothing. Spans nest: the
innermost span around a moment says which phase the host was in.

Device work is named by plan operator with ``jax.named_scope`` instead
(``plan/compile.py``, ``plan/mesh.py``, ``relalg.ops.compact``): a scope
names the HLO operations it produces, at trace time only.
``docs/engine.md`` ("Tracing") lists every span and scope.
"""
from __future__ import annotations

import functools

import jax

PREFIX = "repro."


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """The context manager that records host phase ``name``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


def traced(name: str):
    """Decorator: every call of the function is the host phase ``name``."""
    return functools.partial(jax.profiler.annotate_function,
                             name=PREFIX + name)
