# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Shared backend gating for every kernel package's dispatcher.

Each ``ops.py`` dispatcher resolves its ``use_pallas=None`` default the
same way; the resolution lives here (instead of per-package ``_on_tpu``
copies) so the policy — and the CI interpret-mode override — is defined
exactly once:

* ``on_tpu()`` — the Pallas kernels target real TPUs; elsewhere the
  pure-jnp oracle is the faster *and* always-available path.
* ``REPRO_PALLAS_INTERPRET=1`` forces ``use_pallas=None`` to resolve True
  off-TPU too, running the kernel **bodies** through the Pallas
  interpreter (``pallas_call(interpret=True)``) — the CI leg that
  exercises the real kernel code on CPU runners instead of only the
  oracles. Explicit ``use_pallas=True/False`` is always honored.
* ``pallas_interpret()`` — a kernel taken off-TPU runs interpreted; on a
  TPU it is always the compiled Mosaic kernel, never the interpreter.
* ``out_struct()`` — a kernel's output shape carrying the mesh axes its
  inputs vary over, so a kernel called inside ``shard_map`` passes the
  replication (``check_vma``) check.
* ``dispatch_path()`` — every KG-path dispatcher reports the path it took
  (``"compiled"``, ``"interpret"`` or ``"oracle"``) at trace time into
  :data:`DISPATCH_COUNTS`, so which path ran is never silent.

No kernel subpackage is imported here: consumers import
``repro.kernels.<pkg>`` directly, which keeps this module dependency-free
(and cycle-free — relalg imports kernels, never the reverse).
"""
from __future__ import annotations

import collections
import os
from typing import Optional

import jax


def on_tpu() -> bool:
    """True iff the default jax backend is a real TPU."""
    return jax.default_backend() == "tpu"


def pallas_interpret_forced() -> bool:
    """True iff ``$REPRO_PALLAS_INTERPRET`` requests interpret-mode kernels
    (read per call: tests toggle it with ``monkeypatch.setenv``)."""
    return os.environ.get("REPRO_PALLAS_INTERPRET", "").strip() \
        not in ("", "0", "false", "no")


def resolve_use_pallas(use_pallas: Optional[bool]) -> bool:
    """The single ``use_pallas=None`` policy: kernels on TPU, oracles
    elsewhere — unless the interpret-mode env flag opts the kernel bodies
    in on CPU."""
    if use_pallas is None:
        return on_tpu() or pallas_interpret_forced()
    return bool(use_pallas)


def pallas_interpret() -> bool:
    """Whether a Pallas call taken off-TPU must run interpreted (always:
    only a real TPU executes compiled Mosaic)."""
    return not on_tpu()


#: trace-time tally of dispatcher decisions: ``{(kernel, path): n}``
DISPATCH_COUNTS: collections.Counter = collections.Counter()


def dispatch_path(kernel: str, use_kernel: bool) -> str:
    """Record and return the path a dispatcher takes for one trace:
    ``"oracle"`` when the kernel is not used, else ``"interpret"`` off-TPU
    and ``"compiled"`` on a TPU."""
    path = ("oracle" if not use_kernel
            else "interpret" if pallas_interpret() else "compiled")
    DISPATCH_COUNTS[(kernel, path)] += 1
    return path


def out_struct(shape, dtype, *like) -> jax.ShapeDtypeStruct:
    """``ShapeDtypeStruct`` for a ``pallas_call`` output that varies over
    every mesh axis any of ``like`` varies over (none outside
    ``shard_map``)."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
