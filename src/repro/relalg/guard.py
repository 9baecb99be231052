"""Device↔host transfer accounting.

The MapSDI planner's headline invariant is that the Rule 1–3 fixpoint runs
*symbolically* — zero device work, zero host syncs — until one final
materialization. This module makes that invariant observable:

* Every host materialization in the repo goes through :func:`host_get`
  (array) / :func:`host_int` (scalar) instead of bare ``np.asarray`` /
  ``int``. The helpers behave identically but tick any active
  :class:`TransferLedger`, and write a ``repro.sync`` span around the
  wait into an active profiler trace (:mod:`repro.trace`).
* :func:`count_transfers` counts device→host syncs over a region (the
  planner benchmark reports eager-vs-planned sync counts with it).
* :func:`forbid_transfers` additionally arms ``jax.transfer_guard`` so even
  an *un*-instrumented implicit transfer raises — the belt-and-braces check
  the planner tests use on the symbolic fixpoint.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Iterator, List

import jax
import numpy as np

from repro.trace import span


@dataclasses.dataclass
class TransferLedger:
    """Counts device→host materializations observed while active."""

    device_to_host: int = 0

    def tick(self, n: int = 1) -> None:
        self.device_to_host += n


_ACTIVE: List[TransferLedger] = []


def host_get(x) -> np.ndarray:
    """``np.asarray`` that ticks active transfer ledgers.

    The single sanctioned way to pull a device array to host; jax-array
    inputs count as one device→host sync, numpy inputs are free.
    """
    if isinstance(x, jax.Array):
        for ledger in _ACTIVE:
            ledger.tick()
        with span("sync"):
            return np.asarray(x)
    return np.asarray(x)


def host_int(x) -> int:
    """``int()`` that ticks active transfer ledgers for device scalars."""
    if isinstance(x, jax.Array):
        for ledger in _ACTIVE:
            ledger.tick()
        with span("sync"):
            return int(x)
    return int(x)


@contextlib.contextmanager
def count_transfers() -> Iterator[TransferLedger]:
    """Count instrumented device→host syncs inside the ``with`` block."""
    ledger = TransferLedger()
    _ACTIVE.append(ledger)
    try:
        yield ledger
    finally:
        _ACTIVE.remove(ledger)


@contextlib.contextmanager
def forbid_transfers() -> Iterator[TransferLedger]:
    """Raise on ANY device→host sync inside the ``with`` block.

    Combines the instrumented ledger (raises on :func:`host_get` /
    :func:`host_int`) with ``jax.transfer_guard("disallow")``, which makes
    jax itself reject implicit transfers (e.g. ``int(count)``) that might
    bypass the instrumentation.
    """
    with count_transfers() as ledger:
        with jax.transfer_guard("disallow"):
            yield ledger
        if ledger.device_to_host:
            raise RuntimeError(
                f"{ledger.device_to_host} device→host transfer(s) inside a "
                "forbid_transfers() region")
