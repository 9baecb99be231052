"""Fixed-capacity columnar tables on device.

A ``Table`` is the SPMD-friendly stand-in for the paper's CSV sources: an
int32 matrix ``data[capacity, n_attrs]`` of dictionary codes plus a dynamic
``count`` of valid rows. Rows ``>= count`` are padding filled with ``PAD_ID``
(INT32_MAX) so that lexicographic sorts push them to the end.

Static metadata (attribute names, capacity) is pytree aux data, so tables
flow through ``jax.jit``/``shard_map`` unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.trace import span

from .encoding import PAD_ID, Vocab
from .guard import host_get, host_int


def round_cap(n: int, mult: int = 8) -> int:
    """Round a row count up to a capacity multiple (minimum one multiple)."""
    return max(mult, ((int(n) + mult - 1) // mult) * mult)


def bucket_cap(n: int, mult: int = 8, growth: float = 2.0) -> int:
    """Round a row count up to a *geometric* capacity bucket (8, 16, 32, …).

    :func:`round_cap` sizes a buffer exactly; ``bucket_cap`` sizes it for a
    whole *range* of row counts, so a plan compiled for one bucket stays
    valid for every extension that fits the bucket, and a steadily growing
    source crosses only O(log n) buckets — hence O(log n) recompiles — over
    its lifetime. This is the capacity quantization the ``KGEngine`` plan
    cache keys on (see ``docs/engine.md``).
    """
    cap = mult
    n = int(n)
    while cap < n:
        cap = round_cap(int(cap * growth), mult)
    return cap


def shrink_to_fit(table: "Table", mult: int = 8) -> "Table":
    """Materialize a table at capacity == round_cap(count) (host sync)."""
    n = host_int(table.count)
    cap = round_cap(n, mult)
    data = host_get(table.data)[:n]
    return Table.from_codes(data, table.attrs, cap)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Table:
    """Columnar relation: ``data[capacity, len(attrs)]`` int32 + valid count."""

    data: jax.Array          # [capacity, n_attrs] int32
    count: jax.Array         # scalar int32, number of valid rows
    attrs: Tuple[str, ...]   # static: column names, in column order

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.count), self.attrs

    @classmethod
    def tree_unflatten(cls, attrs, children):
        data, count = children
        return cls(data=data, count=count, attrs=attrs)

    # -- static properties ---------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    @property
    def n_attrs(self) -> int:
        return len(self.attrs)

    def col_index(self, attr: str) -> int:
        try:
            return self.attrs.index(attr)
        except ValueError:
            raise KeyError(f"attribute {attr!r} not in table {self.attrs}")

    def column(self, attr: str) -> jax.Array:
        return self.data[:, self.col_index(attr)]

    @property
    def valid_mask(self) -> jax.Array:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.count

    # -- constructors --------------------------------------------------------
    @classmethod
    def empty(cls, attrs: Sequence[str], capacity: int) -> "Table":
        data = jnp.full((capacity, len(attrs)), PAD_ID, dtype=jnp.int32)
        return cls(data=data, count=jnp.int32(0), attrs=tuple(attrs))

    @classmethod
    def from_codes(cls, codes: np.ndarray, attrs: Sequence[str],
                   capacity: int | None = None) -> "Table":
        """Build from an [n, k] int32 code matrix (host): padded to
        ``capacity`` on the host (span ``table.pad``), then transferred
        (span ``table.put``)."""
        with span("table.from_codes"):
            codes = np.asarray(codes, dtype=np.int32)
            n, k = codes.shape
            if k != len(attrs):
                raise ValueError("codes width != len(attrs)")
            capacity = n if capacity is None else capacity
            if n > capacity:
                raise ValueError(f"{n} rows exceed capacity {capacity}")
            with span("table.pad"):
                data = np.full((capacity, k), PAD_ID, dtype=np.int32)
                data[:n] = codes
            with span("table.put"):
                return cls(data=jnp.asarray(data), count=jnp.int32(n),
                           attrs=tuple(attrs))

    @classmethod
    def from_records(cls, records: Iterable[Mapping[str, object]],
                     attrs: Sequence[str], vocab: Vocab,
                     capacity: int | None = None) -> "Table":
        """Intern host records (list of dicts) into a device table."""
        with span("table.from_records"):
            rows: List[List[int]] = []
            for rec in records:
                rows.append([vocab.intern(rec[a]) for a in attrs])
            codes = (np.asarray(rows, dtype=np.int32)
                     if rows else np.zeros((0, len(attrs)), np.int32))
            return cls.from_codes(codes, attrs, capacity)

    # -- host-side views (tests / sinks only) ---------------------------------
    def to_codes(self) -> np.ndarray:
        n = host_int(self.count)
        return host_get(self.data)[:n]

    def to_records(self, vocab: Vocab) -> List[Dict[str, object]]:
        return [
            {a: vocab.decode(row[i]) for i, a in enumerate(self.attrs)}
            for row in self.to_codes()
        ]

    def row_set(self) -> set:
        """Set of valid rows as tuples — order-insensitive comparison."""
        return {tuple(int(x) for x in row) for row in self.to_codes()}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        traced = isinstance(self.count, jax.core.Tracer)
        count = "?" if traced else int(self.count)
        return (f"Table(attrs={self.attrs}, capacity={self.capacity}, "
                f"count={count})")
