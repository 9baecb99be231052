"""The persistent plan store: an AOT-serialized second tier behind the LRU.

:data:`repro.api.cache.PLAN_CACHE` amortizes compilation *within* one
process; a restarting fleet pays the cold cost (~seconds — see
``experiments/bench/engine.json``) per worker × per DIS shape. The store
makes the amortization survive the process: on an LRU miss the
:class:`~repro.api.KGEngine` consults an on-disk store of AOT-compiled
closures, and on a compile (including overflow-ladder recompiles) it
writes back — so a fresh process with a populated store rehydrates a
ready-to-run executable without re-tracing or re-compiling
(``check_warm_process_cold_start`` in ``benchmarks/engine.py`` gates the
speedup at ≥10×).

**Key.** ``store_key(session_key, envelope)`` = sha256 over

* the engine's in-process plan-cache key (structural IR fingerprint ×
  emitter codes × engine × dedup × annotate mode/slack × mesh signature ×
  capacity-bucket signature), canonicalized by :func:`canonical` — which
  *rejects* anything but ``None``/``bool``/``int``/``float``/``str``/
  ``tuple``, so an ``id()``, an unsorted dict, or any other
  process-unstable value can never silently leak into the key (the
  hypothesis suite in ``tests/test_engine_properties.py`` leans on this);
* the **compatibility envelope** (:func:`store_envelope`): store format
  version, jax/jaxlib versions, XLA backend, device kind and count — the
  runtime facts a serialized executable is only valid under. Two
  processes produce the same key iff their in-process keys AND runtimes
  match.

**Entry format** (version :data:`FORMAT_VERSION`, one file per key)::

    MAGIC(8) | header_len u32 LE | sha256(header)(32) | header JSON | payloads

The header carries the envelope (validated for *equality* on load — a
matching filename with a mismatched envelope is rejected), the
node-indexed plan metadata (capacities/counts/⋈ exchanges, keyed by
:func:`repro.plan.ir.node_order` indices so they rehydrate against a
freshly lowered plan), and per-payload sizes + sha256 checksums (what
turns truncation and bit flips into clean rejections). Two payloads:

* ``native`` — the XLA executable via
  :mod:`jax.experimental.serialize_executable` (plus its pickled
  in/out treedefs). Zero-recompile rehydration: the fast tier.
* ``stablehlo`` — the ``jax.export`` blob. Portable within the envelope;
  the fallback when the native payload fails to load (it re-compiles the
  StableHLO, still skipping planning + tracing).

**Failure discipline.** Every load failure — missing file, bad magic,
truncated bytes, checksum mismatch, envelope mismatch, deserialization
error — degrades to a fresh compile and bumps a reject counter
(``stats()['rejects']``; mirrored as ``store_rejects`` on the engine).
Writes go to a temp file in the same directory and ``os.replace`` into
place under a per-entry advisory ``flock``, so a concurrent reader never
observes a torn entry and concurrent writers never interleave; a busy
lock or an unwritable directory skips the write (counted), never raises.

CLI (the CI plan-store leg's step 1)::

    PYTHONPATH=src python -m repro.api.store populate --root /tmp/plan-store
    PYTHONPATH=src python -m repro.api.store ls --root /tmp/plan-store
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import struct
import tempfile
from typing import Dict, List, Mapping, Optional, Tuple

import jax

MAGIC = b"RPLNSTR1"
# v2: exchange records carry the cost-model provenance (``cost_source``)
# and the envelope may carry a collective-bandwidth calibration tag, so
# plans costed under measured link speeds never collide with static ones.
# v3: the native payload records the executable's device ids.
FORMAT_VERSION = 3

#: payload names inside an entry container
NATIVE, STABLEHLO = "native", "stablehlo"


def default_store_root() -> str:
    """``$REPRO_PLAN_STORE`` if set, else ``~/.cache/repro-plans``."""
    env = os.environ.get("REPRO_PLAN_STORE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro-plans")


# ---------------------------------------------------------------------------
# key canonicalization + envelope
# ---------------------------------------------------------------------------

def canonical(obj) -> str:
    """Deterministic, process-stable encoding of a plan-cache key.

    Only ``None``/``bool``/``int``/``float``/``str``/``tuple`` are
    admitted — these repr identically in every process. Anything else
    (an object whose repr embeds ``id()``, a dict whose iteration order
    depends on insertion, a device array) raises ``TypeError`` instead of
    silently producing a key that only this process can reproduce.
    """
    if obj is None or isinstance(obj, (bool, int, str)):
        return repr(obj)
    if isinstance(obj, float):
        return repr(obj)  # shortest-repr is deterministic in CPython 3
    if isinstance(obj, tuple):
        return "(" + ",".join(canonical(x) for x in obj) + ")"
    raise TypeError(
        f"plan-store keys must be built from None/bool/int/float/str/tuple; "
        f"got {type(obj).__name__} — a process-unstable component would "
        f"make the key irreproducible across workers")


def store_envelope(calibration=None) -> Dict[str, object]:
    """The runtime facts a serialized executable is only valid under.

    ``calibration`` (a :class:`repro.launch.mesh.Calibration` or None)
    tags the envelope with the cost model's bandwidth provenance: a plan
    whose exchange strategies were chosen under measured link speeds must
    not rehydrate into a session costing with the static constants (or
    with a materially different measurement) — calibration drift is an
    envelope mismatch, rejected on load like any other runtime mismatch.
    """
    import jaxlib
    devices = jax.devices()
    env = {
        "format": FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind,
        "device_count": jax.device_count(),
        "calibration": "static",
    }
    if calibration is not None and calibration.source != "static":
        env["calibration"] = canonical(calibration.signature())
    return env


def _envelope_json(envelope: Mapping[str, object]) -> str:
    return json.dumps(dict(envelope), sort_keys=True, separators=(",", ":"))


def store_key(session_key: Tuple, envelope: Mapping[str, object]) -> str:
    """sha256 hex of the canonicalized in-process key × the envelope."""
    blob = canonical(session_key) + "\n" + _envelope_json(envelope)
    return hashlib.sha256(blob.encode()).hexdigest()


# ---------------------------------------------------------------------------
# container read/write (module-level so tests can tamper surgically)
# ---------------------------------------------------------------------------

def write_container(path: str, header: Dict[str, object],
                    payloads: Mapping[str, bytes]) -> None:
    """Serialize one entry (non-atomic — callers go through
    :meth:`PlanStore.save` for the temp+rename+lock discipline)."""
    names = sorted(payloads)
    header = dict(header)
    header["payloads"] = [{"name": n, "size": len(payloads[n]),
                           "sha256": hashlib.sha256(payloads[n]).hexdigest()}
                          for n in names]
    hjson = json.dumps(header, sort_keys=True).encode()
    with open(path, "wb") as f:
        f.write(MAGIC)
        f.write(struct.pack("<I", len(hjson)))
        f.write(hashlib.sha256(hjson).digest())
        f.write(hjson)
        for n in names:
            f.write(payloads[n])
        f.flush()
        os.fsync(f.fileno())


def read_container(path: str) -> Tuple[Dict[str, object], Dict[str, bytes]]:
    """Parse + integrity-check one entry; raises ``ValueError``/``OSError``
    on any corruption (bad magic, truncation, checksum mismatch)."""
    with open(path, "rb") as f:
        blob = f.read()
    if blob[:len(MAGIC)] != MAGIC:
        raise ValueError("bad magic")
    off = len(MAGIC)
    if len(blob) < off + 36:
        raise ValueError("truncated header")
    (hlen,) = struct.unpack("<I", blob[off:off + 4])
    off += 4
    hdigest, off = blob[off:off + 32], off + 32
    hjson = blob[off:off + hlen]
    if len(hjson) != hlen or hashlib.sha256(hjson).digest() != hdigest:
        raise ValueError("header checksum mismatch")
    header = json.loads(hjson.decode())
    off += hlen
    payloads: Dict[str, bytes] = {}
    for spec in header.get("payloads", []):
        data = blob[off:off + int(spec["size"])]
        if len(data) != int(spec["size"]):
            raise ValueError(f"truncated payload {spec['name']!r}")
        if hashlib.sha256(data).hexdigest() != spec["sha256"]:
            raise ValueError(f"payload checksum mismatch {spec['name']!r}")
        payloads[spec["name"]] = data
        off += int(spec["size"])
    return header, payloads


# ---------------------------------------------------------------------------
# node-indexed entry metadata (caps/counts/exchanges survive the process)
# ---------------------------------------------------------------------------

def pack_entry_meta(entry, plan) -> Dict[str, object]:
    """Serialize a :class:`~repro.api.cache.CachedPlan`'s node-keyed
    metadata as :func:`repro.plan.ir.node_order` index lists (the order is
    fingerprint-stable, so a same-key process maps indices back onto its
    own freshly lowered nodes)."""
    from repro.plan.ir import node_order
    index = {n: i for i, n in enumerate(node_order(plan.emits()))}
    meta: Dict[str, object] = {
        "node_count": len(index),
        "engine": entry.engine,
        "dedup": entry.dedup,
        "mode": entry.mode,
        "build_seconds": entry.build_seconds,
        "counts": sorted([index[n], int(v)]
                         for n, v in entry.counts.items()),
        "caps": sorted([index[n], int(v)] for n, v in entry.caps.items()),
    }
    if entry.cap_locals is not None:      # mesh entry: shard layout
        meta["cap_locals"] = {k: int(v)
                              for k, v in sorted(entry.cap_locals.items())}
        meta["out_cap_local"] = int(entry.out_cap_local)
        meta["sink_slack"] = float(entry.sink_slack)
        meta["safe_exchange"] = bool(entry.safe_exchange)
        meta["exchanges"] = sorted(
            [index[n], x.strategy, int(x.gather_bytes),
             int(x.repartition_bytes), float(x.gather_seconds),
             float(x.repartition_seconds),
             getattr(x, "cost_source", "static"),
             int(getattr(x, "parent_fanout", 1))]
            for n, x in (entry.exchanges or {}).items())
    return meta


def unpack_entry_meta(meta: Mapping[str, object], plan) -> Dict[str, object]:
    """Rebuild node-keyed dicts against *this* process's plan nodes;
    raises ``ValueError`` when the stored indices do not fit the local
    plan (a corrupted or key-colliding entry must reject, not mis-map)."""
    from repro.plan.annotate import JoinExchange
    from repro.plan.ir import node_order
    order = node_order(plan.emits())
    if int(meta["node_count"]) != len(order):
        raise ValueError("stored node metadata does not match the plan "
                         f"({meta['node_count']} nodes vs {len(order)})")
    out: Dict[str, object] = {
        "counts": {order[i]: int(v) for i, v in meta["counts"]},
        "caps": {order[i]: int(v) for i, v in meta["caps"]},
        "mode": meta["mode"],
        "build_seconds": float(meta["build_seconds"]),
    }
    if "cap_locals" in meta:
        out["cap_locals"] = {str(k): int(v)
                             for k, v in sorted(meta["cap_locals"].items())}
        out["out_cap_local"] = int(meta["out_cap_local"])
        out["sink_slack"] = float(meta["sink_slack"])
        out["safe_exchange"] = bool(meta["safe_exchange"])
        # pre-fanout entries carry 7 fields; parent_fanout defaults to 1
        # (same format version — the amortization changed pricing, not the
        # envelope)
        out["exchanges"] = {
            order[i]: JoinExchange(strategy=s, gather_bytes=int(gb),
                                   repartition_bytes=int(rb),
                                   gather_seconds=float(gs),
                                   repartition_seconds=float(rs),
                                   cost_source=str(src),
                                   parent_fanout=int(rest[0]) if rest else 1)
            for i, s, gb, rb, gs, rs, src, *rest
            in meta.get("exchanges", [])}
    return out


# ---------------------------------------------------------------------------
# AOT payload (de)serialization
# ---------------------------------------------------------------------------

_export_registered = False


def _register_export_types() -> None:
    """Teach ``jax.export`` to serialize the :class:`repro.relalg.Table`
    pytrees crossing the closure boundary (idempotent)."""
    global _export_registered
    if _export_registered:
        return
    from jax import export
    from repro.relalg import Table
    try:
        export.register_pytree_node_serialization(
            Table, serialized_name="repro.relalg.Table",
            serialize_auxdata=lambda attrs: json.dumps(list(attrs)).encode(),
            deserialize_auxdata=lambda b: tuple(json.loads(b.decode())))
    except ValueError:   # another caller registered it first — fine
        pass
    _export_registered = True


def compile_for_store(fn_jit, abstract_args: Tuple):
    """AOT-compile the closure whose executable the store will serialize.

    On XLA:CPU, JAX's persistent compilation cache is bypassed for this one
    compile: an executable the cache hands back there serializes without
    the functions it calls, and fails when loaded again ("Function ... not
    found"). The store is itself the persistent tier for these programs.
    """
    from jax._src import config as jax_config
    from jax.experimental.compilation_cache import compilation_cache
    lowered = fn_jit.lower(*abstract_args)
    if jax.default_backend() != "cpu":
        return lowered.compile()
    # whether the cache is in use is memoized per process: reset around
    # the toggle so the compile sees it off and later ones see it back on
    with jax_config.enable_compilation_cache(False):
        compilation_cache.reset_cache()
        try:
            return lowered.compile()
        finally:
            compilation_cache.reset_cache()


def serialize_native(compiled) -> bytes:
    """Pickle the AOT-compiled executable with its calling convention
    (:mod:`jax.experimental.serialize_executable` + the in/out treedefs)
    and the ids of the devices it was compiled for, in order."""
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree = se.serialize(compiled)
    device_ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return pickle.dumps((payload, in_tree, out_tree, device_ids),
                        protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_native(blob: bytes):
    """Load a :func:`serialize_native` payload back into a callable with
    the original positional calling convention (zero recompilation).

    The executable is loaded onto the devices it was compiled for: left
    to its default, ``deserialize_and_load`` spreads it over every visible
    device, and a one-device plan in a many-device process then expects
    one argument shard per device."""
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree, device_ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


def serialize_stablehlo(fn_jit, abstract_args: Tuple) -> bytes:
    """``jax.export`` the jitted closure traced over abstract inputs —
    the portable tier (StableHLO; re-compiled on load)."""
    from jax import export
    _register_export_types()
    return export.export(fn_jit)(*abstract_args).serialize()


def deserialize_stablehlo(blob: bytes):
    """Rehydrate the portable tier: the StableHLO module wrapped back in
    ``jax.jit`` (XLA re-compiles it on first call — slower than the
    native tier but still skips planning and tracing)."""
    from jax import export
    _register_export_types()
    return jax.jit(export.deserialize(blob).call)


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LoadResult:
    """Outcome of one :meth:`PlanStore.load`: ``status`` is ``"hit"``
    (header+payloads returned), ``"miss"`` (no entry) or ``"reject"``
    (an entry exists but failed validation — ``reason`` says why)."""

    status: str
    header: Optional[Dict[str, object]] = None
    payloads: Optional[Dict[str, bytes]] = None
    reason: Optional[str] = None


class PlanStore:
    """Disk-backed tier of the plan cache: one entry file per store key.

    ``portable=False`` skips writing the ``stablehlo`` payload (faster
    write-back, native-tier-only entries). ``max_entries`` prunes the
    oldest entries (by mtime) after each save.
    """

    def __init__(self, root: Optional[str] = None, *, portable: bool = True,
                 max_entries: Optional[int] = None):
        self.root = os.path.abspath(root or default_store_root())
        self.portable = portable
        self.max_entries = max_entries
        self.hits = 0
        self.misses = 0
        self.rejects = 0
        self.writes = 0
        self.write_errors = 0
        self.write_skipped = 0
        self.reject_reasons: List[str] = []   # bounded diagnostic ring

    # -- paths ---------------------------------------------------------------
    def entry_path(self, key: str) -> str:
        return os.path.join(self.root, f"{key}.plan")

    def _reject(self, reason: str) -> LoadResult:
        self.rejects += 1
        self.reject_reasons.append(reason)
        del self.reject_reasons[:-16]
        return LoadResult(status="reject", reason=reason)

    # -- read ----------------------------------------------------------------
    def load(self, key: str,
             envelope: Mapping[str, object]) -> LoadResult:
        """Validated read of one entry. NEVER raises: every failure mode
        (missing file, corrupt container, envelope mismatch) returns a
        ``miss``/``reject`` result and the caller compiles fresh."""
        path = self.entry_path(key)
        try:
            if not os.path.exists(path):
                self.misses += 1
                return LoadResult(status="miss")
            header, payloads = read_container(path)
            if header.get("envelope") != dict(envelope):
                return self._reject("envelope mismatch")
            if header.get("key") != key:
                return self._reject("key mismatch")
            self.hits += 1
            return LoadResult(status="hit", header=header, payloads=payloads)
        except Exception as e:   # corrupt bytes must degrade, not crash
            return self._reject(f"{type(e).__name__}: {e}")

    # -- write ---------------------------------------------------------------
    def save(self, key: str, envelope: Mapping[str, object],
             meta: Mapping[str, object],
             payloads: Mapping[str, bytes]) -> bool:
        """Atomic write-back: temp file + ``os.replace`` under a per-entry
        advisory ``flock``. A busy lock (another writer is mid-flight on
        the same entry) skips; any OS error (read-only store, full disk)
        is swallowed and counted. Returns True iff the entry landed."""
        path = self.entry_path(key)
        lock_path = path + ".lock"
        tmp_path = None
        lock_fd = None
        try:
            os.makedirs(self.root, exist_ok=True)
            lock_fd = os.open(lock_path, os.O_CREAT | os.O_RDWR, 0o644)
            try:
                import fcntl
                fcntl.flock(lock_fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except (ImportError, BlockingIOError, PermissionError):
                self.write_skipped += 1
                return False
            fd, tmp_path = tempfile.mkstemp(dir=self.root,
                                            prefix=f".{key[:16]}.tmp.")
            os.close(fd)
            header = {"version": FORMAT_VERSION, "key": key,
                      "envelope": dict(envelope), "meta": dict(meta)}
            write_container(tmp_path, header, payloads)
            os.replace(tmp_path, path)   # readers see old or new, never torn
            tmp_path = None
            self.writes += 1
            if self.max_entries is not None:
                self._prune()
            return True
        except OSError:
            self.write_errors += 1
            return False
        finally:
            if tmp_path is not None:
                try:
                    os.unlink(tmp_path)
                except OSError:
                    pass
            if lock_fd is not None:
                os.close(lock_fd)   # closing drops the flock

    def _prune(self) -> None:
        """Drop the oldest entries beyond ``max_entries`` — tolerant of
        concurrent stores (the serving norm): an entry vanishing or being
        replaced between the listing and the mtime read is skipped and
        counted under ``write_errors`` (the store's NEVER-raises contract
        covers pruning too), and the unlink itself is missing-ok."""
        stamped = []
        for path in self._entry_files():
            try:
                stamped.append((os.path.getmtime(path), path))
            except OSError:      # pruned/replaced behind our back
                self.write_errors += 1
        stamped.sort()
        for _, path in stamped[:max(0, len(stamped) - self.max_entries)]:
            try:
                os.unlink(path)
            except FileNotFoundError:   # a concurrent pruner won the race
                pass
            except OSError:
                self.write_errors += 1

    # -- introspection -------------------------------------------------------
    def _entry_files(self) -> List[str]:
        try:
            return [os.path.join(self.root, f) for f in os.listdir(self.root)
                    if f.endswith(".plan")]
        except OSError:
            return []

    def __len__(self) -> int:
        return len(self._entry_files())

    def stats(self) -> Dict[str, object]:
        files = self._entry_files()
        size = 0
        for p in files:     # same listing/stat race discipline as _prune
            try:
                size += os.path.getsize(p)
            except OSError:
                pass
        return {"root": self.root, "entries": len(files),
                "bytes": size,
                "hits": self.hits, "misses": self.misses,
                "rejects": self.rejects, "writes": self.writes,
                "write_errors": self.write_errors,
                "write_skipped": self.write_skipped}

    def clear(self) -> None:
        for path in self._entry_files():
            try:
                os.unlink(path)
            except OSError:
                pass


def resolve_store(plan_store) -> Optional[PlanStore]:
    """Normalize the ``KGEngine(plan_store=...)`` argument:

    * ``None``/``False`` — store disabled (the in-process LRU only);
    * ``True`` or ``"default"`` — :func:`default_store_root`
      (``$REPRO_PLAN_STORE`` or ``~/.cache/repro-plans``);
    * a path — a :class:`PlanStore` rooted there;
    * a :class:`PlanStore` — used as-is (sessions may share one).
    """
    if plan_store is None or plan_store is False:
        return None
    if isinstance(plan_store, PlanStore):
        return plan_store
    if plan_store is True or plan_store == "default":
        return PlanStore(default_store_root())
    if isinstance(plan_store, (str, os.PathLike)):
        return PlanStore(os.fspath(plan_store))
    raise TypeError(f"plan_store must be None, True, 'default', a path or "
                    f"a PlanStore; got {type(plan_store).__name__}")


# ---------------------------------------------------------------------------
# CLI — the CI plan-store leg's populate step
# ---------------------------------------------------------------------------

def _populate(root: str, n_rows: int) -> int:
    """Compile the standard smoke configurations into ``root`` (every
    engine × dedup, plus a fused-mesh session over all visible devices) —
    a separate process then runs the tier-1 plan-store tests against the
    populated store."""
    from repro.api.config import EngineConfig
    from repro.api.engine import KGEngine
    from repro.api.store import PlanStore as _PlanStore   # NOT the
    # ``__main__`` alias of this class: under ``python -m repro.api.store``
    # the module exists twice, and the engine isinstance-checks against
    # the canonically imported one
    from repro.data.synthetic import make_group_b_dis
    from repro.launch.mesh import make_mesh
    store = _PlanStore(root)
    for engine in ("rmlmapper", "sdm"):
        for dedup in ("lex", "hash"):
            session = KGEngine(make_group_b_dis(n_rows, 0.6, seed=0),
                               config=EngineConfig(engine=engine,
                                                   dedup=dedup,
                                                   plan_store=store))
            session.create_kg()
    mesh = make_mesh((jax.device_count(),), ("data",))
    session = KGEngine(make_group_b_dis(n_rows, 0.6, seed=0),
                       config=EngineConfig(engine="sdm", dedup="hash",
                                           mesh=mesh, plan_store=store))
    session.create_kg()
    print(json.dumps(store.stats(), indent=1))
    return 0 if store.writes > 0 and store.write_errors == 0 else 1


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python -m repro.api.store")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("populate", help="compile smoke configs into a store")
    p.add_argument("--root", default=None)
    p.add_argument("--rows", type=int, default=48)
    p = sub.add_parser("ls", help="list store entries")
    p.add_argument("--root", default=None)
    p = sub.add_parser("clear", help="delete every entry")
    p.add_argument("--root", default=None)
    args = ap.parse_args(argv)
    root = args.root or default_store_root()
    if args.cmd == "populate":
        return _populate(root, args.rows)
    store = PlanStore(root)
    if args.cmd == "clear":
        store.clear()
    for path in sorted(store._entry_files()):
        try:
            header, payloads = read_container(path)
            print(f"{os.path.basename(path)}  "
                  f"{os.path.getsize(path)}B  "
                  f"payloads={sorted(payloads)}  "
                  f"jax={header['envelope']['jax']}  "
                  f"devices={header['envelope']['device_count']}")
        except Exception as e:
            print(f"{os.path.basename(path)}  INVALID ({e})")
    print(json.dumps(store.stats(), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
