"""What the system under test writes into a traced run's profile about
its own work: host spans of its phases and scopes of its plan operators.

The system writes a host span ``repro.<name>`` around each engine phase
(``repro.trace.span``) and names its device work with ``jax.named_scope``
per plan operator (``select``, ``distinct``, ``sink.union``, ...; inside
them ``compact``). A TPU op event carries the HLO instruction's text but
not its metadata, so the scope of an op is read from the HLO of its
program: the profile's ``/host:metadata`` plane holds an ``HloProto`` per
program the process has loaded, under the name of the ``XLA Modules``
events that enclose the program's ops on the device's line. :func:`extract` reads
both, with a small protobuf reader and JAX alone:

* ``program_spans``: ``[name, start_ns, duration_ns]`` of every host event
  named ``repro.*`` (the prefix dropped);
* ``modules``: per device, ``[module, start_ns, duration_ns]`` of each
  program run;
* ``scopes``: per module, ``{instruction: scope path}`` for every
  instruction of that module that ran in the trace; the path is the
  instruction's known scope names joined by ``/`` (``sink.union/compact``),
  or ``None`` where the HLO gives the instruction no scope.

An instruction takes the scopes in its own ``op_name``; without them, a
fusion takes its fused root's, and then any instruction takes the scope
of the instructions that consume its result, where they all agree. An op
whose program or instruction cannot be matched has no scope (``None``):
the reader never infers one from shapes or names of ops.

A program that writes no spans and no scopes (the system before it named
its work) gives empty ``program_spans`` and scope paths that are all
``None``; every reader below then returns ``None``.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from kgbench import devtrace

#: prefix of the system's own host spans (``repro.trace.PREFIX``)
PROGRAM_PREFIX = "repro."
#: the device line whose events are program runs
MODULE_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
#: the scopes the system puts on its device work: one per plan operator,
#: the closure's tail (``sink.*``), the mesh's exchange and global δ, and
#: the row compaction inside them
PLAN_SCOPES = ("select", "coleq", "project", "union", "distinct", "join",
               "emit", "sink.distinct_per_map", "sink.union",
               "sink.distinct", "exchange", "distinct_global")
SCOPES = PLAN_SCOPES + ("compact",)
#: the key of device time with no plan-operator scope
NO_SCOPE = "(no scope)"

Span = Tuple[str, float, float]


# -- protobuf wire format -----------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message; a
    length-delimited value is a ``memoryview`` of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield field, wire, value


def _ints(wire: int, value) -> List[int]:
    """A repeated integer field's values, packed or not."""
    if wire == 0:
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


# -- HLO ----------------------------------------------------------------------

def scope_path(op_name: str) -> Optional[str]:
    """The known scope names of an ``op_name`` (``jit(fn)/sink.union/
    compact/scatter`` -> ``sink.union/compact``); the last part is the
    operation's own name and is not a scope. A fused operation's
    ``op_name`` joins its parts' with ``;``: it takes the scopes they
    share from the outermost in."""
    paths = [[p for p in name.split("/")[:-1] if p in SCOPES]
             for name in op_name.split(";")]
    common = paths[0]
    for path in paths[1:]:
        n = 0
        while n < min(len(common), len(path)) and common[n] == path[n]:
            n += 1
        common = common[:n]
    return "/".join(common) or None


def _instructions(comp) -> Tuple[int, List[Dict]]:
    root, out = None, []
    for f, w, v in _fields(comp):
        if f == 6:
            root = v
        elif f == 2:
            ins = {"name": "", "op_name": "", "id": None, "operands": [],
                   "calls": []}
            for g, w2, x in _fields(v):
                if g == 1:
                    ins["name"] = _text(x)
                elif g == 7:
                    for h, _, y in _fields(x):
                        if h == 2:
                            ins["op_name"] = _text(y)
                elif g == 35:
                    ins["id"] = x
                elif g == 36:
                    ins["operands"] += _ints(w2, x)
                elif g == 38:
                    ins["calls"] += _ints(w2, x)
            out.append(ins)
    return root, out


def hlo_scopes(hlo_proto) -> Dict[str, Optional[str]]:
    """``{instruction name: scope path or None}`` of a serialized
    ``HloProto``, by the rules in the module's docstring."""
    comps: Dict[int, Tuple[int, List[Dict]]] = {}
    for f, _, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, _, comp in _fields(module):
            if g == 3:
                cid = next((v for h, _, v in _fields(comp) if h == 5), None)
                comps[cid] = _instructions(comp)
    by_id = {ins["id"]: ins for _, inss in comps.values() for ins in inss}
    users: Dict[int, List[int]] = {}
    for ins in by_id.values():
        for op in ins["operands"]:
            users.setdefault(op, []).append(ins["id"])
    own = {i: scope_path(ins["op_name"]) for i, ins in by_id.items()}
    for i, ins in by_id.items():          # a fusion without: its root's
        if own[i] is None and ins["calls"]:
            root = comps.get(ins["calls"][0], (None, []))[0]
            own[i] = own.get(root)

    done: Dict[int, Optional[str]] = {}
    visiting = set()

    def resolve(i: int) -> Optional[str]:
        stack = [i]
        while stack:                       # users first, without recursion
            j = stack[-1]
            if j in done:
                stack.pop()
            elif own[j] is not None or not users.get(j):
                done[j] = own[j]
                stack.pop()
            elif j not in visiting:
                visiting.add(j)
                stack.extend(u for u in users[j]
                             if u not in done and u not in visiting)
            else:                          # its users are resolved now
                paths = {done.get(u) for u in users[j]}
                done[j] = paths.pop() if len(paths) == 1 else None
                stack.pop()
        return done[i]

    return {ins["name"]: resolve(i) for i, ins in by_id.items()}


def module_hlos(xspace) -> Dict[str, object]:
    """``{module event name: serialized HloProto}`` of the metadata plane
    of a serialized ``XSpace``."""
    for f, _, plane in _fields(xspace):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if next((_text(v) for g, _, v in fields if g == 2), "") \
                != METADATA_PLANE:
            continue
        stat_ids = set()
        for g, _, v in fields:
            if g == 5:
                md = dict((h, x) for h, _, x in _fields(v)).get(2)
                kv = {h: x for h, _, x in _fields(md)} if md is not None \
                    else {}
                if _text(kv.get(2, b"")) == HLO_STAT:
                    stat_ids.add(kv.get(1))
        out = {}
        for g, _, v in fields:
            if g != 4:
                continue
            md = dict((h, x) for h, _, x in _fields(v)).get(2)
            if md is None:
                continue
            name, proto = None, None
            for h, _, x in _fields(md):
                if h == 2:
                    name = _text(x)
                elif h == 5:
                    stat = {k: y for k, _, y in _fields(x)}
                    if stat.get(1) in stat_ids and 6 in stat:
                        proto = stat[6]
            if name is not None and proto is not None:
                out[name] = proto
        return out
    return {}


# -- the trace ----------------------------------------------------------------

def _trace_file(path: str) -> str:
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one trace under {path}, "
                                f"found {len(files)}")
    return files[0]


def instruction(event_name: str) -> str:
    return devtrace.hlo_parts(event_name)[0]


def extract(path: str, base: Optional[Dict] = None) -> Dict[str, object]:
    """:func:`devtrace.extract` of the trace under ``path`` (or ``base``,
    if given) plus ``program_spans``, ``modules`` and ``scopes``."""
    import jax
    file = _trace_file(path)
    out = dict(devtrace.extract(path) if base is None else base)
    data = jax.profiler.ProfileData.from_file(file)
    spans: List[List] = []
    modules: Dict[str, List[List]] = {}
    for plane in data.planes:
        m = devtrace._DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == MODULE_LINE:
                modules.setdefault(m.group(1), []).extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events)
            elif not m:
                spans.extend([e.name[len(PROGRAM_PREFIX):], e.start_ns,
                              e.duration_ns] for e in line.events
                             if e.name.startswith(PROGRAM_PREFIX))
    ran: Dict[str, set] = {}
    for dev, evs in out["devices"].items():
        for ev, mod in zip(evs, module_of(modules.get(dev, []), evs)):
            if mod is not None:
                ran.setdefault(mod, set()).add(instruction(ev[0]))
    with open(file, "rb") as f:
        hlos = module_hlos(memoryview(f.read()))
    scopes = {}
    for mod, names in ran.items():
        if mod in hlos:
            table = hlo_scopes(hlos[mod])
            scopes[mod] = {n: table.get(n) for n in sorted(names)}
    out.update(program_spans=spans, modules=modules, scopes=scopes)
    return out


def module_of(modules: Sequence, events: Sequence) -> List[Optional[str]]:
    """For each op event, the name of the program run that encloses it on
    its device's line, or ``None``."""
    runs = sorted((s, s + d, name) for name, s, d in modules)
    starts = [r[0] for r in runs]
    out = []
    for ev in events:
        k = bisect.bisect_right(starts, ev[1]) - 1
        out.append(runs[k][2] if k >= 0 and ev[1] < runs[k][1] else None)
    return out


class Program:
    """The system's own spans and scopes in a traced window, beside the
    benchmark's :class:`devtrace.Profile` of the same trace."""

    def __init__(self, profile: devtrace.Profile, data: Dict) -> None:
        self.profile = profile
        self.spans: List[Span] = [tuple(s) for s in data.get(
            "program_spans", ())]
        self.scopes: Dict[str, Dict[str, Optional[str]]] = data.get(
            "scopes", {})
        self.paths: Dict[str, List[Optional[str]]] = {}
        for dev, evs in profile.devices.items():
            mods = module_of(data.get("modules", {}).get(dev, []), evs)
            self.paths[dev] = [
                self.scopes.get(mod, {}).get(instruction(ev[0]))
                if mod is not None else None
                for ev, mod in zip(evs, mods)]

    @property
    def scoped(self) -> bool:
        """Whether any device op has a scope."""
        return any(p is not None for ps in self.paths.values() for p in ps)

    # -- device time by scope ----------------------------------------------
    def _inside(self):
        lo, hi = self.profile.window
        for dev, evs in self.profile.devices.items():
            keep = [i for i, ev in enumerate(evs) if lo <= ev[1] < hi]
            yield [evs[i] for i in keep], [self.paths[dev][i] for i in keep]

    def scope_seconds(self, match) -> float:
        """Device seconds of the window's ops whose scope path ``match``
        accepts (union per device, so nested events count once), averaged
        over the devices."""
        lo, hi = self.profile.window
        per = []
        for evs, paths in self._inside():
            per.append(devtrace.union_length(
                [(ev[1], ev[1] + ev[2]) for ev, p in zip(evs, paths)
                 if p is not None and match(p)], lo, hi))
        return sum(per) / len(per) * 1e-9

    def device_by_scope(self, depth: int = 1) -> Dict[str, float]:
        """Device self time (s) of the window's ops, averaged over the
        devices, summed by the first ``depth`` names of their scope path;
        ops with none under :data:`NO_SCOPE`."""
        out: Dict[str, float] = {}
        n = len(self.profile.devices)
        for evs, paths in self._inside():
            for t, p in zip(devtrace.self_times(evs), paths):
                key = "/".join(p.split("/")[:depth]) if p else NO_SCOPE
                out[key] = out.get(key, 0.0) + t * 1e-9 / n
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    # -- device idle by span -----------------------------------------------
    def _segments(self) -> List[Tuple[float, float, str]]:
        """The window cut where any span starts or ends, each piece named
        by the innermost span over it (the latest started of those open):
        ``repro.<name>`` for the program's, ``kgbench.<name>`` for the
        benchmark's."""
        lo, hi = self.profile.window
        spans = ([("repro." + n, s, s + d) for n, s, d in self.spans]
                 + [("kgbench." + n, s, s + d)
                    for n, s, d in self.profile.spans])
        spans = [(n, max(s, lo), min(e, hi)) for n, s, e in spans
                 if e > lo and s < hi]
        marks = sorted({lo, hi} | {s for _, s, _ in spans}
                       | {e for _, _, e in spans})
        by_start = sorted(spans, key=lambda x: (x[1], -x[2]))
        out, open_, k = [], [], 0
        for a, b in zip(marks, marks[1:]):
            while k < len(by_start) and by_start[k][1] <= a:
                open_.append(by_start[k])
                k += 1
            open_ = [x for x in open_ if x[2] > a]
            if open_:
                out.append((a, b, open_[-1][0]))
        return out

    def idle_by_span(self, within: Optional[Sequence] = None
                     ) -> Dict[str, float]:
        """Device idle seconds of the window (or of its part inside the
        ``within`` intervals), averaged over the devices, summed by the
        innermost span over them."""
        lo, hi = self.profile.window
        out: Dict[str, float] = {}
        n = len(self.profile.devices)
        segments = self._segments()
        if within is not None:
            segments = _clip(segments, _merged(within, lo, hi))
        for dev in self.profile.devices:
            busy = _merged(self.profile._ops(dev), lo, hi)
            for (a, b, name), used in zip(segments,
                                          _overlaps(segments, busy)):
                idle = (b - a) - used
                if idle > 0:
                    out[name] = out.get(name, 0.0) + idle * 1e-9 / n
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def idle_inside(self, names: Sequence[str]) -> float:
        """Device idle seconds, averaged over the devices, inside the
        union of the program's spans called ``names``, in the window."""
        return sum(self.idle_by_span(within=[
            (s, s + d) for n, s, d in self.spans if n in names]).values())

    def longest_gaps(self, top: int = 5) -> List[Tuple[float, str]]:
        """The ``top`` longest device-idle gaps of the window (seconds),
        each with the spans open at its middle, outermost first, joined
        by ``>``."""
        lo, hi = self.profile.window
        spans = ([("repro." + n, s, s + d) for n, s, d in self.spans]
                 + [("kgbench." + n, s, s + d)
                    for n, s, d in self.profile.spans])
        out = []
        for dev in self.profile.devices:
            for a, b in devtrace.gaps(self.profile._ops(dev), lo, hi):
                out.append((b - a, (a + b) / 2))
        out.sort(reverse=True)
        return [(d * 1e-9, " > ".join(
                    n for n, s, e in sorted(spans, key=lambda x: (x[1], -x[2]))
                    if s <= t < e))
                for d, t in out[:top]]

    def count(self, name: str) -> int:
        """Program spans called ``name`` that start in the window."""
        lo, hi = self.profile.window
        return sum(1 for n, s, _ in self.spans if n == name and lo <= s < hi)


def _merged(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of ``[start, end)`` intervals inside ``[lo, hi)``, as
    sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(segments, cover) -> List[Tuple[float, float, str]]:
    """The parts of the sorted disjoint ``(start, end, name)`` segments
    inside the sorted disjoint ``cover`` intervals."""
    out, k = [], 0
    for a, b, name in segments:
        while k < len(cover) and cover[k][1] <= a:
            k += 1
        j = k
        while j < len(cover) and cover[j][0] < b:
            out.append((max(a, cover[j][0]), min(b, cover[j][1]), name))
            j += 1
    return out


def _overlaps(segments, busy) -> List[float]:
    """For each of the sorted disjoint ``(start, end, ...)`` segments, its
    overlap with the sorted disjoint ``busy`` intervals."""
    out, k = [], 0
    for seg in segments:
        a, b = seg[0], seg[1]
        while k < len(busy) and busy[k][1] <= a:
            k += 1
        used, j = 0.0, k
        while j < len(busy) and busy[j][0] < b:
            used += min(b, busy[j][1]) - max(a, busy[j][0])
            j += 1
        out.append(used)
    return out


def of(run) -> Optional[Program]:
    """The :class:`Program` of a traced run (read from its trace once, and
    kept on the run), or ``None`` for a run without a trace."""
    if getattr(run, "profile", None) is None:
        return None
    prog = getattr(run, "program", None)
    if prog is None:
        from kgbench.harness import TRACE_DIR
        base = {"devices": run.profile.devices, "spans": run.profile.spans}
        prog = Program(run.profile, extract(TRACE_DIR, base=base))
        run.program = prog
    return prog


def has_compact(path: str) -> bool:
    return "compact" in path.split("/")


# -- the per-layer metrics ------------------------------------------------------

def compact_share_pct(run) -> Optional[float]:
    """Device time of ops under a ``compact`` scope over busy time, %;
    ``None`` where no op of the window has a scope."""
    prog = of(run)
    if prog is None or not prog.scoped:
        return None
    return 100.0 * prog.scope_seconds(has_compact) / run.profile.busy_s


def engine_idle_ms(run, names: Sequence[str], per: str) -> Optional[float]:
    """Device idle inside the program's spans ``names`` over the window,
    per program span ``per`` that starts in the window, ms; ``None``
    where the window holds no such span."""
    prog = of(run)
    n = None if prog is None else prog.count(per)
    if not n:
        return None
    return 1e3 * prog.idle_inside(names) / n
