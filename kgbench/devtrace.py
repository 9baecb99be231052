"""The profiler trace of a traced run, reduced to what the metrics need.

A traced run records its window with ``jax.profiler`` into
``.xplane.pb``. :func:`extract` reads it with JAX alone into plain
lists: per TPU device the events of its op line (each an XLA operation
that ran on the chip, with a start and a duration in nanoseconds), and
the benchmark's host spans (``kgbench.<name>``), on one clock.

From those, :class:`Profile` gives

* ``window_s``: the traced window, the host span ``window``;
* ``busy_s``: per device the union of its op intervals inside the window,
  averaged over the devices; the idle share is ``1 - busy_s/window_s``;
* the device time of ops of one kind (sorts, a kernel), averaged over the
  devices;
* the breakdown: the ops that took most device time, and the longest idle
  gaps, each named by the innermost benchmark span it falls in.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from kgbench.spans import PREFIX

#: the line of a TPU device plane whose events are single XLA operations
OP_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: one op event: (name, start_ns, duration_ns); a TPU op event's name is
#: its whole HLO instruction text
Event = Tuple[str, float, float]


class Tracer:
    """``jax.profiler`` around the window: Python tracing off, host
    tracing at level 1 (annotations such as the benchmark's spans, not the
    runtime's transfer threads, which write millions of events)."""

    def __init__(self, path: str) -> None:
        self.path = path

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.path, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()


def hlo_parts(text: str) -> Tuple[str, str, str, str]:
    """``(instruction, result shape, opcode, operands)`` of an HLO
    instruction's text (``%name = shape opcode(operands), attrs``); an
    event whose name is no instruction gives ``(text, "", "", "")``."""
    m = re.match(r"\s*%?([\w.\-]+) = ", text)
    if not m:
        return text, "", "", ""
    rest = text[m.end():]
    depth, i = 0, 0
    for i, ch in enumerate(rest):      # the shape ends at a top-level space
        depth += ch == "("
        depth -= ch == ")"
        if ch == " " and depth == 0:
            break
    shape, rest = rest[:i], rest[i + 1:]
    op = re.match(r"([\w\-]+)\(", rest)
    if not op:
        return m.group(1), shape, "", ""
    depth, j = 1, op.end()
    while j < len(rest) and depth:
        depth += rest[j] == "("
        depth -= rest[j] == ")"
        j += 1
    return m.group(1), shape, op.group(1), rest[op.end():j - 1]


def short_name(text: str) -> str:
    """An op's name for the breakdown: instruction, opcode, result shape
    without layouts."""
    inst, shape, opcode, _ = hlo_parts(text)
    shape = re.sub(r"\{[^{}]*\}", "", shape)
    return " ".join(x for x in (inst, opcode, shape) if x)[:120]


def self_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration less that of the events nested in it (a
    conditional or a loop holds the ops of its body on the same line)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    out = [float(e[2]) for e in events]
    stack: List[int] = []
    for i in order:
        s, e = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1] + events[stack[-1]][2]:
            out[stack[-1]] -= events[i][2]
        stack.append(i)
    return out


def extract(path: str) -> Dict[str, object]:
    """The trace file under ``path`` as ``{"devices": {id: [Event]},
    "spans": [(name, start_ns, duration_ns)]}``."""
    import jax
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one trace under {path}, "
                                f"found {len(files)}")
    data = jax.profiler.ProfileData.from_file(files[0])
    devices: Dict[str, List[Event]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OP_LINE:
                evs = devices.setdefault(m.group(1), [])
                evs.extend((e.name, e.start_ns, e.duration_ns)
                           for e in line.events)
            elif not m:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name[len(PREFIX):], e.start_ns,
                                      e.duration_ns))
    return {"devices": devices, "spans": spans}


def union_length(intervals: Sequence[Tuple[float, float]], lo: float,
                 hi: float) -> float:
    """Total length of the union of ``[start, end)`` intervals, clipped to
    ``[lo, hi)``."""
    total, reach = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, reach), min(e, hi)
        if e > s:
            total += e - s
            reach = e
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi)`` that no interval covers."""
    out, reach = [], lo
    for s, e in sorted(intervals):
        if s > reach:
            out.append((reach, min(s, hi)))
        reach = max(reach, e)
        if reach >= hi:
            break
    if reach < hi:
        out.append((reach, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Profile:
    devices: Dict[str, List[Event]]
    spans: List[Tuple[str, float, float]]
    n_devices: int

    @classmethod
    def load(cls, path: str, n_devices: int) -> "Profile":
        return cls.from_extract(extract(path), n_devices)

    @classmethod
    def from_extract(cls, data: Dict, n_devices: int) -> "Profile":
        devices = {str(k): [tuple(e[:3]) for e in v]
                   for k, v in data["devices"].items()}
        if len(devices) < n_devices:
            raise ValueError(f"the trace holds ops of {len(devices)} "
                             f"devices, the run used {n_devices}")
        spans = [tuple(s) for s in data["spans"]]
        return cls(devices=devices, spans=spans, n_devices=n_devices)

    # -- the window ----------------------------------------------------------
    @property
    def window(self) -> Tuple[float, float]:
        wins = [(s, s + d) for n, s, d in self.spans if n == "window"]
        if len(wins) != 1:
            raise ValueError(f"expected one window span, found {len(wins)}")
        return wins[0]

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def _ops(self, dev: str) -> List[Tuple[float, float]]:
        return [(s, s + d) for _, s, d in self.devices[dev]]

    @property
    def busy_s(self) -> float:
        lo, hi = self.window
        per = [union_length(self._ops(dev), lo, hi) for dev in self.devices]
        return sum(per) / len(per) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, match: Callable[[Event], bool]) -> float:
        """Device seconds of the ops ``match`` selects inside the window
        (union per device, so nested events count once), averaged over
        the devices."""
        lo, hi = self.window
        per = [union_length([(ev[1], ev[1] + ev[2]) for ev in evs
                             if match(ev)], lo, hi)
               for evs in self.devices.values()]
        return sum(per) / len(per) * 1e-9

    def events(self, match: Callable[[Event], bool]) -> List[Event]:
        lo, hi = self.window
        return [ev for evs in self.devices.values() for ev in evs
                if match(ev) and lo <= ev[1] < hi]

    # -- breakdown -----------------------------------------------------------
    def label(self, t: float) -> str:
        """The innermost benchmark span around time ``t``."""
        best: Optional[Tuple[float, str]] = None
        for name, s, d in self.spans:
            if s <= t < s + d and (best is None or d < best[0]):
                best = (d, name)
        return best[1] if best else "outside spans"

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        """The ops that took most device time (self time, summed over
        their runs, averaged over the devices), and the longest idle
        gaps named by the innermost benchmark span around them."""
        lo, hi = self.window
        by_op: Dict[str, float] = {}
        for evs in self.devices.values():
            inside = [ev for ev in evs if lo <= ev[1] < hi]
            for ev, t in zip(inside, self_times(inside)):
                name = short_name(ev[0])
                by_op[name] = by_op.get(name, 0.0) + t
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        n = len(self.devices)
        idle = []
        for dev in self.devices:
            for s, e in gaps(self._ops(dev), lo, hi):
                idle.append((e - s, self.label((s + e) / 2)))
        idle.sort(key=lambda x: -x[0])
        return {"device_ops": [[k, v / n * 1e-9] for k, v in ops],
                "idle_gaps": [[lbl, d * 1e-9] for d, lbl in idle[:top]]}
