"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy intervals and idle share, device time per XLA
program, kernel events, and host spans to label idle gaps.

Read with ``jax.profiler.ProfileData`` only.  On a TPU the device planes
are named ``/device:TPU:<n>``; their ``XLA Modules`` line holds one event
per program execution (named ``<jit name>(<id>)``) and their ``XLA Ops``
line one event per operation, a Pallas kernel among them under its kernel
name.  Host planes (``/host:...``) hold the threads' TraceMe spans, the
benchmark's own ``bench.*`` annotations among them.  All times here are
seconds on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

Interval = Tuple[float, float]

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    """What one trace holds, per device, on one clock (seconds)."""
    ops: Dict[int, List[Event]]          # device -> XLA op events
    modules: Dict[int, List[Event]]      # device -> program executions
    host: List[Event]                    # host spans, all threads
    marks: Dict[str, float]              # bench.mark.<name> -> start time

    @property
    def devices(self) -> List[int]:
        return sorted(set(self.ops) | set(self.modules))

    def window(self, t0: float, t1: float) -> "Trace":
        """Events clipped to [t0, t1]."""
        def clip(evs):
            out = []
            for e in evs:
                s, t = max(e.start, t0), min(e.end, t1)
                if t > s:
                    out.append(Event(e.name, s, t))
            return out
        return Trace({d: clip(v) for d, v in self.ops.items()},
                     {d: clip(v) for d, v in self.modules.items()},
                     clip(self.host), dict(self.marks))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    marks: Dict[str, float] = {}
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            evs = [Event(e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                   for e in line.events]
            if m:
                dev = int(m.group(1))
                if line.name == "XLA Ops":
                    ops.setdefault(dev, []).extend(evs)
                elif line.name == "XLA Modules":
                    modules.setdefault(dev, []).extend(evs)
            elif plane.name.startswith("/host:"):
                for e in evs:
                    if e.name.startswith("bench.mark."):
                        marks[e.name[len("bench.mark."):]] = e.start
                host.extend(evs)
    for d in ops:
        ops[d].sort(key=lambda e: e.start)
    for d in modules:
        modules[d].sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Trace(ops, modules, host, marks)


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_intervals(tr: Trace, device: int) -> List[Interval]:
    """When an operation ran on ``device``: the union of its op events
    (of its program executions where the trace has no op line)."""
    evs = tr.ops.get(device) or tr.modules.get(device) or []
    return union([(e.start, e.end) for e in evs if e.end > e.start])


def busy_seconds(tr: Trace, device: int) -> float:
    return sum(t - s for s, t in busy_intervals(tr, device))


def idle_gaps(tr: Trace, device: int, t0: float, t1: float
              ) -> List[Interval]:
    """Stretches of [t0, t1] in which nothing ran on ``device``."""
    gaps, cur = [], t0
    for s, t in busy_intervals(tr, device):
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, t)
    if cur < t1:
        gaps.append((cur, t1))
    return [(s, t) for s, t in gaps if t > s]


def program_name(module_event_name: str) -> str:
    """``jit__generate_impl(42)`` -> ``jit__generate_impl``."""
    return _MODULE_ID.sub("", module_event_name)


def program_time(tr: Trace, device: int, match) -> Tuple[float, int]:
    """(device seconds, executions) of the programs whose name ``match``
    accepts."""
    evs = [e for e in tr.modules.get(device, [])
           if match(program_name(e.name))]
    return sum(e.dur for e in evs), len(evs)


def op_time(tr: Trace, device: int, match) -> Tuple[float, int]:
    """(device seconds, events) of the ops whose name ``match`` accepts."""
    evs = [e for e in tr.ops.get(device, []) if match(e.name)]
    return sum(e.dur for e in evs), len(evs)


_OP_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?(?:\s*=.*)?$", re.S)
_CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """An op event is named by its HLO instruction, ``%fusion.12 = bf16[..]
    fusion(...)``: keep the instruction's name without its number."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def top_ops(tr: Trace, device: int, n: int = 10) -> List[list]:
    """The ``n`` op names with the most device time, as [name, seconds].
    Control-flow ops (a ``while`` holds every op of its loop) are left
    out, so no time is counted twice."""
    acc: Dict[str, float] = {}
    for e in tr.ops.get(device, []):
        name = op_name(e.name)
        if name.startswith(_CONTAINERS):
            continue
        acc[name] = acc.get(name, 0.0) + e.dur
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def host_label(tr: Trace, s: float, t: float) -> str:
    """What the host was doing in [s, t]: the benchmark span (``bench.*``)
    that covers the gap's middle, innermost first; else the host event
    that overlaps the gap the most."""
    mid = (s + t) / 2
    spans = [e for e in tr.host if e.name.startswith("bench.")
             and not e.name.startswith("bench.mark.")
             and e.start <= mid <= e.end]
    if spans:
        return min(spans, key=lambda e: e.dur).name
    best, best_ov = "no host span", 0.0
    for e in tr.host:
        if e.start > t:
            break
        ov = min(e.end, t) - max(e.start, s)
        if ov > best_ov and e.dur < 10 * (t - s) + 1e-3:
            best, best_ov = e.name, ov
    return best


def top_gaps(tr: Trace, device: int, t0: float, t1: float, n: int = 10
             ) -> List[list]:
    gaps = sorted(idle_gaps(tr, device, t0, t1), key=lambda g: g[0] - g[1])
    return [[host_label(tr, s, t), t - s] for s, t in gaps[:n]]
