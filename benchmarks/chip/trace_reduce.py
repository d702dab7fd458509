"""Reduce a profiler trace to device busy time, top device operations and
idle gaps attributed to what the host was doing.

The benchmark marks its own host spans with ``jax.profiler.TraceAnnotation``
(``bench.window`` around the traced requests, ``bench.plan`` around the
gateway's plan, ``bench.run`` around the share runner, ``bench.warmup``).
The traced window is the ``bench.window`` span. Busy time on a device is
the union of the intervals in which one of its operations ran, clipped to
the window. Operation time is counted on the operations that contain no
other (a ``while`` spans its body), named by program and operation
(``jit_prefill/fusion.7``); an idle gap is a stretch of the window in which none ran, named
by the innermost host event on the benchmark's own thread that covers the
gap's middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

Interval = Tuple[float, float]          # start, end in ns


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns
    end: float          # ns
    module: str = ""    # the program an operation ran in, where known


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: List[float]                 # per device plane
    device_ops: List[Tuple[str, float]]  # seconds, averaged over devices
    idle_gaps: List[Tuple[str, float]]   # seconds, averaged over devices

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def op_name(text: str) -> str:
    """An HLO operation's name without its signature:
    ``%fusion.7 = (f32[8]...) fusion(...)`` is ``fusion.7``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _in_modules(ops: List[Event], modules: List[Event]) -> List[Event]:
    """Name each operation by the program it ran in: ``jit_prefill/fusion.7``
    (modules do not overlap on one device)."""
    modules = sorted(modules, key=lambda e: e.start)
    starts = [m.start for m in modules]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        mod = modules[i].name if i >= 0 and e.start < modules[i].end else ""
        out.append(dataclasses.replace(e, name=op_name(e.name),
                                       module=op_name(mod)))
    return out


def leaves(evs: Sequence[Event]) -> List[Event]:
    """The operations that contain no other operation: a ``while`` or a
    ``call`` spans its body's operations, which are counted themselves."""
    order = sorted(evs, key=lambda e: (e.start, -e.end))
    out = []
    for i, e in enumerate(order):
        nxt = order[i + 1] if i + 1 < len(order) else None
        if nxt is None or nxt.start >= e.end or nxt.end > e.end:
            out.append(e)
    return out


def load(trace_dir: str) -> Tuple[List[Event], List[List[Event]]]:
    """(events on the host thread that holds the window span, operations
    of each device plane) from the newest ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    host: List[Event] = []
    devices: List[List[Event]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            evs = [e for ln in ops for e in _events(ln)]
            mods = [e for ln in lines if ln.name == MODULES_LINE
                    for e in _events(ln)]
            if evs:
                devices.append(_in_modules(evs, mods))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = _events(ln)
                if any(e.name == WINDOW_SPAN for e in evs):
                    host = evs
    return host, devices


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _host_names(host: Sequence[Event], times: Sequence[float]) -> List[str]:
    """For each time, the innermost (shortest) host event that covers it:
    one sweep over the host events in start order."""
    order = sorted(range(len(times)), key=times.__getitem__)
    spans = sorted(host, key=lambda e: e.start)
    out = ["(no host span)"] * len(times)
    active: List[Event] = []
    j = 0
    for i in order:
        t = times[i]
        while j < len(spans) and spans[j].start <= t:
            active.append(spans[j])
            j += 1
        active = [e for e in active if e.end > t]
        if active:
            out[i] = min(active, key=lambda e: e.end - e.start).name
    return out


def reduce(host: Sequence[Event],
           devices: Sequence[Sequence[Event]]) -> TraceSummary:
    windows = [e for e in host if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    if not devices:
        raise ValueError("no device operations in the trace")
    lo, hi = windows[0].start, windows[0].end
    n = len(devices)
    spans = [e for e in host if e.end > e.start and e.name != WINDOW_SPAN]
    busy_s, ops, gaps = [], collections.Counter(), collections.Counter()
    for evs in devices:
        busy = union(_clip([(e.start, e.end) for e in evs], lo, hi))
        busy_s.append(sum(e - s for s, e in busy) * 1e-9)
        for e in leaves(evs):
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                ops[f"{e.module}/{e.name}" if e.module else e.name] += \
                    d * 1e-9 / n
        idle = _gaps(busy, lo, hi)
        names = _host_names(spans, [(s + e) / 2 for s, e in idle])
        for name, (s, e) in zip(names, idle):
            gaps[name] += (e - s) * 1e-9 / n
    return TraceSummary(window_s=(hi - lo) * 1e-9, busy_s=busy_s,
                        device_ops=ops.most_common(TOP),
                        idle_gaps=gaps.most_common(TOP))


def summarize(trace_dir: str) -> TraceSummary:
    return reduce(*load(trace_dir))


def breakdown(s: TraceSummary) -> Dict:
    return {"device_ops": [[k, v] for k, v in s.device_ops],
            "idle_gaps": [[k, v] for k, v in s.idle_gaps]}
