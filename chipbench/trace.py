"""From a profiler trace (``.xplane.pb``) to device busy time, idle gaps
and device time per program.

The reduction works on plain intervals, so it is tested on hand-built
event lists as well as on recorded traces:

* busy time is the union of the intervals in which an operation ran on
  a device, clipped to the traced window, averaged over the devices;
* an idle gap is a stretch of the window in which no operation ran; each
  is named by the host events that cover most of it;
* a program's device time is the sum of its executions on the devices'
  module line, by program name without the ``(id)`` suffix.

On a TPU trace every device is a plane ``/device:TPU:<i>``; its ``XLA
Ops`` line holds one event per operation and its ``XLA Modules`` line one
event per execution of a compiled program (``jit_<name>(<id>)``).  Host
planes (``/host:...``) hold the host threads, the benchmark's own spans
(``TraceAnnotation``) among them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the benchmark's span around the traced window
WINDOW_SPAN = "chipbench.window"
#: every span the benchmark itself records starts so
SPAN_PREFIX = "chipbench."
#: host events shorter than this name no idle gap and are not kept
MIN_HOST_NS = 2_000
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    """What the reduction reads: per device, its op and module events; the
    host events; the window."""
    ops: List[List[Event]]          # per device
    modules: List[List[Event]]      # per device
    host: List[Event]
    window: Tuple[float, float]     # ns


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[12:].isdigit()


def union_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    """Length of the union of the intervals, clipped to [lo, hi]."""
    total, end = 0.0, lo
    for e in sorted(events, key=lambda e: e.start_ns):
        s, t = max(e.start_ns, end), min(e.end_ns, hi)
        if t > s:
            total += t - s
        end = max(end, min(e.end_ns, hi))
    return total


def gaps(events: Iterable[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] covered by no interval."""
    out, end = [], lo
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.start_ns > end and end < hi:
            out.append((end, min(e.start_ns, hi)))
        end = max(end, e.end_ns)
    if end < hi:
        out.append((end, hi))
    return out


def program_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name)


def module_time_ns(trace: Trace) -> Dict[str, float]:
    """Device time per program inside the window, averaged over devices."""
    lo, hi = trace.window
    out: Dict[str, float] = {}
    for dev in trace.modules:
        for e in dev:
            t = min(e.end_ns, hi) - max(e.start_ns, lo)
            if t > 0:
                k = program_name(e.name)
                out[k] = out.get(k, 0.0) + t / len(trace.modules)
    return out


def busy_ns(trace: Trace) -> float:
    lo, hi = trace.window
    per = [union_ns(dev, lo, hi) for dev in trace.ops]
    return float(np.mean(per)) if per else 0.0


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return event_name.split(" = ", 1)[0]


def self_times(events: List[Event]) -> List[Tuple[Event, float]]:
    """Each event with its time minus that of the events nested in it (a
    ``while`` op holds its body's ops on the same line)."""
    out: List[Tuple[Event, float]] = []
    stack: List[List] = []          # [event, self time]
    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(e.end_ns, stack[-1][0].end_ns) - e.start_ns
        stack.append([e, e.end_ns - e.start_ns])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def top_ops(trace: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """The device operations that took most self time inside the window,
    in seconds, averaged over devices."""
    lo, hi = trace.window
    tot: Dict[str, float] = {}
    for dev in trace.ops:
        inside = [e for e in dev if e.end_ns > lo and e.start_ns < hi]
        for e, t in self_times(inside):
            name = op_name(e.name)
            tot[name] = tot.get(name, 0.0) + t / len(trace.ops)
    return [(n, t / 1e9) for n, t in
            sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps of the first device, each named by the
    benchmark's span around it and the host events that overlap it most
    (``idle`` where none does)."""
    if not trace.ops:
        return []
    lo, hi = trace.window
    found = sorted(gaps(trace.ops[0], lo, hi), key=lambda g: g[0] - g[1])[:k]
    host = [e for e in trace.host if e.name != WINDOW_SPAN]
    names = sorted({e.name for e in host})
    code = {n: i for i, n in enumerate(names)}
    own = np.array([n.startswith(SPAN_PREFIX) for n in names], dtype=bool)
    idx = np.array([code[e.name] for e in host], dtype=np.int64)
    starts = np.array([e.start_ns for e in host], dtype=np.float64)
    ends = np.array([e.end_ns for e in host], dtype=np.float64)
    out = []
    for s, t in found:
        over = np.clip(np.minimum(ends, t) - np.maximum(starts, s), 0, None)
        cover = np.bincount(idx, weights=over, minlength=len(names))
        rank = np.argsort(-cover, kind="stable")
        spans = [names[i] for i in rank[:len(names)] if own[i]
                 and cover[i] > 0][:1]
        best = [names[i] for i in rank if not own[i] and cover[i] > 0][:3]
        label = " > ".join(best) or "idle"
        out.append((f"{spans[0]}: {label}" if spans else label,
                    (t - s) / 1e9))
    return out


def latest_xplane(directory: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> Trace:
    """Read a recorded profile.  The window is the benchmark's own
    ``chipbench.window`` span, or the whole extent of the device ops."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: List[List[Event]] = []
    modules: List[List[Event]] = []
    host: List[Event] = []
    window = None
    for plane in data.planes:
        if is_device_plane(plane.name):
            dev_ops: List[Event] = []
            dev_mods: List[Event] = []
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dst = dev_ops if line.name == OPS_LINE else dev_mods
                    dst.extend(Event(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                               for e in line.events)
            ops.append(dev_ops)
            modules.append(dev_mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.end_ns - ev.start_ns >= MIN_HOST_NS:
                        host.append(ev)
    if window is None:
        every = [e for dev in ops for e in dev]
        window = ((min(e.start_ns for e in every),
                   max(e.end_ns for e in every)) if every else (0.0, 0.0))
    return Trace(ops=ops, modules=modules, host=host, window=window)


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    module_s: Dict[str, float]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def idle_share_pct(summary: Optional["Summary"]) -> Optional[float]:
    """1 - busy / window, in %; None without a device trace."""
    if summary is None or summary.window_s <= 0.0:
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)


def summarize(trace: Trace) -> Optional[Summary]:
    """None when the trace holds no device operation (a CPU run)."""
    if not any(trace.ops):
        return None
    lo, hi = trace.window
    return Summary(busy_s=busy_ns(trace) / 1e9, window_s=(hi - lo) / 1e9,
                   module_s={k: v / 1e9
                             for k, v in module_time_ns(trace).items()},
                   device_ops=top_ops(trace), idle_gaps=idle_gaps(trace))
