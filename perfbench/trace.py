"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<n>``; their operations are the events of the ``XLA Ops``
line.  The measured window is the host annotation ``perfbench.window``
that the traffic module wraps around it, so device time is clipped to the
same interval the host clock measured.  Host annotations whose names start
with ``perfbench.`` say what the host was doing, and name the device's
idle gaps.

On a TPU each event of ``XLA Ops`` is named by its HLO instruction's
text, ``%sparse_conv_pallas.34 = f32[32,2048,7,7]{3,2,1,0:T(8,128)}
custom-call(...)``; :func:`op_name` keeps the instruction's name and its
result shape, ``sparse_conv_pallas.34 f32[32,2048,7,7]``.  Pallas kernels
are the custom calls that ``pallas_call`` lowers to; XLA names each after
its kernel (``sparse_conv_pallas.<n>``, ``bsr_conv_pallas.<n>``), and
:data:`KERNEL_EVENT` matches those names.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "perfbench.window"
HOST_PREFIX = "perfbench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# A Pallas kernel's event: ``<kernel>_pallas`` with XLA's instance suffix.
KERNEL_EVENT = re.compile(r"^(?P<kernel>\w+)_pallas(\.\d+)?( |$)")
# An HLO instruction's text: its name, and its result shape when that is
# one array.
_HLO = re.compile(r"^%?(?P<name>[^ ]+) = (?P<shape>\w+\[[\d,]*\])?")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    """What the reduction needs of one trace: the window, each device's
    operations and the host's benchmark annotations."""

    window: Interval
    devices: List[List[Event]]
    host: List[Event]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def op_name(raw: str) -> str:
    """An operation's name as the reduction reports it: the instruction's
    name and result shape when ``raw`` is an HLO instruction's text, else
    ``raw``."""
    m = _HLO.match(raw)
    if not m:
        return raw
    return m["name"] + (" " + m["shape"] if m["shape"] else "")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def from_profile(pd) -> Trace:
    """Extract a :class:`Trace` from a ``ProfileData``."""
    devices: List[List[Event]] = []
    host: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [Event(op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            host += [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for line in plane.lines for e in line.events
                     if e.name.startswith(HOST_PREFIX)]
    windows = [e for e in host if e.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation, found "
                         f"{len(windows)}")
    w = windows[0]
    return Trace(window=(w.start_ns, w.end_ns), devices=devices,
                 host=[e for e in host if e.name != WINDOW])


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def clip(events: Sequence[Event], window: Interval) -> List[Event]:
    lo, hi = window
    return [Event(e.name, max(e.start_ns, lo), min(e.end_ns, hi))
            for e in events if e.end_ns > lo and e.start_ns < hi]


def union(events: Sequence[Event]) -> List[Interval]:
    """The union of the events' intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if out and e.start_ns <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end_ns)
        else:
            out.append([e.start_ns, e.end_ns])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> Optional[float]:
    """Seconds in the window in which an operation ran on a device,
    averaged over the devices; None when the trace has no device."""
    if not trace.devices:
        return None
    per = [sum(b - a for a, b in union(clip(ops, trace.window))) / 1e9
           for ops in trace.devices]
    return sum(per) / len(per)


def idle_share(trace: Trace) -> Optional[float]:
    busy = busy_s(trace)
    if busy is None or trace.window_s <= 0:
        return None
    return 1.0 - busy / trace.window_s


def kernel_s(trace: Trace) -> Optional[Dict[str, float]]:
    """Device seconds in the window of each Pallas kernel, averaged over
    the devices; None when no kernel ran."""
    if not trace.devices:
        return None
    out: Dict[str, float] = {}
    for ops in trace.devices:
        for e in clip(ops, trace.window):
            m = KERNEL_EVENT.match(e.name)
            if m:
                out[m["kernel"]] = (out.get(m["kernel"], 0.0)
                                    + e.dur_ns / 1e9 / len(trace.devices))
    return out or None


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` operations that took most device time in the window, as
    ``[name, seconds]`` summed over the window's forwards and averaged over
    the devices."""
    tot: Dict[str, float] = {}
    for ops in trace.devices:
        for e in clip(ops, trace.window):
            tot[e.name] = (tot.get(e.name, 0.0)
                           + e.dur_ns / 1e9 / len(trace.devices))
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """Idle time of the first device in the window, by what the host was
    doing then: each idle moment goes to the shortest ``perfbench.*``
    annotation that covers it, or to ``host.other``.  The ``n`` largest
    totals, as ``[name, seconds]``."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    gaps, t = [], lo
    for a, b in union(clip(trace.devices[0], trace.window)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    host = clip(trace.host, trace.window)
    # Sweep the boundaries of gaps and annotations in time order, keeping
    # the annotations that cover the current moment in a heap by duration.
    marks = []
    for i, (a, b) in enumerate(gaps):
        marks += [(a, 1, "gap", i), (b, 0, "gap", i)]
    for i, e in enumerate(host):
        marks += [(e.start_ns, 1, "host", i), (e.end_ns, 0, "host", i)]
    marks.sort(key=lambda m: (m[0], m[1]))
    active: List[Tuple[float, int]] = []
    ended = set()
    idle = False
    tot: Dict[str, float] = {}
    prev = lo
    for t, opening, kind, i in marks:
        if idle and t > prev:
            while active and active[0][1] in ended:
                heapq.heappop(active)
            owner = host[active[0][1]].name if active else "host.other"
            tot[owner] = tot.get(owner, 0.0) + (t - prev) / 1e9
        prev = t
        if kind == "gap":
            idle = bool(opening)
        elif opening:
            heapq.heappush(active, (host[i].dur_ns, i))
        else:
            ended.add(i)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
