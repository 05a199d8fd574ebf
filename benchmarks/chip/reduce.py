"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

* device op intervals: the ``XLA Ops`` line of every ``/device:TPU:<n>``
  plane (events nest: a ``while`` op spans the ops of its body);
* busy time: the union of those intervals, per device;
* idle gaps: the complement of the union inside the traced window, each
  named by the innermost host event (any line of ``/host:CPU``) that
  covers its midpoint;
* host spans: events of the host plane, such as the harness's
  ``bench.run`` and ``bench.churn`` annotations.

Times are nanoseconds on the trace's own clock.  Nothing here imports
JAX until :func:`load` is called.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import NamedTuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


class Event(NamedTuple):
    name: str
    start: float
    end: float


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = hlo.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_ops(pd) -> list[list[Event]]:
    """Per device (in plane order): its op events, sorted by start."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        evs = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                evs.extend(Event(short_name(e.name), e.start_ns, e.end_ns)
                           for e in line.events)
        evs.sort(key=lambda e: (e.start, -e.end))
        out.append(evs)
    return out


def host_events(pd) -> list[Event]:
    """Events of every line of the host plane (the Python thread, the
    runtime's threads), sorted by start."""
    evs = []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                evs.extend(Event(e.name, e.start_ns, e.end_ns) for e in line.events)
    return sorted(evs, key=lambda e: (e.start, -e.end))


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, t0, t1) -> list[tuple[float, float]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, t0, t1) -> list[tuple[float, float]]:
    """Idle intervals of [t0, t1] not covered by the merged ``busy``."""
    out, t = [], t0
    for s, e in clip(busy, t0, t1):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < t1:
        out.append((t, t1))
    return out


def self_times(ops: list[Event]) -> collections.Counter:
    """Per op name: its duration less the time of the ops nested in it."""
    selft: collections.Counter = collections.Counter()
    stack: list[list] = []            # [event, time covered by children]
    for ev in ops:
        while stack and stack[-1][0].end <= ev.start:
            done, child = stack.pop()
            selft[done.name] += (done.end - done.start) - child
        if stack and ev.end <= stack[-1][0].end:
            stack[-1][1] += ev.end - ev.start
        stack.append([ev, 0.0])
    while stack:
        done, child = stack.pop()
        selft[done.name] += (done.end - done.start) - child
    return selft


def innermost(host: list[Event], t: float) -> str:
    """Name of the shortest host event covering time ``t``."""
    best = None
    for ev in host:
        if ev.start > t:
            break
        if ev.end >= t and (best is None or ev.end - ev.start < best.end - best.start):
            best = ev
    return best.name if best else "no host event"


class Reduced(NamedTuple):
    """A traced window: per-device ops and busy union, host spans, gaps."""
    window: tuple[float, float]
    ops: list[list[Event]]
    busy: list[list[tuple[float, float]]]
    host: list[Event]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy_ns(self) -> float:
        """Busy time inside the window, averaged over the devices."""
        if not self.busy:
            return 0.0
        return sum(total(clip(b, *self.window)) for b in self.busy) / len(self.busy)

    def spans(self, name: str) -> list[Event]:
        return [e for e in self.host if e.name == name]

    def kernel_ns(self, patterns) -> float:
        """Time of ops whose name contains any of ``patterns`` (the union of
        their intervals, so a call and the kernel inside it count once),
        summed over devices."""
        t = 0.0
        for ops in self.ops:
            hit = [(e.start, e.end) for e in ops if any(p in e.name for p in patterns)]
            t += total(clip(union(hit), *self.window))
        return t

    def idle_gaps(self) -> list[tuple[float, float]]:
        return gaps(self.busy[0], *self.window) if self.busy else []


def reduce(pd, span: str) -> Reduced:
    """Reduce a trace to the window from the first ``span`` host event's
    start to the last one's end."""
    host = host_events(pd)
    marks = [e for e in host if e.name == span]
    if not marks:
        lines = [(p.name, ln.name) for p in pd.planes for ln in p.lines]
        raise ValueError(f"the trace has no host span {span!r}; lines: {lines}")
    window = (marks[0].start, marks[-1].end)
    ops = device_ops(pd)
    busy = [union((e.start, e.end) for e in dev) for dev in ops]
    return Reduced(window=window, ops=ops, busy=busy, host=host)


def breakdown(red: Reduced, n: int = 10) -> dict:
    """The ``n`` device ops with most self time and the ``n`` longest idle
    gaps, each gap named by what the host was doing in it (seconds)."""
    selft: collections.Counter = collections.Counter()
    for dev in red.ops:
        inside = [e for e in dev if e.end > red.window[0] and e.start < red.window[1]]
        selft.update(self_times(inside))
    k = max(len(red.ops), 1)
    top = [[name, t / k / 1e9] for name, t in selft.most_common(n)]
    idle = sorted(red.idle_gaps(), key=lambda g: g[0] - g[1])[:n]
    named = [[innermost(red.host, (s + e) / 2), (e - s) / 1e9] for s, e in idle]
    return {"device_ops": top, "idle_gaps": named}
