"""Split a traced window by program stage and by host span.

The program tags its device ops with named scopes and its host loop with
profiler annotations (``repro.obs``: ``STAGES`` and ``SPANS``, all named
``repro.*``).  An op's stage is the innermost ``repro.*`` component of
its JAX name stack, which the trace keeps in the op's metadata
(``xmeta.py``); ``""`` where there is none.  This module splits the
window that ``reduce.reduce`` finds two ways:

* busy time by stage: the self time of the ops inside the window (each
  op less the ops nested in it), summed per stage, so the stages and
  ``""`` add up to the device's busy time;
* idle time by host span: the part of the window's idle gaps that lies
  under each span, and the part under no ``repro.*`` span.

The idle split needs only the host events that ``reduce.Reduced`` keeps:
the per-layer metrics ``fetch_idle_ms_per_chunk``,
``churn_idle_ms_per_chunk`` and ``unattributed_idle_ms_per_chunk`` read it
(:func:`idle_ms_per_chunk`).  ``reduce.py`` does not keep an op's stage,
so no per-layer metric reads the busy split yet; ``stage_split.py``
reports it for one traced run.
"""
from __future__ import annotations

import collections
import re

import reduce as tr
import xmeta

PREFIX = "repro."
STAGE = re.compile(r"repro\.[A-Za-z0-9_]+")


def stage_of(tf_op: str) -> str:
    """The innermost ``repro.*`` component of a JAX name stack, or ``""``."""
    found = STAGE.findall(tf_op)
    return found[-1] if found else ""


def stage_ops(pd, data: bytes) -> list[list[tr.Event]]:
    """Per device (in plane order, as ``reduce.device_ops``): its op events
    named by their stage, sorted by start.  Each event is matched to its
    own metadata by its position in the file ``data`` (the line's name and
    event count are checked), so ops of one short name in different
    programs keep their own stages."""
    tf_ops = xmeta.device_tf_ops(data, tr.DEVICE_PREFIX)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        evs = []
        for k, line in enumerate(plane.lines):
            if line.name != tr.OPS_LINE:
                continue
            events = list(line.events)
            name, ops = tf_ops[plane.name][k]
            if name != line.name or len(ops) != len(events):
                raise ValueError(f"{plane.name} line {k}: {line.name!r} with {len(events)} "
                                 f"events, the file has {name!r} with {len(ops)}")
            evs.extend(tr.Event(stage_of(t), e.start_ns, e.end_ns)
                       for e, t in zip(events, ops))
        evs.sort(key=lambda e: (e.start, -e.end))
        out.append(evs)
    return out


def load(path: str, span: str):
    """``(reduce.reduce(trace, span), stage_ops(trace))`` of one trace file."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    return tr.reduce(pd, span), stage_ops(pd, data)


def stage_ns(red: tr.Reduced, ops: list[list[tr.Event]]) -> collections.Counter:
    """Self time per stage inside the window, averaged over devices (ns);
    the key ``""`` holds ops under no stage."""
    t0, t1 = red.window
    out: collections.Counter = collections.Counter()
    for dev in ops:
        inside = sorted((e._replace(start=max(e.start, t0), end=min(e.end, t1))
                         for e in dev if e.end > t0 and e.start < t1),
                        key=lambda e: (e.start, -e.end))
        out.update(tr.self_times(inside))
    k = max(len(ops), 1)
    return collections.Counter({s: t / k for s, t in out.items()})


def overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_ns(red: tr.Reduced) -> dict[str, float]:
    """Idle time of the window under each ``repro.*`` host span (ns); the
    key ``""`` holds idle time under none."""
    idle = red.idle_gaps()
    names = sorted({e.name for e in red.host if e.name.startswith(PREFIX)})
    out = {n: overlap(idle, tr.union((e.start, e.end) for e in red.spans(n))) for n in names}
    under = overlap(idle, tr.union((e.start, e.end) for e in red.host
                                   if e.name.startswith(PREFIX)))
    out[""] = tr.total(idle) - under
    return out


def idle_ms_per_chunk(ctx, span: str):
    """Idle time of the traced window under the host span ``span``
    (``""``: under no ``repro.*`` span), in ms per timed chunk, from a
    metric's ``harness.Context``.  0 where the trace has no such span, as
    in a program without spans, whose idle time is all unattributed."""
    chunks = ctx.windows / ctx.cell.traffic["chunk_windows"]
    if not ctx.trace.busy or chunks <= 0:
        return None
    return idle_ns(ctx.trace).get(span, 0.0) / chunks / 1e6


def split(red: tr.Reduced, ops: list[list[tr.Event]], windows: int) -> dict:
    """Device self time per stage in µs per window, and the busy time per
    window it adds up to."""
    return {
        "stage_us_per_window": {s: t / windows / 1e3 for s, t in sorted(stage_ns(red, ops).items())},
        "window_device_us": red.busy_ns() / windows / 1e3,
    }
