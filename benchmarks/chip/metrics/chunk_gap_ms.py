"""Mean device-idle time between chunks, in milliseconds: the idle time of
the device from the middle of one ``bench.run`` span to the middle of the
next.  A chunk is one long device program, so the middles fall inside
busy time and what lies between is the host's work between chunks: trace
copies, the churn swap, dispatch."""
from reduce import gaps, total


def read(ctx):
    runs = ctx.trace.spans("bench.run")
    busy = ctx.trace.busy[0] if ctx.trace.busy else []
    mids = [(r.start + r.end) / 2 for r in runs]
    idle = [total(gaps(busy, a, b)) for a, b in zip(mids, mids[1:])]
    return sum(idle) / len(idle) / 1e6 if idle else None
