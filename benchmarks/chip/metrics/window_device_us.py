"""Device busy time per fleet window (every sweep point advances one
window), in microseconds: the union of device op intervals in the traced
window over the windows simulated in it."""


def read(ctx):
    busy = ctx.trace.busy_ns()
    return busy / ctx.windows / 1e3 if busy > 0 and ctx.windows else None
