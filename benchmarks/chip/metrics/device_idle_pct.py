"""Share of the traced window in which no op ran on the device, in percent
(averaged over the devices the cell uses)."""


def read(ctx):
    if ctx.trace.window_ns <= 0 or not ctx.trace.busy:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_ns() / ctx.trace.window_ns)
