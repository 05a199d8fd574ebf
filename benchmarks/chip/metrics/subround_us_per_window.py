"""Device time of the fused switch kernel (``subround``) per fleet window,
in microseconds."""


def read(ctx):
    return ctx.kernel_us_per_window("subround")
