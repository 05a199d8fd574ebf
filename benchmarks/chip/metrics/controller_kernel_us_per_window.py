"""Device time of the controller's kernels per fleet window, in
microseconds: the servers' count-min sketch (``cms``, every window) plus
the period's id-match contractions (``hot_gather``)."""


def read(ctx):
    parts = [ctx.kernel_us_per_window(k) for k in ("cms", "hot_gather")]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None
