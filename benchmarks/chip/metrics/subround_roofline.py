"""The switch kernel's share of its roofline, in percent: its counted HBM
bytes (``kernels/subround.py``; no matmul, so bytes bound it) at the
chip's peak bandwidth, over its measured time."""


def read(ctx):
    return ctx.roofline("subround")
