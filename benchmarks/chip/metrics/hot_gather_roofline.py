"""The controller's id-match kernel's share of its roofline, in percent:
the larger of its counted operations at the int8 peak and its counted
bytes at peak bandwidth (``kernels/hot_gather.py``), over its measured
time."""


def read(ctx):
    return ctx.roofline("hot_gather")
