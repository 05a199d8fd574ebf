"""The servers' count-min sketch kernel's share of its roofline, in
percent: counted HBM bytes (``kernels/cms.py``) at peak bandwidth over its
measured time."""


def read(ctx):
    return ctx.roofline("cms")
