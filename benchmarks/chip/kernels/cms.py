"""Work of the servers' count-min sketch (``repro.kernels.cms_update_query``)
per fleet window.

One call per server and sweep point over every ingress lane of the
window (``B`` lanes): key hashes (16 B) and a mask in, the estimate out,
and the ``[DEPTH, W]`` int32 sketch read and written.  One-hot selects
and sums, not a matmul: bounded by HBM bytes, no operations counted.
"""
TRACE_NAMES = ("cms_update_query", "_cms_kernel")


def per_call(b, depth, width):
    return b * (16 + 4 + 4) + 2 * depth * width * 4


def per_window(sh):
    if not sh["track"]:
        return None
    calls = sh["servers"] * sh["points"]
    b = per_call(sh["subrounds"] * sh["lanes"], sh["cms_depth"], sh["cms_width"])
    return dict(ops=0, bytes=calls * b, ops_peak=None)
