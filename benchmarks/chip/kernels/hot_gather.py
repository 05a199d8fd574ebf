"""Work of the controller's id-match contraction (``repro.kernels.hot_gather``)
per fleet window.

Per control period and sweep point, three calls (``core/controller.py``
``_merge_scores``): cached keys against report lanes, report lanes
against report lanes, report lanes against cached keys, each with one
int32 column (``D`` = 1).  A call on ``B`` ids, ``H`` hot ids and ``D``
columns is a ``[B, H] x [H, D]`` contraction: ``2 B H D`` integer
operations (the kernel runs them on the MXU in 8-bit limbs, so they are
held against the int8 peak) and ``4 (2 B + H + H D + B D)`` bytes.
"""
TRACE_NAMES = ("hot_gather",)


def per_call(b, h, d):
    return 2 * b * h * d, 4 * (2 * b + h + h * d + b * d)


def per_window(sh):
    if sh["scheme"] != "orbitcache" or not sh["period"]:
        return None
    c, nr = sh["entries"], sh["report_lanes"]
    calls = [per_call(c, nr, 1), per_call(nr, nr, 1), per_call(nr, c, 1)]
    scale = sh["points"] / sh["period"]
    return dict(ops=scale * sum(o for o, _ in calls),
                bytes=scale * sum(b for _, b in calls), ops_peak="int8_ops_per_s")
