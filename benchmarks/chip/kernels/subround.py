"""Work of the fused switch pass (``repro.kernels.subround``) per fleet window.

One call per subround and sweep point, on ``B`` ingress lanes against a
table of ``C`` entries with ``S`` request slots, ``F`` fragments and ``J``
serves.  Bytes are its unpadded inputs and outputs at the dispatcher: per
lane 16 B of key hash and 11 int32/float32 fields in, 4 decisions out;
per entry the lookup, state, request-table, orbit-line and serve-grid
arrays in and out.  Its work is selects and compares, not a matmul, so
it is bounded by HBM bytes and counts no operations.
"""
TRACE_NAMES = ("subround",)


def per_call(b, c, s, f, j):
    lanes_in = b * (16 + 11 * 4)
    lanes_out = b * 4 * 4
    tables_in = c * (16 + 3 * 4) + 6 * 4 * c * s + 3 * 4 * c + 4 * 4 * c * f + 4 * c + 4
    tables_out = (3 * 4 * c + 6 * 4 * c * s + 3 * 4 * c + 4 * 4 * c * f + 4 * c
                  + 2 * 4 * c * f + 6 * 4 * c * j + 3 * 4 * c)
    return lanes_in + lanes_out + tables_in + tables_out


def per_window(sh):
    if sh["scheme"] != "orbitcache":
        return None
    calls = sh["subrounds"] * sh["points"]
    b = per_call(sh["lanes"], sh["entries"], sh["queue"], sh["frags"], sh["serves"])
    return dict(ops=0, bytes=calls * b, ops_peak=None)
