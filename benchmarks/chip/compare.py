"""The comparison that decides ``correct``: the program against the plain
reference (``reference.py``), point by point, on every timed chunk's
traces and on the final state.

Three numbers, each with a limit set from readings of sound runs and of
the control (the reference with bfloat16 time, see ``PERF.md``):

* ``state_mismatches``: elements that differ among the integer, boolean
  and byte arrays (every trace of every timed chunk; switch tables, orbit
  lines and their value bytes, server FIFOs, key versions, sketches,
  client counters, pending replies, PRNG keys, the controller's active
  size and F-REQ lanes).  An exact comparison: limit 0.
* ``latency_hist_gap``: the switch and server latency histograms,
  sum of |program - reference| over the reference's total count.
* ``time_gap_us``: the largest gap in simulated time (``now`` and every
  packet, request-table and FIFO timestamp), in microseconds.
"""
from __future__ import annotations

import numpy as np

LIMITS = {
    "state_mismatches": 0,
    "latency_hist_gap": 0.01,
    "time_gap_us": 1.0,
}
HIST = ("clients.hist_switch", "clients.hist_server")


def flatten(tree, prefix="") -> dict:
    """Nested dicts -> {"a.b": leaf}."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, name + "."))
        else:
            out[name] = v
    return out


def is_time(name: str) -> bool:
    leaf = name.rsplit(".", 1)[-1]
    return leaf in ("ts", "rt_ts", "now")


def _exact(a, b) -> int:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.size, b.size, 1)
    return int(np.sum(a != b))


def compare(prog: dict, ref: dict) -> tuple[dict, int, int]:
    """``prog`` and ``ref``: one dict per point with ``traces`` (a list of
    per-chunk ``{name: array}``), ``state`` (flat ``{name: array}``) and
    ``control`` (flat, may be empty).  Returns the numbers, the blocks
    compared (one per point and chunk, plus one per point for its final
    state) and the blocks that differ."""
    mism, hist_diff, hist_total, tgap = 0, 0, 0, 0.0
    attempted = failed = 0
    for p, r in zip(prog, ref):
        if len(p["traces"]) != len(r["traces"]):
            raise ValueError("program and reference ran different chunk counts")
        for tp, tr in zip(p["traces"], r["traces"]):
            bad = sum(_exact(tp.get(k, np.zeros(0)), v) for k, v in tr.items())
            attempted += 1
            failed += bad > 0
            mism += bad
        bad_state = 0
        have = {**p["state"], **p["control"]}
        for k, v in list(r["state"].items()) + list(r["control"].items()):
            if k not in have:
                bad_state += max(np.size(v), 1)
                continue
            got = np.asarray(have[k])
            want = np.asarray(v)
            if k in HIST:
                hist_diff += int(np.abs(got.astype(np.int64) - want.astype(np.int64)).sum())
                hist_total += int(want.astype(np.int64).sum())
                bad_state += got.shape != want.shape
            elif is_time(k):
                if got.shape != want.shape:
                    bad_state += max(want.size, 1)
                elif want.size:
                    gap = np.abs(got.astype(np.float64) - want.astype(np.float64))
                    tgap = max(tgap, float(np.nanmax(np.where(np.isnan(gap), np.inf, gap))))
            else:
                bad_state += _exact(got, want)
        attempted += 1
        failed += bad_state > 0
        mism += bad_state
    numbers = {
        "state_mismatches": mism,
        "latency_hist_gap": hist_diff / max(hist_total, 1),
        "time_gap_us": tgap,
    }
    return numbers, attempted, failed


def verdict(numbers: dict, limits: dict = LIMITS) -> bool:
    return all(numbers[k] <= limits[k] for k in limits if k in numbers)
