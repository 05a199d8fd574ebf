"""The trace reduction, on synthetic intervals and on a small trace
recorded on one v5e (``record_trace.py``: the churn cell cut to a test's
size, two 8-window chunks)."""
import gzip
import shutil
from pathlib import Path

import pytest

from chip_tiny import layout, tiny_cell

import harness
import reduce as tr

DATA = Path(__file__).resolve().parent / "data" / "tiny_churn.xplane.pb.gz"
E = tr.Event


def test_union_gaps_and_clip():
    u = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert tr.gaps(u, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    assert tr.total(tr.clip(u, 2, 6)) == 2


def test_self_times_subtract_nested_ops():
    ops = [E("while", 0, 10), E("a", 1, 3), E("b", 4, 8), E("c", 5, 6), E("d", 12, 13)]
    s = tr.self_times(ops)
    assert s == {"while": 4, "a": 2, "b": 3, "c": 1, "d": 1}


def test_short_name_and_innermost_host_event():
    assert tr.short_name("%fusion.12 = f32[4]{0} fusion(%p)") == "fusion.12"
    host = [E("bench.run", 0, 10), E("$fleet.py:329 run", 1, 9), E("np.asarray", 2, 3)]
    assert tr.innermost(host, 2.5) == "np.asarray"
    assert tr.innermost(host, 5) == "$fleet.py:329 run"
    assert tr.innermost(host, 11) == "no host event"


def test_chunk_gap_counts_idle_time_between_chunk_middles():
    # the second busy interval runs on past the first span's end
    busy = [(5, 95), (96, 150), (152, 199), (212, 290)]
    runs = [E("bench.run", 0, 100), E("bench.run", 110, 200), E("bench.run", 210, 300)]
    red = tr.Reduced(window=(0, 300), ops=[], busy=[busy], host=runs)
    ctx = harness.Context(cell=None, trace=red, windows=3, shapes={}, peaks={})
    # idle in [50, 155]: 1 + 2; in [155, 255]: 13; in ns, read as ms
    assert layout.metric_reader("chunk_gap_ms").read(ctx) == (3 + 13) / 2 / 1e6


def _reduce_fixture(tmp_path):
    raw = tmp_path / "t.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(raw, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.reduce(tr.load(str(raw)), harness.SPAN_RUN)


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    return _reduce_fixture(tmp_path_factory.mktemp("trace"))


def test_recorded_trace_has_device_ops_and_spans(red):
    assert len(red.ops) == 1 and len(red.ops[0]) > 1000
    assert len(red.spans(harness.SPAN_RUN)) == 2
    assert len(red.spans(harness.SPAN_CHURN)) == 2     # a swap before each chunk
    busy = red.busy_ns()
    assert 0 < busy <= red.window_ns
    idle = tr.total(red.idle_gaps())
    assert abs(busy + idle - red.window_ns) < 1e-3 * red.window_ns


@pytest.mark.parametrize("kernel", ["subround", "cms", "hot_gather"])
def test_kernels_are_found_by_name(red, kernel):
    assert red.kernel_ns(layout.kernel_counter(kernel).TRACE_NAMES) > 0


def test_breakdown_shape(red):
    bd = tr.breakdown(red)
    assert 1 <= len(bd["device_ops"]) <= 10 and 1 <= len(bd["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and t >= 0 for n, t in bd["device_ops"] + bd["idle_gaps"])


def test_every_metric_reads_the_recorded_trace(red):
    cell = tiny_cell("paper_rack_orbitcache", "hotin_churn", chunk=8)
    b = layout.bench()
    ctx = harness.Context(cell=cell, trace=red, windows=2 * 8,
                          shapes=layout.shapes(cell), peaks=layout.peaks("TPU v5 lite"))
    for m in b["per_layer"]:
        v = layout.metric_reader(m["name"]).read(ctx)
        assert v is not None and v >= 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, m["name"]
