"""Program stages and host spans in a trace: the metadata reader
(``xmeta.py``), each device op's stage, the split of busy time by stage
(``stages.py``) and of idle time by host span (``stages.py`` and the
``*_idle_ms_per_chunk`` metrics).  On synthetic
intervals, on a trace of the scoped program (``tiny_churn_stages``,
recorded on one v5e by ``record_trace.py``) and on the older trace of the
program before it had scopes (``tiny_churn``)."""
import gzip
import shutil
from pathlib import Path

import pytest

from chip_tiny import layout, tiny_cell

import harness
import reduce as tr
import stages
import xmeta
from repro import obs

DATA = Path(__file__).resolve().parent / "data"
E = tr.Event


def _unzip(name, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA / name, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def raw(tmp_path_factory):
    return _unzip("tiny_churn_stages.xplane.pb.gz", tmp_path_factory)


@pytest.fixture(scope="module")
def scoped(raw):
    return stages.load(str(raw), harness.SPAN_RUN)


@pytest.mark.parametrize("tf_op,stage", [
    ("jit(body)/vmap()/while/body/repro.server/repro.key_version/scatter-add:",
     "repro.key_version"),
    ("jit(body)/while/body/repro.switch/closed_call/repro.install/scatter:", "repro.install"),
    ("jit(body)/vmap()/while/body/closed_call/jit(searchsorted)/gather:", ""),
    ("", ""),
])
def test_stage_is_the_innermost_repro_component(tf_op, stage):
    assert stages.stage_of(tf_op) == stage


def test_metadata_reader_matches_every_device_event(raw):
    from jax.profiler import ProfileData

    found = xmeta.device_tf_ops(raw.read_bytes(), tr.DEVICE_PREFIX)
    assert list(found) == ["/device:TPU:0"]
    plane = next(p for p in ProfileData.from_file(str(raw)).planes
                 if p.name == "/device:TPU:0")
    assert [ln.name for ln in plane.lines] == [n for n, _ in found["/device:TPU:0"]]
    ops = dict(found["/device:TPU:0"])[tr.OPS_LINE]
    assert len(ops) == sum(1 for ln in plane.lines if ln.name == tr.OPS_LINE
                           for _ in ln.events)
    assert sum(1 for t in ops if t.startswith("jit(")) > len(ops) / 2


def test_stage_ops_are_the_reductions_ops(scoped):
    red, ops = scoped
    assert [[(e.start, e.end) for e in dev] for dev in ops] == \
           [[(e.start, e.end) for e in dev] for dev in red.ops]
    seen = {e.name for dev in ops for e in dev}
    assert set(obs.STAGES) <= seen <= set(obs.STAGES) | {""}


def test_nested_stage_time_is_not_counted_twice():
    ops = [E("", 0, 100), E("repro.server", 10, 50), E("repro.key_version", 20, 30),
           E("repro.sketch", 35, 45), E("repro.gen", 60, 70), E("repro.server", 110, 120)]
    red = tr.Reduced(window=(0, 130), ops=[ops], busy=[tr.union((e.start, e.end) for e in ops)],
                     host=[])
    ns = stages.stage_ns(red, [ops])
    assert ns == {"": 50, "repro.server": 30, "repro.key_version": 10, "repro.sketch": 10,
                  "repro.gen": 10}
    assert sum(ns.values()) == red.busy_ns()
    split = stages.split(red, [ops], windows=2)
    assert split["stage_us_per_window"]["repro.server"] == 30 / 2 / 1e3
    assert split["window_device_us"] == 110 / 2 / 1e3


def test_busy_partition_on_the_recording(scoped):
    red, ops = scoped
    ns = stages.stage_ns(red, ops)
    assert all(ns[s] > 0 for s in obs.STAGES)
    # not exact: a nested op whose rounded end lies past its loop's end is
    # not subtracted from the loop's self time
    assert sum(ns.values()) == pytest.approx(red.busy_ns(), rel=1e-3)


def _idle_case():
    busy = [(5, 40), (60, 140), (170, 250)]
    host = [E("bench.churn", 0, 8), E("repro.churn", 1, 7),
            E("bench.run", 10, 150), E("repro.fetch", 45, 58),
            E("bench.churn", 150, 165), E("repro.churn", 152, 160),
            E("bench.run", 166, 260), E("repro.fetch", 250, 256)]
    return tr.Reduced(window=(0, 260), ops=[], busy=[busy], host=host)


IDLE_METRICS = {"repro.fetch": "fetch_idle_ms_per_chunk",
                "repro.churn": "churn_idle_ms_per_chunk",
                "": "unattributed_idle_ms_per_chunk"}


def _idle_metrics(red, chunks, chunk_windows=8):
    """What the three idle metrics read of ``red`` (ms per chunk)."""
    cell = tiny_cell("paper_rack_orbitcache", "hotin_churn", chunk=chunk_windows)
    ctx = harness.Context(cell=cell, trace=red, windows=chunks * chunk_windows,
                          shapes={}, peaks={})
    return {s: layout.metric_reader(m).read(ctx) for s, m in IDLE_METRICS.items()}


def test_idle_partition_on_synthetic_intervals():
    # idle: [0,5] [40,60] [140,170] [250,260]; fetch covers 13 + 6, churn 4 + 8
    assert stages.idle_ns(_idle_case()) == {"repro.fetch": 19, "repro.churn": 12, "": 34}
    assert _idle_metrics(_idle_case(), chunks=2) == {"": 17 / 1e6, "repro.churn": 6 / 1e6,
                                                     "repro.fetch": 9.5 / 1e6}
    assert tr.total(_idle_case().idle_gaps()) == 65


def test_idle_partition_on_the_recording(scoped):
    red, _ = scoped
    idle = stages.idle_ns(red)
    assert set(idle) == set(obs.SPANS) | {""}
    assert all(idle[s] > 0 for s in obs.SPANS)
    assert sum(idle.values()) == pytest.approx(tr.total(red.idle_gaps()), rel=1e-9)
    read = _idle_metrics(red, chunks=2)
    assert all(v > 0 for v in read.values())
    assert 2 * sum(read.values()) == pytest.approx(tr.total(red.idle_gaps()) / 1e6, rel=1e-9)


def test_a_program_without_scopes_has_no_named_stage(tmp_path_factory):
    red, ops = stages.load(str(_unzip("tiny_churn.xplane.pb.gz", tmp_path_factory)),
                           harness.SPAN_RUN)
    assert set(stages.stage_ns(red, ops)) == {""}
    assert set(stages.idle_ns(red)) == {""}
    # all of its idle time is unattributed; the span metrics read 0, not nothing
    read = _idle_metrics(red, chunks=2)
    assert read["repro.fetch"] == read["repro.churn"] == 0
    assert 2 * read[""] == pytest.approx(tr.total(red.idle_gaps()) / 1e6, rel=1e-9)


def test_idle_metrics_need_device_ops():
    red = tr.Reduced(window=(0, 10), ops=[], busy=[], host=[E("repro.fetch", 0, 10)])
    assert set(_idle_metrics(red, chunks=1).values()) == {None}


@pytest.mark.parametrize("span", obs.SPANS)
def test_each_span_has_its_idle_metric_in_the_benchmark(span):
    name = IDLE_METRICS[span]
    assert name == span.removeprefix(stages.PREFIX) + "_idle_ms_per_chunk"
    entry = next(m for m in layout.bench()["per_layer"] if m["name"] == name)
    assert entry["unit"] == "ms" and entry["layer"] == "host loop"
    assert set(IDLE_METRICS) == set(obs.SPANS) | {""}


def test_no_stage_name_matches_a_kernel_pattern():
    patterns = [p for k in ("subround", "cms", "hot_gather")
                for p in layout.kernel_counter(k).TRACE_NAMES]
    for name in obs.STAGES + obs.SPANS:
        assert name.startswith(stages.PREFIX), name
        assert not any(p in name for p in patterns), name


def test_stage_split_needs_a_tpu(tmp_path):
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/stage_split.py", "--workload",
         layout.bench()["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=layout.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr
