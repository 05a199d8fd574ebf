"""Every name in BENCHMARK.json resolves to its files; peaks are keyed by
device kind and an unknown kind is an error."""
import re

import pytest

from chip_tiny import layout

B = layout.bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(w):
    cell = layout.cell(w["name"], B)
    assert cell.config["rack"] and cell.config["workload"]
    assert len(cell.traffic["offered_rps"]) >= 1
    assert cell.end_to_end and cell.per_layer
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "sim_req_per_s"}
    assert cell.chips in (1, 4)


@pytest.mark.parametrize("m", B["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_resolves(m):
    assert callable(layout.metric_reader(m["name"]).read)
    assert m["moves"] in {e["name"] for e in B["end_to_end"]}


@pytest.mark.parametrize("k", ["subround", "cms", "hot_gather"])
def test_kernel_counter_resolves(k):
    mod = layout.kernel_counter(k)
    assert mod.TRACE_NAMES and callable(mod.per_window)


def test_names_follow_the_contract():
    names = [c["name"] for c in B["configs"]] + [w["name"] for w in B["workloads"]] \
        + [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
    for c in B["configs"]:
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert layout.config(c["name"])["reduced"] == c["reduced"]


def test_peaks_by_device_kind():
    p = layout.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_ops_per_s"] == 197e12
    with pytest.raises(KeyError):
        layout.peaks("TPU v9 imaginary")


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        layout.cell("no_such_cell", B)
    with pytest.raises(FileNotFoundError):
        layout.traffic("no_such_mix")
    with pytest.raises(FileNotFoundError):
        layout.metric_reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        layout.scheme("no_such_scheme")


def _run_cell(root, tmp_path):
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run_cell.py", "--workload",
         B["workloads"][0]["name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result(tmp_path):
    p = _run_cell(layout.ROOT, tmp_path)
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero_without_result(tmp_path):
    import shutil
    root = tmp_path / "checkout"
    shutil.copytree(layout.HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(layout.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    p = _run_cell(root, tmp_path)
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_check_points_follow_the_seed(w):
    import harness
    tr = layout.cell(w["name"], B).traffic
    seed = 2**40 + 17
    pts = harness.check_points(tr, seed)
    assert pts == harness.check_points(tr, seed) == sorted(set(pts))
    assert len(pts) == min(tr.get("check_points") or len(tr["offered_rps"]),
                           len(tr["offered_rps"]))
    assert max(range(len(tr["offered_rps"])), key=tr["offered_rps"].__getitem__) in pts
    assert len(pts) > len(tr["offered_rps"]) // 2


@pytest.mark.parametrize("n,k", [(4, 2), (12, 6), (2, 1)])
def test_check_points_must_be_more_than_half(n, k):
    import harness
    with pytest.raises(ValueError):
        harness.check_points({"offered_rps": [1.0] * n, "check_points": k}, 7)


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_scheme_resolves(w):
    s = layout.scheme(layout.cell(w["name"], B).config["rack"]["scheme"])
    assert callable(s.init_switch) and callable(s.switch_window)
    assert callable(s.program_state)
    assert not s.PRELOAD or callable(s.preload)
    assert not s.CONTROLLER or (callable(s.cache_update) and callable(s.update_lanes)
                                and callable(s.program_update))
