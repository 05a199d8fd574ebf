"""The comparison that decides ``correct``, at a size a CPU run can hold.

The program (the fleet under the ``ref`` kernels) must equal the plain
reference exactly on every cell's configuration and mix; the control (the
reference with bfloat16 simulated time in the program's place) and each
planted fault of the timed path must come out not correct.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_tiny import tiny_cell

import compare
import harness
from repro.kvstore.fleet import BatchedRackSimulator

SEED = 2**33 + 12345          # larger than 32 signed bits hold


def drive(cell, n_timed=2, seed=SEED):
    """The harness's program side without the clock: set-up, ``n_timed``
    chunks, and what the comparison reads."""
    n = len(cell.traffic["offered_rps"])
    seeds = harness.point_seeds(seed, n)
    prog = harness.Program(cell, seeds)
    prog.preload()
    prog.chunk(first=True)
    chunks = [prog.chunk(first=False) for _ in range(n_timed)]
    return seeds, prog.outputs(chunks, range(n))


CELLS = [("paper_rack_orbitcache", "ladder12"), ("paper_rack_nocache", "ladder12"),
         ("paper_rack_orbitcache", "hotin_churn")]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_program_equals_reference(config, traffic):
    cell = tiny_cell(config, traffic)
    seeds, prog = drive(cell)
    ref = harness.replay(cell, seeds, 2, range(len(seeds)))
    numbers, attempted, failed = compare.compare(prog, ref)
    assert numbers == {"state_mismatches": 0, "latency_hist_gap": 0.0, "time_gap_us": 0.0}
    assert attempted == 2 * 3 and failed == 0
    assert compare.verdict(numbers)
    if traffic == "hotin_churn":
        assert any(int(np.sum(p["control"]["update.n_insert"])) for p in prog)


def test_control_in_bfloat16_is_rejected():
    cell = tiny_cell("paper_rack_orbitcache", "ladder12")
    seeds, prog = drive(cell)
    control = harness.replay(cell, seeds, 2, range(len(seeds)), tdt="bfloat16")
    numbers, _, _ = compare.compare(prog, control)
    assert numbers["latency_hist_gap"] > 10 * compare.LIMITS["latency_hist_gap"]
    assert numbers["time_gap_us"] > 10 * compare.LIMITS["time_gap_us"]
    assert not compare.verdict(numbers)


def _patch_chunks(monkeypatch, wrap):
    """Wrap both chunk entries of the fleet: ``run_windows`` (the ladders)
    and ``run_periods`` (a controller period, churn)."""
    for name in ("run_windows", "run_periods"):
        monkeypatch.setattr(BatchedRackSimulator, name,
                            wrap(getattr(BatchedRackSimulator, name)))


def unchanged_state(monkeypatch):
    """Every chunk returns its traces but leaves the fleet's state as it was."""
    def wrap(orig):
        def chunk(self, *a):
            keep = jax.tree.map(jnp.copy, self.carry)
            out = orig(self, *a)
            self.carry = keep
            return out
        return chunk
    _patch_chunks(monkeypatch, wrap)


def half_the_points(monkeypatch):
    """Only the first half of the sweep points is simulated; their results
    stand for the rest."""
    orig = BatchedRackSimulator.run

    def run(self, *a, **k):
        res = orig(self, *a, **k)
        h = len(res) // 2
        return res[:h] + res[:len(res) - h]
    monkeypatch.setattr(BatchedRackSimulator, "run", run)


def altered_answer(monkeypatch):
    """One server's serve count of one window is off by one where the
    chunk produces it."""
    def wrap(orig):
        def chunk(self, *a):
            out = orig(self, *a)
            out["served"] = out["served"].copy()
            out["served"][0, 0, 0] += 1
            return out
        return chunk
    _patch_chunks(monkeypatch, wrap)


@pytest.mark.parametrize("traffic", ["ladder12", "hotin_churn"])
@pytest.mark.parametrize("fault", [unchanged_state, half_the_points, altered_answer])
def test_fault_in_the_timed_path_is_not_correct(fault, traffic, monkeypatch):
    """Each fault, driven through a whole run of the harness.  The churn
    cell keeps its 4 equally loaded points and checks 3 of them, as on the
    chip, so a half-batch fault is seen whichever points the seed draws."""
    fault(monkeypatch)
    cell = tiny_cell("paper_rack_orbitcache", traffic,
                     points=None if traffic == "hotin_churn" else 2)
    result, checks = harness.run(cell, SEED, 0.0, False, time.perf_counter(),
                                 jax.devices()[0])
    assert result["correct"] is False
    assert result["failed"] > 0
    assert checks["state_mismatches"]["value"] > 0
    assert list(result)[-1] == "checks"


def test_sound_run_is_correct():
    cell = tiny_cell("paper_rack_orbitcache", "ladder12")
    result, checks = harness.run(cell, SEED, 0.0, False, time.perf_counter(),
                                 jax.devices()[0])
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["sim_req_per_s"]["value"] > 0
    assert set(checks) == set(compare.LIMITS) | {"compiles_in_window"}
    assert checks["compiles_in_window"]["value"] == 0
