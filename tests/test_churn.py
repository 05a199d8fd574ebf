"""End-to-end Fig. 18 churn through the traced in-scan control plane.

The quick-profile churn story: hot_in_swap makes every cached key cold;
periodic traced cache updates (server CMS reports -> evict/insert ->
F-REQ fetches, all inside the compiled period scan) must re-learn the hot
set and recover throughput — in a one-point view AND batched, with the
two bit-identical on shared seeds (the fleet is a batching transform, not
an approximation).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.kvstore.fleet import BatchedRackSimulator, RackSimulator, _tree_take
from repro.kvstore.simulator import RackConfig
from repro.kvstore.workload import Workload, WorkloadConfig

SMALL = RackConfig(scheme="orbitcache", cache_entries=64, num_servers=8,
                   client_batch=256, fetch_lanes=64,
                   track_popularity=True)


def test_one_point_and_batched_controller_paths_bit_identical():
    """Same seed => a one-point view's period scan and point 0 of a
    two-point fleet produce identical traces AND identical post-run switch
    state, straight through controller periods and a churn event."""
    def fresh_wl():
        return Workload(WorkloadConfig(num_keys=20_000, offered_rps=2.0e6))

    wl_s = fresh_wl()
    sim = RackSimulator(SMALL, wl_s)
    sim.preload(wl_s.hottest_keys(64))

    wl_b = fresh_wl()
    bsim = BatchedRackSimulator(SMALL, wl_b, seeds=[0, 5])
    bsim.preload()

    got_traces = []
    want_traces = []
    for phase in range(2):
        if phase:
            wl_s.hot_in_swap(32)
            wl_b.hot_in_swap(32)
            sim.refresh_workloads()
            bsim.refresh_workloads()
        want_traces.append(sim.run_periods(2, 16))
        got_traces.append(bsim.run_periods(2, 16))
    for want, got in zip(want_traces, got_traces):
        for k in want:
            np.testing.assert_array_equal(got[k][0], want[k], err_msg=k)
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(
                _tree_take(bsim.carry.policy, 0)),
            jax.tree.leaves(sim.carry.policy)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w),
            err_msg=f"policy leaf {jax.tree_util.keystr(path)}")
    assert bsim.controllers[0].active_size == sim.controller.active_size


def test_batched_churn_recovery():
    """Fig. 18 quick profile, batched: every independently-seeded point
    re-learns the swapped hot set inside the vmapped period scans and
    recovers most of its pre-churn throughput."""
    wl = Workload(WorkloadConfig(num_keys=50_000, offered_rps=3e6))
    cfg = RackConfig(scheme="orbitcache", cache_entries=128,
                     track_popularity=True)
    bsim = BatchedRackSimulator(cfg, wl, n_points=2)
    bsim.preload()

    def late_rps(results):
        out = []
        for res in results:
            rx = res.traces["rx_switch"] + res.traces["rx_server"]
            n = len(rx) // 2
            out.append(rx[n:].sum() / (n * cfg.window_us * 1e-6))
        return out

    before = late_rps(bsim.run(0.03))
    wl.hot_in_swap(128)            # every cached key is now cold
    bsim.refresh_workloads()
    bsim.run(0.03, controller_period_s=0.01)   # traced in-scan re-learning
    after = late_rps(bsim.run(0.03))
    for i, (b, a) in enumerate(zip(before, after)):
        assert a > 0.8 * b, (i, b, a)
