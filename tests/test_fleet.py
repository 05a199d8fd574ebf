"""BatchedRackSimulator: point i of an N-point fleet == a one-point view.

Each batched point must reproduce a one-point fleet (``RackSimulator``)
exactly (same RNG seed => bit-identical traces): points are independent
under vmap, and stacked and shared workload leaves agree.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.kvstore.fleet import BatchedRackSimulator, RackSimulator, _tree_take
from repro.kvstore.simulator import RackConfig
from repro.kvstore.workload import Workload, WorkloadArrays, WorkloadConfig

CFG = RackConfig(scheme="orbitcache", cache_entries=64, num_servers=8,
                 client_batch=256, fetch_lanes=64)


@pytest.fixture(scope="module")
def wl():
    return Workload(WorkloadConfig(num_keys=20_000, offered_rps=2.0e6))


def _one_point(cfg, wl, seed, windows=24):
    sim = RackSimulator(dataclasses.replace(cfg, seed=seed), wl)
    if cfg.scheme == "orbitcache":
        sim.preload(wl.hottest_keys(cfg.cache_entries))
    elif cfg.scheme == "netcache":
        sim.preload(wl.hottest_keys(2000))
    return sim.run_windows(windows)


@pytest.mark.parametrize("scheme", ["orbitcache", "netcache", "nocache"])
def test_batched_points_match_one_point(wl, scheme):
    cfg = dataclasses.replace(CFG, scheme=scheme)
    bsim = BatchedRackSimulator(cfg, wl, seeds=[0, 3])
    if scheme == "netcache":
        bsim.preload([wl.hottest_keys(2000)] * 2)
    else:
        bsim.preload()
    got = bsim.run_windows(24)
    for i, seed in enumerate((0, 3)):
        want = _one_point(cfg, wl, seed)
        for k in want:
            np.testing.assert_array_equal(
                got[k][i], want[k],
                err_msg=f"{scheme} point {i} (seed {seed}): trace {k!r}")


@pytest.mark.parametrize("scheme", ["orbitcache", "netcache"])
def test_batched_preload_matches_one_point_tables(wl, scheme):
    """Per-point preload under stacked-leaf sharing builds the *same tables*
    as preloading each rack in a one-point view — checked on the policy
    state right after preload (not just on end-of-run traces).  The skew
    sweep stacks the CDF leaf while perm/vlen stay shared, so per-point
    preload runs against the shared-leaf machinery."""
    wl2 = Workload(WorkloadConfig(num_keys=20_000, zipf_alpha=0.9,
                                  offered_rps=2.0e6))
    cfg = dataclasses.replace(CFG, scheme=scheme)
    points = [wl, wl2]
    keys = [w.hottest_keys(64 if scheme == "orbitcache" else 2000)
            for w in points]
    bsim = BatchedRackSimulator(cfg, points)
    axes = bsim._wl_axes
    assert axes.table == 0 and axes.cdf == 0 and axes.perm is None
    bsim.preload(keys)
    for i, w in enumerate(points):
        sim = RackSimulator(dataclasses.replace(cfg, seed=cfg.seed + i), w)
        sim.preload(np.asarray(keys[i]))
        want = sim.carry.policy
        got = _tree_take(bsim.carry.policy, i)
        for (path, g), want_leaf in zip(
                jax.tree_util.tree_leaves_with_path(got),
                jax.tree.leaves(want)):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(want_leaf),
                err_msg=f"{scheme} point {i}: policy leaf "
                        f"{jax.tree_util.keystr(path)}")


def test_batched_offered_sweep_orders_load(wl):
    """A load sweep in one fleet: tx scales with per-point offered load."""
    loads = (0.5e6, 1.0e6, 2.0e6)
    bsim = BatchedRackSimulator(CFG, wl, offered_rps=loads)
    bsim.preload()
    bsim.reset_stats()
    res = bsim.run(0.01, chunk_windows=64)
    assert len(res) == 3
    tx = [r.offered_rps(burn_frac=0.0) for r in res]
    assert tx[0] < tx[1] < tx[2]
    for got, load in zip(tx, loads):
        assert abs(got - load) / load < 0.15


def test_batched_shares_unchanged_workload_leaves(wl):
    wl2 = Workload(WorkloadConfig(num_keys=20_000, zipf_alpha=0.9,
                                  offered_rps=2.0e6))
    # same point replicated: every leaf shared
    b1 = BatchedRackSimulator(CFG, wl, n_points=4)
    _, axes = b1._wl_and_axes()
    assert axes == WorkloadArrays(None, None, None, None, wl.zipf_fine_steps)
    # skew sweep: only the Zipf table and CDF are stacked, searched to the
    # deeper of the two points' fine steps
    b2 = BatchedRackSimulator(CFG, [wl, wl2])
    arrs, axes = b2._wl_and_axes()
    assert axes.table == 0 and axes.cdf == 0
    assert axes.perm is None and axes.vlen is None
    assert arrs.table.shape == (2, 2**14) and arrs.cdf.shape == (2, 20_000)
    assert arrs.fine_steps == axes.fine_steps == max(
        wl.zipf_fine_steps, wl2.zipf_fine_steps)


def test_batched_rejects_mismatched_points(wl):
    small = Workload(WorkloadConfig(num_keys=5_000))
    with pytest.raises(ValueError, match="num_keys"):
        BatchedRackSimulator(CFG, [wl, small])
    with pytest.raises(ValueError, match="sweep points"):
        BatchedRackSimulator(CFG, [wl, wl, wl], offered_rps=(1e6, 2e6))


@pytest.mark.parametrize("scheme", ["orbitcache", "netcache"])
def test_preloads_run_the_warm_windows(wl, scheme):
    """Both caches' preloads, one-point view and fleet, end with the same
    16 warm-up windows behind them, so a sweep's first measured window is
    the 17th whichever switch it runs."""
    cfg = dataclasses.replace(CFG, scheme=scheme)
    keys = wl.hottest_keys(64 if scheme == "orbitcache" else 2000)
    sim = RackSimulator(cfg, wl)
    sim.preload(keys)
    bsim = BatchedRackSimulator(cfg, wl, seeds=[cfg.seed])
    bsim.preload([keys])
    warm_us = 16 * cfg.window_us
    assert float(sim.carry.now) == warm_us
    np.testing.assert_array_equal(np.asarray(bsim.carry.now), [warm_us])
    assert int(sim.carry.clients.tx) > 0
    for got, want in zip(jax.tree.leaves(_tree_take(bsim.carry, 0)),
                         jax.tree.leaves(sim.carry)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
