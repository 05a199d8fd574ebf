"""Rack-simulator fidelity: the paper's qualitative claims at small scale.

Full-scale reproduction lives in benchmarks/; these tests assert the load-
balancing physics on CPU-sized runs.
"""
import numpy as np
import pytest

from repro.kvstore.fleet import RackSimulator
from repro.kvstore.simulator import RackConfig
from repro.kvstore.workload import Workload, WorkloadConfig, production_workload

N_KEYS = 200_000


@pytest.fixture(scope="module")
def wl():
    return Workload(WorkloadConfig(num_keys=N_KEYS, offered_rps=3.5e6))


def run(scheme, wl, seconds=0.04, **kw):
    cfg = RackConfig(scheme=scheme, cache_entries=128, **kw)
    sim = RackSimulator(cfg, wl)
    if scheme == "orbitcache":
        sim.preload(wl.hottest_keys(128))
    elif scheme == "netcache":
        sim.preload(wl.hottest_keys(10_000))
    return sim, sim.run(seconds)


def test_orbitcache_beats_nocache_under_skew(wl):
    """At a fixed offered load past NoCache's knee: OrbitCache delivers
    more (lossless here), and NoCache's hot-key server is saturated
    (max per-server drop fraction >> 0) while OrbitCache's rack is clean.
    The full knee-ratio reproduction (3.97x) lives in benchmarks/fig09."""
    _, oc = run("orbitcache", wl)
    _, nc = run("nocache", wl)
    assert oc.throughput_rps() > 1.15 * nc.throughput_rps()
    assert oc.max_server_drop_frac() < 0.02
    assert nc.max_server_drop_frac() > 0.3


def test_cache_hits_absorb_head(wl):
    sim, res = run("orbitcache", wl)
    hit_share = res.traces["rx_switch"].sum() / max(
        res.traces["rx_switch"].sum() + res.traces["rx_server"].sum(), 1)
    cov = wl.head_coverage(128)
    assert abs(hit_share - cov) < 0.12, (hit_share, cov)


def test_no_wrong_key_replies_without_updates(wl):
    sim, res = run("orbitcache", wl)
    assert int(res.traces["mismatches"][-1]) == 0


def test_writes_reduce_throughput(wl):
    import dataclasses
    wl_w = Workload(dataclasses.replace(wl.cfg, write_ratio=0.5))
    _, ro = run("orbitcache", wl)
    _, rw = run("orbitcache", wl_w)
    assert rw.throughput_rps() < ro.throughput_rps()


def test_netcache_limited_by_uncacheable_items(wl):
    _, ncache = run("netcache", wl)
    _, ocache = run("orbitcache", wl)
    # NetCache still beats NoCache but loses to OrbitCache on balance
    assert ocache.balancing_efficiency() > ncache.balancing_efficiency()


def test_production_workload_configs():
    for name in "ABCDE":
        cfg = production_workload(name)
        frac_small = dict(cfg.value_sizes)[64]
        assert 0 <= cfg.write_ratio <= 0.25
        assert 0 < frac_small <= 0.95


def test_dynamic_hot_in_recovers():
    wl2 = Workload(WorkloadConfig(num_keys=50_000, offered_rps=3e6))
    cfg = RackConfig(scheme="orbitcache", cache_entries=128,
                     track_popularity=True)
    sim = RackSimulator(cfg, wl2)
    sim.preload(wl2.hottest_keys(128))
    before = sim.run(0.03).throughput_rps(burn_frac=0.5)
    wl2.hot_in_swap(128)           # all cache entries become cold
    sim.refresh_workloads()
    during = sim.run(0.03, controller_period_s=0.01)
    after = sim.run(0.03).throughput_rps(burn_frac=0.5)
    # controller re-learns the hot set and recovers most throughput
    assert after > 0.8 * before, (before, after)
