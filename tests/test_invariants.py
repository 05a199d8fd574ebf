"""Structural data-plane invariants over randomized multi-window traces.

Parity tests prove the fused path equals the composed path; these prove
both are *right*: properties the switch hardware guarantees by
construction must hold of the simulated state after every window, under
randomized load, write mixes and clock advance.  Checked post-window (the
only externally observable instants — mid-subround states are internal):

  * at most one valid (live) orbit line per key, and live lines belong to
    occupied, valid, version-current entries (the §3.7 drop-stale rule);
  * request-table queues within [0, S] and the circular-queue pointer
    algebra ``rear == (front + qlen) mod S``; server FIFOs within
    [0, depth];
  * versions monotone: state-table and store versions never step back;
  * running counters (uint32, ``sat_add``) monotone — never wrap.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import COUNTER_DTYPE, sat_add
from repro.kvstore.fleet import RackSimulator
from repro.kvstore.simulator import RackConfig
from repro.kvstore.workload import Workload, WorkloadConfig

RNG = np.random.default_rng(20260727)


def _check_switch_invariants(sw, prev=None, label=""):
    c = sw.lookup.occupied.shape[0]
    s = sw.reqtab.queue_size
    f = sw.orbit.max_frags
    occ = np.asarray(sw.lookup.occupied)
    kidx = np.asarray(sw.lookup.kidx)
    valid = np.asarray(sw.state.valid)
    version = np.asarray(sw.state.version)
    qlen = np.asarray(sw.reqtab.qlen)
    front = np.asarray(sw.reqtab.front)
    rear = np.asarray(sw.reqtab.rear)
    live = np.asarray(sw.orbit.live).reshape(c, f)
    okidx = np.asarray(sw.orbit.kidx).reshape(c, f)
    over = np.asarray(sw.orbit.version).reshape(c, f)

    # lookup injectivity: occupied entries hold distinct keys
    keys = kidx[occ]
    assert len(set(keys.tolist())) == len(keys), f"{label}: duplicate keys"

    # at most one valid orbit line per key: live fragment-0 lines carry
    # distinct keys, each belonging to an occupied entry for that key
    served_keys = okidx[:, 0][live[:, 0]]
    assert len(set(served_keys.tolist())) == len(served_keys), (
        f"{label}: a key has more than one live orbit line")
    # drop-stale rule (§3.7): every live line's entry is occupied, valid
    # and version-current
    for cc in range(c):
        for ff in range(f):
            if live[cc, ff]:
                assert occ[cc], f"{label}: live line on unoccupied entry {cc}"
                assert valid[cc], f"{label}: live line on invalid entry {cc}"
                assert over[cc, ff] == version[cc], (
                    f"{label}: stale live line at entry {cc} frag {ff}")

    # circular-queue algebra
    assert (qlen >= 0).all() and (qlen <= s).all(), f"{label}: qlen out of range"
    assert (front >= 0).all() and (front < s).all()
    assert (rear >= 0).all() and (rear < s).all()
    np.testing.assert_array_equal(
        rear, (front + qlen) % s,
        err_msg=f"{label}: rear != (front + qlen) mod S")

    # counters: uint32, monotone vs the previous window
    counters = sw.counters
    for name in ("popularity", "hits", "overflow", "cached_reqs"):
        arr = np.asarray(getattr(counters, name))
        assert arr.dtype == np.uint32, f"{label}: {name} not uint32"
        if prev is not None:
            before = np.asarray(getattr(prev.counters, name))
            assert (arr.astype(np.uint64) >= before.astype(np.uint64)).all(), (
                f"{label}: counter {name} stepped backwards (wrap?)")
    if prev is not None:
        pv = np.asarray(prev.state.version)
        assert (version >= pv).all(), f"{label}: state version decreased"


def _check_server_invariants(servers, cfg, prev=None, label=""):
    qlen = np.asarray(servers.qlen)
    assert (qlen >= 0).all() and (qlen <= cfg.server_queue).all(), (
        f"{label}: server backlog out of range")
    front = np.asarray(servers.front)
    rear = np.asarray(servers.rear)
    q = cfg.server_queue
    assert (front >= 0).all() and (front < q).all()
    assert (rear >= 0).all() and (rear < q).all()
    np.testing.assert_array_equal(
        rear, (front + qlen) % q,
        err_msg=f"{label}: server ring pointer algebra broken")
    if prev is not None:
        assert (np.asarray(servers.key_version)
                >= np.asarray(prev.key_version)).all(), (
            f"{label}: store version decreased")
        assert (np.asarray(servers.served)
                >= np.asarray(prev.served)).all()
        assert (np.asarray(servers.dropped)
                >= np.asarray(prev.dropped)).all()


@pytest.mark.parametrize("seed", [0, 1])
def test_orbitcache_invariants_over_randomized_trace(seed):
    """Random load/write-mix staircase; invariants hold after every chunk."""
    rng = np.random.default_rng(seed)
    wl = Workload(WorkloadConfig(num_keys=3_000, offered_rps=1.0e6,
                                 write_ratio=0.1))
    cfg = RackConfig(scheme="orbitcache", cache_entries=16, num_servers=2,
                     client_batch=64, fetch_lanes=16, value_pad=64,
                     server_queue=16, subrounds=2, seed=seed)
    sim = RackSimulator(cfg, wl)
    sim.preload(wl.hottest_keys(16))
    prev_sw, prev_srv = None, None
    for chunk in range(4):
        sim.set_offered(float(rng.uniform(0.3, 2.5)) * 1.0e6)
        sim.set_write_ratio(float(rng.uniform(0.0, 0.4)))
        sim.run_windows(4)
        sw = sim.carry.policy
        _check_switch_invariants(sw, prev_sw, label=f"chunk {chunk}")
        _check_server_invariants(sim.carry.servers, cfg, prev_srv,
                                 label=f"chunk {chunk}")
        # snapshot to host: the next chunk donates (deletes) these buffers
        prev_sw = jax.tree.map(np.asarray, sw)
        prev_srv = jax.tree.map(np.asarray, sim.carry.servers)


def test_invariants_survive_controller_churn():
    """Cache updates (eviction + CacheIdx inheritance, §3.8) are the
    adversarial case for the one-line-per-key rule: versions bump, lines
    die, new keys inherit slots — invariants must hold straight through."""
    wl = Workload(WorkloadConfig(num_keys=2_000, offered_rps=1.0e6))
    cfg = RackConfig(scheme="orbitcache", cache_entries=16, num_servers=2,
                     client_batch=64, fetch_lanes=16, value_pad=64,
                     server_queue=16, subrounds=2,
                     track_popularity=True)
    sim = RackSimulator(cfg, wl)
    sim.preload(wl.hottest_keys(16))
    for period in range(3):
        sim.run_windows(4)
        sim._control_plane_update()  # host-side eviction/insert surgery
        sim.run_windows(4)
        # popularity counters reset on update, so no cross-period
        # monotonicity here — the structural invariants are the point
        _check_switch_invariants(sim.carry.policy, None,
                                 label=f"period {period}")


def test_netcache_invariants_over_randomized_trace():
    wl = Workload(WorkloadConfig(num_keys=3_000, offered_rps=1.0e6))
    cfg = RackConfig(scheme="netcache", cache_entries=16, num_servers=2,
                     client_batch=64, fetch_lanes=16, value_pad=64,
                     server_queue=16, subrounds=2, netcache_entries=500)
    sim = RackSimulator(cfg, wl)
    sim.preload(wl.hottest_keys(500))
    prev_hits = 0
    for chunk in range(3):
        sim.set_offered(float(RNG.uniform(0.3, 2.0)) * 1.0e6)
        sim.run_windows(4)
        st = sim.carry.policy
        vlen = np.asarray(st.vlen)
        limit = cfg.netcache_value_limit
        assert (vlen >= 0).all() and (vlen <= limit).all(), (
            "netcache stored a value beyond its hardware limit")
        hits = int(st.hits)
        assert st.hits.dtype == COUNTER_DTYPE
        assert hits >= prev_hits, "netcache hit counter wrapped"
        prev_hits = hits
        _check_server_invariants(sim.carry.servers, cfg)


def test_sat_add_counters_never_wrap_randomized():
    """sat_add fuzz: random accumulate sequences clamp at the ceiling and
    are monotone for non-negative deltas — including int32 deltas that
    would sign-wrap under naive promotion."""
    top = np.uint64(np.iinfo(np.uint32).max)
    for trial in range(50):
        rng = np.random.default_rng(1000 + trial)
        start = np.uint32(rng.integers(0, np.iinfo(np.uint32).max,
                                       dtype=np.uint64))
        acc = jnp.asarray(start, COUNTER_DTYPE)
        model = np.uint64(start)
        for _ in range(8):
            delta = int(rng.integers(0, 2**31 - 1))
            acc = sat_add(acc, jnp.int32(delta))
            model = min(model + np.uint64(delta), top)
            assert np.uint64(int(acc)) == model, (
                f"trial {trial}: sat_add diverged from the saturating model")
