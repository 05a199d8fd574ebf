"""Traced control plane vs the host oracle (paper §3.8, §3.10).

``controller_step`` must be BIT-identical to ``CacheController.update``
over randomized periods — same merge (estimates summed across reports),
same (score desc, key asc) ranking, same CacheIdx inheritance, same
counter resets, same §3.10 sizing — on every switch-state leaf and every
emitted fetch.  Runs on the active kernel backend (the merge goes through
``kernels.hot_gather``), so the CI kernel-parity job re-checks it under
the Pallas interpreter.

Also the regression tests for the three controller fixes:

* period accumulators (popularity / overflow / cached_reqs) are
  read-and-reset each period;
* a key reported by several servers scores the SUM of its estimates;
* a zero-traffic period holds the dynamic size.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.controller import (
    CacheController,
    ControllerConfig,
    controller_step,
)
from repro.core.hashing import hash128_u32
from repro.core.types import COUNTER_DTYPE, init_switch_state


# ---------------------------------------------------------------------------
# randomized state/report builders
# ---------------------------------------------------------------------------
def random_state(rng, cap=16, f=2, universe=200):
    """A structurally-consistent random switch state (distinct cached keys,
    matching hkeys, random validity/versions/liveness, random period
    counters)."""
    sw = init_switch_state(cap, queue_size=4, value_pad=32, max_frags=f)
    n_occ = int(rng.integers(0, cap + 1))
    slots = rng.choice(cap, size=n_occ, replace=False)
    keys = rng.choice(universe, size=n_occ, replace=False).astype(np.int32)
    occ = np.zeros(cap, bool)
    occ[slots] = True
    kidx = np.full(cap, -1, np.int32)
    kidx[slots] = keys
    return sw._replace(
        lookup=sw.lookup._replace(
            hkeys=hash128_u32(jnp.asarray(kidx)),
            occupied=jnp.asarray(occ),
            kidx=jnp.asarray(kidx),
        ),
        state=sw.state._replace(
            valid=jnp.asarray(occ & (rng.random(cap) < 0.7)),
            version=jnp.asarray(rng.integers(0, 5, cap).astype(np.int32)),
        ),
        orbit=sw.orbit._replace(
            live=jnp.asarray(np.repeat(occ, f) & (rng.random(cap * f) < 0.5)),
        ),
        counters=sw.counters._replace(
            popularity=jnp.asarray(
                rng.integers(0, 1000, cap).astype(np.uint32) * occ),
            overflow=jnp.asarray(rng.integers(0, 60), COUNTER_DTYPE),
            cached_reqs=jnp.asarray(rng.integers(0, 5000), COUNTER_DTYPE),
            hits=jnp.asarray(rng.integers(0, 9999), COUNTER_DTYPE),
        ),
    )


def random_reports(rng, n_srv=3, k=8, universe=200):
    """Per-server (top_kidx, est) pairs with empty lanes and cross-server
    duplicates (the summed-merge case)."""
    reps = []
    for _ in range(n_srv):
        nk = int(rng.integers(0, k + 1))
        ks = np.full(k, -1, np.int32)
        ks[:nk] = rng.choice(universe, size=nk, replace=False)
        es = rng.integers(0, 2000, k).astype(np.int32) * (ks >= 0)
        reps.append((ks, es))
    return reps


def assert_state_equal(got, want, msg=""):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w),
            err_msg=f"{msg} leaf {jax.tree_util.keystr(path)}")


def run_both(sw, reports, ctrl):
    """Feed identical inputs to the host oracle and the traced step."""
    ovf, cr = sw.counters.overflow, sw.counters.cached_reqs
    act0 = jnp.int32(ctrl.active_size)
    host_sw, info = ctrl.update(sw, reports, int(ovf), int(cr))
    rk = jnp.concatenate([jnp.asarray(k) for k, _ in reports])
    re_ = jnp.concatenate([jnp.asarray(e) for _, e in reports])
    tr_sw, act, upd = controller_step(sw, rk, re_, ovf, cr, act0, ctrl.cfg)
    return host_sw, info, tr_sw, act, upd


# ---------------------------------------------------------------------------
# bit-identity (the tentpole acceptance criterion)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dynamic", [False, True])
def test_traced_matches_host_over_random_periods(dynamic):
    rng = np.random.default_rng(42 + dynamic)
    for trial in range(12):
        cap = int(rng.integers(4, 24))
        cfg = ControllerConfig(
            active_size=int(rng.integers(2, cap + 4)),
            min_size=2, max_size=cap + 4, size_step=3,
            dynamic_sizing=dynamic,
            overflow_threshold=float(rng.choice([0.01, 0.05])),
        )
        ctrl = CacheController(cfg)
        sw = random_state(rng, cap=cap)
        # chain several periods on the SAME evolving state: the traced
        # output feeds the next period, so any divergence compounds
        for period in range(3):
            host_sw, info, tr_sw, act, upd = run_both(
                sw, random_reports(rng), ctrl)
            assert int(act) == ctrl.active_size, (trial, period)
            assert_state_equal(tr_sw, host_sw, f"trial {trial} period {period}")
            n_f = int(upd.n_insert)
            got = [(int(k), int(c)) for k, c in
                   zip(upd.fetch_kidx[:n_f], upd.fetch_cidx[:n_f])]
            assert got == info.fetches, (trial, period)
            assert bool(np.all(np.asarray(upd.fetch_valid)[n_f:] == False))  # noqa: E712
            n_e = int(upd.n_evict)
            assert [int(x) for x in upd.evicted_kidx[:n_e]] == list(info.evicted)
            # next period: fresh traffic counters on the traced state
            sw = tr_sw._replace(counters=tr_sw.counters._replace(
                popularity=jnp.asarray(
                    rng.integers(0, 500, cap).astype(np.uint32)
                    * np.asarray(tr_sw.lookup.occupied)),
                overflow=jnp.asarray(rng.integers(0, 40), COUNTER_DTYPE),
                cached_reqs=jnp.asarray(rng.integers(0, 3000), COUNTER_DTYPE),
            ))


def test_traced_matches_host_vmapped():
    """The same update vmapped over a rack axis (the fleet/fabric form)."""
    rng = np.random.default_rng(7)
    cfg = ControllerConfig(active_size=10, min_size=2, max_size=20,
                           size_step=2, dynamic_sizing=True)
    states, reports, hosts = [], [], []
    for i in range(3):
        sw = random_state(rng, cap=12)
        reps = random_reports(rng)
        ctrl = CacheController(cfg)
        host_sw, _ = ctrl.update(sw, reps, int(sw.counters.overflow),
                                 int(sw.counters.cached_reqs))
        states.append(sw)
        reports.append(reps)
        hosts.append((host_sw, ctrl.active_size))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    rk = jnp.stack([jnp.concatenate([jnp.asarray(k) for k, _ in r])
                    for r in reports])
    re_ = jnp.stack([jnp.concatenate([jnp.asarray(e) for _, e in r])
                     for r in reports])
    act0 = jnp.full((3,), cfg.active_size, jnp.int32)
    v_sw, v_act, _ = jax.vmap(
        lambda s, k, e, a: controller_step(
            s, k, e, s.counters.overflow, s.counters.cached_reqs, a, cfg)
    )(stacked, rk, re_, act0)
    for i, (host_sw, host_act) in enumerate(hosts):
        assert int(v_act[i]) == host_act
        got_i = jax.tree.map(lambda x: x[i], v_sw)
        assert_state_equal(got_i, host_sw, f"point {i}")


def test_fetch_batch_parity():
    """traced_fetch_batch == build_fetch_batch for the same fetch list."""
    from repro.kvstore.simulator import (RackConfig, build_fetch_batch,
                                         traced_fetch_batch)
    cfg = RackConfig(fetch_lanes=16, subrounds=4, value_pad=64,
                     num_servers=8)
    vlen = jnp.asarray(np.random.default_rng(0).integers(
        16, 64, 100).astype(np.int32))
    fetches = [(17, 3), (42, 0), (99, 7)]
    want = build_fetch_batch(cfg, vlen, fetches)
    cap = 8
    fk = jnp.asarray([17, 42, 99] + [-1] * (cap - 3), jnp.int32)
    fv = jnp.asarray([True] * 3 + [False] * (cap - 3))
    got = traced_fetch_batch(cfg, vlen, fk, fv)
    assert_state_equal(got, want, "fetch batch")


# ---------------------------------------------------------------------------
# fix 1: period accumulators are read-and-reset
# ---------------------------------------------------------------------------
def test_period_counters_reset_each_update():
    rng = np.random.default_rng(0)
    sw = random_state(rng, cap=8)
    ctrl = CacheController(ControllerConfig(active_size=8))
    sw2, _ = ctrl.update(sw, [], int(sw.counters.overflow),
                         int(sw.counters.cached_reqs))
    assert int(sw2.counters.overflow) == 0
    assert int(sw2.counters.cached_reqs) == 0
    assert int(sw2.counters.popularity.sum()) == 0
    # hits is a lifetime counter, not a period accumulator
    assert int(sw2.counters.hits) == int(sw.counters.hits)


def test_two_consecutive_periods_size_from_period_counts():
    """§3.10 sizing must see PER-PERIOD ratios.  Period 1 overflows hard
    (shrink); period 2 is clean (grow).  With lifetime-cumulative
    accumulators the second ratio would stay ~5% (above threshold) and
    the size would keep shrinking."""
    cfg = ControllerConfig(active_size=64, min_size=16, max_size=128,
                           size_step=16, dynamic_sizing=True,
                           overflow_threshold=0.01)
    rng = np.random.default_rng(1)
    sw = random_state(rng, cap=8)

    def with_counts(sw, ovf, cached):
        return sw._replace(counters=sw.counters._replace(
            overflow=jnp.asarray(ovf, COUNTER_DTYPE),
            cached_reqs=jnp.asarray(cached, COUNTER_DTYPE)))

    # the in-scan read-and-reset loop: counters come FROM the state
    ctrl = CacheController(cfg)
    sw = with_counts(sw, 500, 10_000)                       # 5% > 1%
    sw, _ = ctrl.update(sw, [], int(sw.counters.overflow),
                        int(sw.counters.cached_reqs))
    assert ctrl.active_size == 48                            # shrank
    # period 2 adds clean traffic ON TOP of the (reset) accumulators
    sw = with_counts(sw, int(sw.counters.overflow) + 0,
                     int(sw.counters.cached_reqs) + 10_000)  # 0% < 1%
    sw, _ = ctrl.update(sw, [], int(sw.counters.overflow),
                        int(sw.counters.cached_reqs))
    assert ctrl.active_size == 64                            # grew back

    # end-to-end: the traced period scan feeds per-period counters too
    tr_sw = with_counts(random_state(rng, cap=8), 500, 10_000)
    act = jnp.int32(64)
    tr_sw, act, _ = controller_step(
        tr_sw, jnp.full((4,), -1, jnp.int32), jnp.zeros((4,), jnp.int32),
        tr_sw.counters.overflow, tr_sw.counters.cached_reqs, act, cfg)
    assert int(act) == 48
    tr_sw = with_counts(tr_sw, int(tr_sw.counters.overflow),
                        int(tr_sw.counters.cached_reqs) + 10_000)
    tr_sw, act, _ = controller_step(
        tr_sw, jnp.full((4,), -1, jnp.int32), jnp.zeros((4,), jnp.int32),
        tr_sw.counters.overflow, tr_sw.counters.cached_reqs, act, cfg)
    assert int(act) == 64


def test_simulator_counters_reflect_only_current_period():
    """Through the real rack: after a control-plane update the switch
    counters restart from zero, so the next period's overflow equals that
    period's trace, not the lifetime total."""
    from repro.kvstore.fleet import RackSimulator
    from repro.kvstore.simulator import RackConfig
    from repro.kvstore.workload import Workload, WorkloadConfig
    wl = Workload(WorkloadConfig(num_keys=5_000, offered_rps=1.5e6))
    cfg = RackConfig(scheme="orbitcache", cache_entries=16, num_servers=2,
                     client_batch=128, fetch_lanes=16, value_pad=64,
                     subrounds=2, track_popularity=True)
    sim = RackSimulator(cfg, wl)
    sim.preload(wl.hottest_keys(16))
    sim.run_windows(8)
    sim._control_plane_update()
    assert int(sim.carry.policy.counters.overflow) == 0
    assert int(sim.carry.policy.counters.cached_reqs) == 0
    t = sim.run_windows(8)
    # cached_reqs accumulated post-reset == this period's hit trace
    assert int(sim.carry.policy.counters.cached_reqs) == int(t["hits"].sum())


# ---------------------------------------------------------------------------
# fix 2: estimates are summed across server reports
# ---------------------------------------------------------------------------
def test_reports_summed_across_servers():
    """Key 7's traffic spreads over three servers (60 each); key 9 hits one
    server for 100.  Summed, 7 (180) outranks 9 (100); first-report-wins
    would have ranked 7 at 60 and inserted 9."""
    sw = init_switch_state(4, queue_size=4, value_pad=32)
    cfg = ControllerConfig(active_size=1)
    reports = [
        (np.asarray([7], np.int32), np.asarray([60], np.int32)),
        (np.asarray([9], np.int32), np.asarray([100], np.int32)),
        (np.asarray([7], np.int32), np.asarray([60], np.int32)),
        (np.asarray([7], np.int32), np.asarray([60], np.int32)),
    ]
    ctrl = CacheController(cfg)
    host_sw, info = ctrl.update(sw, reports)
    assert list(info.inserted) == [7]
    rk = jnp.asarray([7, 9, 7, 7], jnp.int32)
    re_ = jnp.asarray([60, 100, 60, 60], jnp.int32)
    tr_sw, _, upd = controller_step(
        sw, rk, re_, sw.counters.overflow, sw.counters.cached_reqs,
        jnp.int32(1), cfg)
    assert int(upd.n_insert) == 1 and int(upd.fetch_kidx[0]) == 7
    assert_state_equal(tr_sw, host_sw)


# ---------------------------------------------------------------------------
# fix 3: zero-traffic periods hold the dynamic size
# ---------------------------------------------------------------------------
def test_resize_holds_on_zero_traffic():
    cfg = ControllerConfig(active_size=64, min_size=16, max_size=128,
                           size_step=16, dynamic_sizing=True)
    ctrl = CacheController(cfg)
    ctrl.resize(0, 0)
    assert ctrl.active_size == 64          # held (was: grew to 80)
    ctrl.resize(0, 1000)
    assert ctrl.active_size == 80          # clean traffic grows
    ctrl.resize(500, 1000)
    assert ctrl.active_size == 64          # 50% overflow shrinks
    # traced twin agrees on all three
    from repro.core.controller import _traced_resize
    for ovf, cr, want in ((0, 0, 64), (0, 1000, 80), (500, 1000, 48)):
        act, _ = _traced_resize(cfg, jnp.int32(64),
                                jnp.asarray(ovf, COUNTER_DTYPE),
                                jnp.asarray(cr, COUNTER_DTYPE))
        assert int(act) == want


# ---------------------------------------------------------------------------
# spine mode: live installs + re-validation
# ---------------------------------------------------------------------------
def test_install_live_revalidates_and_installs_lines():
    cap, f = 4, 1
    sw = init_switch_state(cap, queue_size=4, value_pad=32, max_frags=f)
    kidx = np.asarray([10, 11, -1, -1], np.int32)
    occ = np.asarray([True, True, False, False])
    sw = sw._replace(
        lookup=sw.lookup._replace(hkeys=hash128_u32(jnp.asarray(kidx)),
                                  occupied=jnp.asarray(occ),
                                  kidx=jnp.asarray(kidx)),
        # entry 0 valid; entry 1 was invalidated by a remote write
        state=sw.state._replace(valid=jnp.asarray([True, False, False, False]),
                                version=jnp.asarray([3, 5, 0, 0], np.int32)),
        orbit=sw.orbit._replace(live=jnp.asarray([True, False, False, False]),
                                kidx=jnp.asarray([10, 11, -1, -1], np.int32),
                                version=jnp.asarray([3, 4, 0, 0], np.int32),
                                vlen=jnp.asarray([32, 48, 0, 0], np.int32)),
        counters=sw.counters._replace(
            popularity=jnp.asarray([500, 400, 0, 0], np.uint32)),
    )
    cfg = ControllerConfig(active_size=3)
    rk = jnp.asarray([20, -1], jnp.int32)
    rv = jnp.asarray([64, 0], jnp.int32)
    sw2, _, upd = controller_step(
        sw, rk, jnp.asarray([50, 0], jnp.int32),
        sw.counters.overflow, sw.counters.cached_reqs, jnp.int32(3), cfg,
        install_live=True, report_vlen=rv)
    # kept entries: 10 untouched, 11 re-validated with a version bump and
    # a refreshed live line
    assert bool(sw2.state.valid[0]) and int(sw2.state.version[0]) == 3
    assert bool(sw2.state.valid[1]) and int(sw2.state.version[1]) == 6
    assert bool(sw2.orbit.live[1]) and int(sw2.orbit.version[1]) == 6
    assert int(sw2.orbit.vlen[1]) == 48      # metadata kept
    # insert 20 went live immediately (no F-REQ path), vlen from the report
    c20 = int(np.asarray(sw2.lookup.kidx).tolist().index(20))
    assert bool(sw2.lookup.occupied[c20]) and bool(sw2.state.valid[c20])
    assert bool(sw2.orbit.live[c20])
    assert int(sw2.orbit.kidx[c20]) == 20
    assert int(sw2.orbit.vlen[c20]) == 64
    assert int(upd.n_insert) == 1
