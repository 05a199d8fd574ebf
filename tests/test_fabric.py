"""Cross-rack spine fabric: exchange primitives + topology regressions.

The load-bearing guarantee: with rack-local fraction 1.0 the fabric is
bit-identical, rack by rack and leaf by leaf, to R independent racks
(``BatchedRackSimulator``) — the spine runs but never receives a lane, the
forward lanes stay all-invalid, and the rack RNG streams are untouched.
Everything else (one-hot lane exchange, locality draws, global-key homing,
conservation of remote traffic through the spine) is unit-tested on top.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fabric as fb
from repro.core.types import OP_R_REQ, empty_batch
from repro.kvstore.fabric_sim import FabricConfig, preload_spine
from repro.kvstore.fleet import (
    BatchedFabricSimulator,
    BatchedRackSimulator,
    FabricSimulator,
)
from repro.kvstore.simulator import RackConfig
from repro.kvstore.workload import Workload, WorkloadConfig

RNG = np.random.default_rng(7)


def _small_cfg(scheme="orbitcache"):
    return RackConfig(scheme=scheme, cache_entries=16, num_servers=2,
                      client_batch=64, fetch_lanes=16, value_pad=64,
                      server_queue=16, subrounds=2)


def _small_wl(**kw):
    kw.setdefault("num_keys", 2000)
    kw.setdefault("offered_rps", 8e5)
    return Workload(WorkloadConfig(**kw))


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------
def test_global_key_roundtrip():
    kidx = jnp.asarray(RNG.integers(0, 10_000, 256), jnp.int32)
    home = jnp.asarray(RNG.integers(0, 5, 256), jnp.int32)
    gk = fb.global_key(kidx, home, 5)
    lk, h = fb.split_global_key(gk, 5)
    np.testing.assert_array_equal(np.asarray(lk), np.asarray(kidx))
    np.testing.assert_array_equal(np.asarray(h), np.asarray(home))
    # distinct (kidx, home) pairs map to distinct global ids
    assert len(set(np.asarray(gk).tolist())) == len(
        {(int(k), int(r)) for k, r in zip(np.asarray(kidx), np.asarray(home))})


def test_draw_targets_locality_extremes():
    shape = (4, 2, 64)
    rng = jax.random.PRNGKey(0)
    src = np.arange(4)[:, None, None]
    t1 = np.asarray(fb.draw_targets(rng, 4, jnp.float32(1.0), shape))
    assert (t1 == src).all(), "locality 1.0 must be deterministically local"
    t0 = np.asarray(fb.draw_targets(rng, 4, jnp.float32(0.0), shape))
    assert (t0 != src).all(), "locality 0.0 must never stay local"
    assert t0.min() >= 0 and t0.max() < 4
    # middle ground: both kinds present, all targets in range
    tm = np.asarray(fb.draw_targets(rng, 4, jnp.float32(0.5), shape))
    assert (tm == src).any() and (tm != src).any()
    assert tm.min() >= 0 and tm.max() < 4


def test_draw_targets_single_rack_degenerates():
    t = np.asarray(fb.draw_targets(jax.random.PRNGKey(1), 1,
                                   jnp.float32(0.3), (1, 2, 8)))
    assert (t == 0).all()


def test_compact_slots_order_and_drops():
    mask = jnp.asarray([0, 1, 0, 1, 1, 0, 1, 1], bool)
    writer, written, dropped = fb.compact_slots(mask, 3)
    # first three masked lanes (1, 3, 4) claim slots 0..2 in lane order
    np.testing.assert_array_equal(np.asarray(writer), [1, 3, 4])
    assert np.asarray(written).all()
    assert int(dropped) == 2  # lanes 6, 7 overflow the width
    # wide enough: nothing drops, tail unwritten
    writer, written, dropped = fb.compact_slots(mask, 8)
    assert int(dropped) == 0
    np.testing.assert_array_equal(np.asarray(written),
                                  [1, 1, 1, 1, 1, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(writer)[:5], [1, 3, 4, 6, 7])


def test_exchange_roundtrip_preserves_packets():
    """Rack lanes -> spine rows -> per-rack forward lanes: every surviving
    packet keeps its payload and lands at its home rack in arrival order."""
    r, s, lanes, w_spine, w_fwd = 3, 2, 8, 16, 8
    pk = empty_batch(r * s * lanes, value_pad=16)
    kidx = jnp.arange(r * s * lanes, dtype=jnp.int32)
    pk = pk._replace(op=jnp.full_like(kidx, OP_R_REQ), kidx=kidx,
                     seq=kidx * 7, valid=jnp.ones_like(kidx, bool))
    batches = jax.tree.map(
        lambda a: a.reshape((r, s, lanes) + a.shape[1:]), pk)
    tgt = jnp.asarray(RNG.integers(0, r, (r, s, lanes)), jnp.int32)
    src = jnp.arange(r, dtype=jnp.int32)[:, None, None]
    remote = jnp.asarray(RNG.random((r, s, lanes)) < 0.5) & (tgt != src)

    template = empty_batch(w_spine, value_pad=16)
    spine, writer, written, dropped = fb.exchange_to_spine(
        batches, remote, template)
    assert int(dropped) == 0  # wide enough for this case
    assert int(jnp.sum(spine.valid)) == int(jnp.sum(remote))
    tgt_s = jax.vmap(lambda t, wr, wn: jnp.where(wn, t[wr], 0))(
        fb.racks_to_rows(tgt), writer, written)

    # every spine lane carries a genuinely remote packet, fields intact
    kidx_rows = np.asarray(fb.racks_to_rows(batches.kidx))
    for row in range(s):
        wn = np.asarray(written[row])
        wr = np.asarray(writer[row])
        got_k = np.asarray(spine.kidx[row])[wn]
        np.testing.assert_array_equal(got_k, kidx_rows[row][wr[wn]])
        np.testing.assert_array_equal(np.asarray(spine.seq[row])[wn],
                                      got_k * 7)
        # arrival order is preserved: writers are strictly increasing
        assert (np.diff(wr[wn]) > 0).all()

    fwd_template = empty_batch(w_fwd, value_pad=16)
    rack_fwd, drops2 = fb.exchange_to_racks(
        spine, spine.valid, tgt_s, r, fwd_template)
    # conservation: forwarded + dropped == spine lanes
    n_fwd = int(jnp.sum(rack_fwd.valid))
    assert n_fwd + int(drops2) == int(jnp.sum(spine.valid))
    # every forwarded packet sits in its home rack's buffer (kidx doubles
    # as the flat origin index, so its drawn target is directly recoverable)
    tgt_flat = np.asarray(tgt).reshape(-1)
    for rr in range(r):
        v = np.asarray(rack_fwd.valid[rr])
        ks = np.asarray(rack_fwd.kidx[rr])[v]
        assert (tgt_flat[ks] == rr).all()


# ---------------------------------------------------------------------------
# topology regressions
# ---------------------------------------------------------------------------
def _assert_rack_trees_equal(a, b):
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb_ = jax.tree.leaves(b)
    assert len(fa) == len(fb_)
    for (path, la), lb in zip(fa, fb_):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"fabric/fleet divergence at "
                    f"{jax.tree_util.keystr(path)}")


def test_fabric_locality_one_bit_identical_to_independent_racks():
    """ACCEPTANCE: at rack-local fraction 1.0 every per-rack leaf (switch
    policy, servers, clients, pending, RNG, clocks) evolves bit-identically
    to a BatchedRackSimulator fleet of independent racks — through preload,
    warm-up and measured windows."""
    wl = _small_wl(write_ratio=0.05)
    cfg = _small_cfg("orbitcache")
    fcfg = FabricConfig(n_racks=3, local_frac=1.0, spine_scheme="orbitcache",
                        spine_lanes=64, fwd_lanes=32, spine_cache_entries=32)
    fsim = FabricSimulator(cfg, fcfg, wl)
    bsim = BatchedRackSimulator(cfg, wl, n_points=3)
    fsim.preload(warm_windows=16)  # fleet.preload warms 16 windows
    bsim.preload()
    _assert_rack_trees_equal(fsim.carry.racks, bsim.carry)
    f_out = fsim.run_windows(6)
    b_out = bsim.run_windows(6)
    _assert_rack_trees_equal(fsim.carry.racks, bsim.carry)
    # per-rack metrics agree too ([n, R] vs [R, n] layouts)
    for k in ("tx", "rx_switch", "rx_server", "hits", "fwd"):
        np.testing.assert_array_equal(f_out[f"rack_{k}"],
                                      np.moveaxis(b_out[k], 0, 1),
                                      err_msg=k)
    # and the spine saw nothing
    assert f_out["spine_remote"].sum() == 0
    assert f_out["spine_fwd"].sum() == 0
    assert f_out["spine_in_drops"].sum() == 0
    assert f_out["spine_fwd_drops"].sum() == 0


@pytest.mark.parametrize("spine_scheme", ["orbitcache", "netcache", "nocache"])
def test_fabric_remote_traffic_conservation(spine_scheme):
    """Every remote request is spine-served, forwarded down, absorbed into
    a spine queue (orbitcache), or dropped at a full lane buffer — nothing
    vanishes, nothing is double-counted.

    ``spine_fwd`` counts the spine's ROUTE_SERVER egress *before* the
    forward-lane compaction, so the exact per-window laws are:
      nocache:    fwd + in_drops == remote            (no serving, no queues)
      netcache:   served + fwd + in_drops == remote   (serves are same-window)
      orbitcache: fwd + in_drops <= remote            (absorbed lanes queue),
                  and serves over a trace are bounded by remote + the spine
                  queue capacity carried in from warm-up.
    """
    wl = _small_wl()
    cfg = _small_cfg("orbitcache")
    fcfg = FabricConfig(n_racks=3, local_frac=0.5, spine_scheme=spine_scheme,
                        spine_lanes=96, fwd_lanes=96, spine_cache_entries=32,
                        spine_queue_size=8)
    sim = FabricSimulator(cfg, fcfg, wl)
    sim.preload(warm_windows=2)
    rx0 = int(sim.carry.spine_clients.rx_switch)  # warm-up serves
    out = sim.run_windows(8)
    remote = int(out["spine_remote"].sum())
    served = int(out["spine_served"].sum())
    fwd = int(out["spine_fwd"].sum())
    in_drops = int(out["spine_in_drops"].sum())
    assert remote > 0
    if spine_scheme == "nocache":
        assert served == 0
        assert fwd + in_drops == remote
    elif spine_scheme == "netcache":
        assert fwd > 0
        assert served + fwd + in_drops == remote
    else:  # orbitcache
        assert fwd > 0
        assert fwd + in_drops <= remote
        queue_cap = fcfg.spine_cache_entries * fcfg.spine_queue_size
        assert served <= remote + queue_cap
        # spine-served requests really were answered at the spine tier
        assert served == int(sim.carry.spine_clients.rx_switch) - rx0


def test_fabric_remote_requests_reach_owning_rack_servers():
    """With locality < 1 and a nocache spine, forwarded requests land on
    the HOME rack's servers: total server arrivals across racks rise on
    the racks receiving forwards, and forwarded lanes carry local kidx."""
    wl = _small_wl()
    cfg = _small_cfg("nocache")
    fcfg = FabricConfig(n_racks=2, local_frac=0.5, spine_scheme="nocache",
                        spine_lanes=128, fwd_lanes=128)
    sim = FabricSimulator(cfg, fcfg, wl)
    out = sim.run_windows(8)
    # the rack tier forwarded more than its local requests alone: the
    # fabric injected the remote half back into the racks
    assert int(out["spine_fwd"].sum()) > 0
    served_total = out["rack_served"].sum()
    assert served_total > 0


@pytest.mark.parametrize("scheme", ["orbitcache", "netcache", "nocache"])
def test_batched_fabric_matches_one_point_fabric(scheme):
    """The vmapped fabric sweep is bit-identical per point to one-point
    views (``FabricSimulator``) with the same seeds/locality — through the
    fleet's own preload of every rack scheme, a window chunk and ``run``."""
    wl = _small_wl()
    cfg = _small_cfg(scheme)
    fcfg = FabricConfig(n_racks=2, spine_scheme="orbitcache",
                        spine_lanes=64, fwd_lanes=32, spine_cache_entries=32)
    fracs = [1.0, 0.5]
    bf = BatchedFabricSimulator(cfg, fcfg, wl, local_fracs=fracs)
    bf.preload(warm_windows=2)
    one_point = []
    for i, frac in enumerate(fracs):
        s = FabricSimulator(dataclasses.replace(cfg, seed=cfg.seed + 1000 * i),
                            dataclasses.replace(fcfg, local_frac=frac), wl)
        s.preload(warm_windows=2)
        s.run_windows(4)
        one_point.append(s)
    bf.run_windows(4)
    for i, s in enumerate(one_point):
        _assert_rack_trees_equal(
            jax.tree.map(lambda x: x[i], bf.carry), s.carry)
    seconds = 4 * cfg.window_us * 1e-6
    got = bf.run(seconds, chunk_windows=4)
    for i, s in enumerate(one_point):
        want = s.run(seconds, chunk_windows=4)
        for g, w in zip(got[i].racks, want.racks):
            _assert_rack_trees_equal(g.traces, w.traces)
        _assert_rack_trees_equal(got[i].spine, want.spine)


def test_spine_preload_installs_global_hot_set():
    wl = _small_wl()
    cfg = _small_cfg()
    fcfg = FabricConfig(n_racks=4, spine_scheme="orbitcache",
                        spine_cache_entries=32)
    from repro.kvstore.fabric_sim import init_spine_policy
    sw = preload_spine(init_spine_policy(cfg, fcfg), cfg, fcfg, wl)
    occ = np.asarray(sw.lookup.occupied)
    assert occ.sum() == 32
    gk = np.asarray(sw.lookup.kidx)[occ]
    lk, home = gk // 4, gk % 4
    # every rack's head is represented (rank-interleaved truncation)
    assert set(home.tolist()) == {0, 1, 2, 3}
    # and it is the popularity head of each rack's keyspace
    hot = set(wl.hottest_keys(8).tolist())
    assert set(lk.tolist()) <= hot
    live = np.asarray(sw.orbit.live)
    assert live.sum() == 32  # one live fragment-0 line per entry


def test_spine_controller_revalidates_written_entries():
    """The preload-only spine decays under remote writes (entries
    invalidate forever); the in-scan global spine controller re-validates
    kept entries, refreshes their lines, and restores spine serving."""
    wl = _small_wl(write_ratio=0.2)
    cfg = dataclasses.replace(_small_cfg(), track_popularity=True,
                              seed=1)
    fcfg = FabricConfig(n_racks=2, local_frac=0.5,
                        spine_scheme="orbitcache", spine_lanes=128,
                        fwd_lanes=64, spine_cache_entries=32,
                        spine_k_report=8)
    sim = FabricSimulator(cfg, fcfg, wl)
    sim.preload(warm_windows=8)

    sim.run_windows(60)  # no controller: remote writes kill spine entries
    sp = sim.carry.spine
    valid_before = int(np.asarray(sp.state.valid).sum())
    assert valid_before < 32, "write traffic should invalidate spine entries"

    t = sim.run_periods(4, 15)
    sp = sim.carry.spine
    valid_after = int(np.asarray(sp.state.valid).sum())
    assert valid_after > valid_before
    # re-validated entries serve again: EVERY valid entry must be occupied
    # with a live, version-current fragment-0 line (a revalidation that
    # forgot to refresh the orbit line would leave the entry dead)
    occ = np.asarray(sp.lookup.occupied)
    live = np.asarray(sp.orbit.live).reshape(occ.shape[0], -1)[:, 0]
    ver_ok = np.asarray(sp.orbit.version).reshape(occ.shape[0], -1)[:, 0] \
        == np.asarray(sp.state.version)
    valid = np.asarray(sp.state.valid)
    assert (valid <= (occ & live & ver_ok)).all()
    assert t["spine_served"][-15:].sum() > 0


def test_spine_controller_learns_new_global_hot_keys():
    """A spine smaller than the global head: the controller must install
    reported keys it has never seen (live, metadata-served) under their
    global identities."""
    wl = _small_wl()
    cfg = dataclasses.replace(_small_cfg(), track_popularity=True)
    fcfg = FabricConfig(n_racks=2, local_frac=0.5,
                        spine_scheme="orbitcache", spine_lanes=128,
                        fwd_lanes=64, spine_cache_entries=16,
                        spine_k_report=8)
    sim = FabricSimulator(cfg, fcfg, wl)
    # NO preload: the spine starts empty and must learn from rack reports
    sim.run_periods(3, 20)
    sp = sim.carry.spine
    occ = np.asarray(sp.lookup.occupied)
    assert occ.sum() > 0, "spine controller never installed anything"
    gk = np.asarray(sp.lookup.kidx)[occ]
    lk, home = gk // 2, gk % 2
    assert set(home.tolist()) <= {0, 1}
    # installed keys come from the workload head (server-report ranking)
    hot = set(wl.hottest_keys(200).tolist())
    assert set(lk.tolist()) <= hot
    # installs are live metadata-served lines with per-key value lengths
    f = sp.orbit.max_frags
    lines = np.flatnonzero(occ) * f
    assert np.asarray(sp.orbit.live)[lines].all()
    np.testing.assert_array_equal(
        np.asarray(sp.orbit.vlen)[lines],
        np.asarray(wl.vlen)[lk])
