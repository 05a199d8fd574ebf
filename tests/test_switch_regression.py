"""Fused-pipeline regression: bit-identical to the composed seed path.

The seed implementation did the lookup with ``lookup.lookup`` (pure [B, C]
compare), a separate validity check, a scatter-add popularity update, and a
free-standing ``rt.enqueue``; PR 1 fused the lookup slice into the
``orbit_match`` kernel; PR 2 fused match + admission into
the fused pipeline op (retired since); this PR folds the ENTIRE subround — match,
admission + metadata apply, state-table pass, orbit install, serving round
— into ``kernels.subround``, a single ``pallas_call`` behind
``core.pipeline``, with orbit value bytes hoisted out of the per-subround
scan.  These tests replay traffic through the seed-composed and fused
implementations and assert bit-identical outputs and state:

  * per step (``switch_step`` vs the verbatim seed sequence), on the
    oracle backend and the Pallas interpreter;
  * per window (``window_step`` vs a PR-1-style composed window that scans
    the full SwitchState and installs value bytes eagerly), for all three
    schemes;
  * per subround edge case (zero recirculation budget, full request-table
    queues, multi-fragment lines, all-invalid ingress), on both backends;
  * structurally: the per-subround scan carry holds no orbit value bytes,
    the subround traces exactly ONE pallas_call on the kernel backends,
    and the running counters saturate instead of wrapping.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels as kn
from repro.core import lookup as lk
from repro.core import orbit as ob
from repro.core import pipeline as pipe
from repro.core import request_table as rt
from repro.core import state_table as stt
from repro.core import switch as swm
from repro.core.controller import CacheController, ControllerConfig
from repro.core.hashing import hash128_u32
from repro.core.types import (
    OP_CRN_REQ, OP_F_REP, OP_R_REQ, OP_W_REP, OP_W_REQ, Counters, PacketBatch,
    SwitchState, empty_batch, init_switch_state, sat_add,
)
from repro.kvstore.store import synth_value

PAD = 64


def _seed_switch_step(sw, pkts, recirc_packets, max_serves):
    """Verbatim seed implementation (pre kernel dispatch)."""
    op, valid = pkts.op, pkts.valid
    cidx = lk.lookup(sw.lookup, pkts.hkey)
    hit = (cidx >= 0) & valid
    safe_cidx = jnp.where(hit, cidx, 0)

    r_req = valid & (op == swm.OP_R_REQ)
    w_req = valid & (op == swm.OP_W_REQ)
    r_rep = valid & (op == swm.OP_R_REP)
    w_rep = valid & (op == swm.OP_W_REP)
    f_rep = valid & (op == swm.OP_F_REP)
    f_req = valid & (op == swm.OP_F_REQ)
    crn = valid & (op == swm.OP_CRN_REQ)

    r_hit = r_req & hit
    entry_valid = sw.state.valid[safe_cidx] & hit
    want_enq = r_hit & entry_valid
    enq = rt.enqueue(
        sw.reqtab, cidx, want_enq, pkts.client, pkts.seq, pkts.port, pkts.ts,
        kidx=pkts.kidx,
    )
    invalid_fwd = r_hit & ~entry_valid

    c_entries = sw.counters.popularity.shape[0]
    pop_idx = jnp.where(r_hit, cidx, c_entries)
    popularity = sw.counters.popularity.at[pop_idx].add(1, mode='drop')
    n_hit = jnp.sum(r_hit.astype(jnp.int32))
    n_overflow = jnp.sum(enq.overflow.astype(jnp.int32))
    n_invalid_fwd = jnp.sum(invalid_fwd.astype(jnp.int32))

    w_cached = w_req & hit
    state2 = stt.invalidate(sw.state, safe_cidx, w_cached)
    flag_out = jnp.where(w_cached, jnp.int32(1), pkts.flag)

    install = (w_rep | f_rep) & hit & (pkts.flag >= 1)
    state3 = stt.validate(state2, safe_cidx, install)
    inst_version = state3.version[safe_cidx]
    frag = jnp.where(f_rep, pkts.seq, 0)
    orbit2 = ob.install_lines(
        sw.orbit, safe_cidx, install, pkts.kidx, inst_version,
        pkts.vlen, pkts.val, frag=frag, n_frags=jnp.maximum(pkts.flag, 1),
    )

    # running counters accumulate wrap-safe (uint32 saturating) in both the
    # composed and fused paths — part of the counter-overflow fix
    counters = Counters(
        popularity=popularity,
        hits=sat_add(sw.counters.hits, n_hit),
        overflow=sat_add(sw.counters.overflow, n_overflow + n_invalid_fwd),
        cached_reqs=sat_add(sw.counters.cached_reqs, n_hit),
    )
    sw2 = SwitchState(
        lookup=sw.lookup, state=state3, reqtab=enq.table, orbit=orbit2,
        counters=counters,
    )

    sw3, grid = ob.orbit_pass(sw2, recirc_packets, max_serves)
    n_served = jnp.sum(grid.served.astype(jnp.int32))
    bytes_served = jnp.sum(
        jnp.where(grid.served, grid.vlen[:, None], 0)).astype(jnp.uint32)

    route = jnp.full(pkts.width, swm.ROUTE_DROP, jnp.int32)
    to_server = (
        (r_req & ~hit) | enq.overflow | invalid_fwd | w_req | crn | f_req
    )
    to_client = r_rep | (w_rep & ~install) | (w_rep & install)
    route = jnp.where(to_server & valid, swm.ROUTE_SERVER, route)
    route = jnp.where(to_client & valid, swm.ROUTE_CLIENT, route)

    stats = swm.StepStats(
        n_r_req=jnp.sum(r_req.astype(jnp.int32)),
        n_hit=n_hit,
        n_enq=jnp.sum(enq.accepted.astype(jnp.int32)),
        n_overflow=n_overflow,
        n_invalid_fwd=n_invalid_fwd,
        n_w_req=jnp.sum(w_req.astype(jnp.int32)),
        n_w_cached=jnp.sum(w_cached.astype(jnp.int32)),
        n_install=jnp.sum(install.astype(jnp.int32)),
        n_served=n_served,
        bytes_served=bytes_served,
        n_crn=jnp.sum(crn.astype(jnp.int32)),
        n_fwd=jnp.sum((to_server & valid).astype(jnp.int32)),
    )
    return sw3, swm.StepOutput(route=route, flag=flag_out, grid=grid,
                               stats=stats)


def _boot(keys=(0, 1, 2, 3), entries=8):
    sw = init_switch_state(entries, queue_size=4, value_pad=PAD)
    ctrl = CacheController(ControllerConfig(active_size=entries))
    sw, fetches = ctrl.preload(sw, np.asarray(keys, np.int32))
    ks = jnp.asarray([k for k, _ in fetches], jnp.int32)
    vals = synth_value(ks, jnp.zeros_like(ks), PAD)
    n = len(fetches)
    pk = empty_batch(max(n, 8), value_pad=PAD)
    pk = pk._replace(
        op=pk.op.at[:n].set(OP_F_REP),
        kidx=pk.kidx.at[:n].set(ks),
        hkey=pk.hkey.at[:n].set(hash128_u32(ks)),
        flag=pk.flag.at[:n].set(1),
        val=pk.val.at[:n].set(vals),
        vlen=pk.vlen.at[:n].set(32),
        valid=pk.valid.at[:n].set(True),
    )
    return sw, pk


def _traffic(rng: np.random.Generator, b=24):
    """Mixed-op batch: hits, misses, writes, installs, CRN, dead lanes."""
    ops = rng.choice(
        [OP_R_REQ, OP_R_REQ, OP_R_REQ, OP_W_REQ, OP_W_REP, OP_F_REP,
         OP_CRN_REQ], size=b).astype(np.int32)
    kidx = rng.choice([0, 1, 2, 3, 7, 99, 1234], size=b).astype(np.int32)
    flags = rng.integers(0, 2, b).astype(np.int32)
    valid = rng.random(b) < 0.85
    k = jnp.asarray(kidx)
    pk = empty_batch(b, value_pad=PAD)
    return pk._replace(
        op=jnp.asarray(ops),
        kidx=k,
        hkey=hash128_u32(k),
        flag=jnp.asarray(flags),
        seq=jnp.arange(b, dtype=jnp.int32),
        client=jnp.arange(b, dtype=jnp.int32) % 4,
        vlen=jnp.full(b, 32, jnp.int32),
        val=synth_value(k, jnp.zeros_like(k), PAD),
        valid=jnp.asarray(valid),
        ts=jnp.arange(b, dtype=jnp.float32),
    )


def _assert_trees_equal(a, b, label):
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for (path, la), lb in zip(fa, fb):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"{label}: mismatch at {jax.tree_util.keystr(path)}")


# ---------------------------------------------------------------------------
# window-level regression: fused pipeline vs the PR-1 composed window
# ---------------------------------------------------------------------------
def _composed_window_step(cfg, server_cfg, client_cfg, key_size, wl, carry):
    """PR-1-style window step: full-SwitchState subround scan over the
    seed-composed switch pass (eager value installs), identical client /
    server / routing stages.  The reference the fused pipeline must match
    bit-for-bit."""
    from repro.baselines.netcache import netcache_step
    from repro.baselines.nocache import nocache_step
    from repro.kvstore import client as cl
    from repro.kvstore import simulator as sim_mod
    from repro.kvstore.server import server_step
    from repro.core.types import OP_NONE, ROUTE_CLIENT, ROUTE_SERVER

    c = cfg
    rng, r_gen = jax.random.split(carry.rng)
    clients, reqs = cl.generate(
        carry.clients, client_cfg, r_gen, wl,
        carry.offered, carry.write_ratio, c.num_servers, carry.now,
    )
    sub = jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=1), reqs, carry.pending,
        carry.fetch,
    )
    pad_to = sub.op.shape[0] * sub.op.shape[1]

    window = jnp.float32(c.window_us)
    if c.scheme == "orbitcache":
        def one_subround(sw, pk):
            live = sw.orbit.live
            nlive = jnp.maximum(jnp.sum(live.astype(jnp.int32)), 1)
            mean_line = (
                jnp.sum(jnp.where(live, sw.orbit.vlen, 0)) / nlive
                + sim_mod.HDR_BYTES + key_size
            )
            pps = (c.recirc_gbps * 1e9 / 8.0) / mean_line
            budget = (pps * window * 1e-6 / c.subrounds).astype(jnp.int32)
            sw2, out = _seed_switch_step(sw, pk, budget, c.max_serves)
            interval_us = nlive.astype(jnp.float32) / pps * 1e6
            return sw2, (out.route, out.flag, out.grid, out.stats, interval_us)

        policy, (routes, flags, grids, stats, intervals) = jax.lax.scan(
            one_subround, carry.policy, sub, unroll=c.subrounds
        )
        switch_reply = jnp.zeros((pad_to,), bool)
        r_idx = jnp.arange(c.subrounds, dtype=jnp.float32)[:, None, None]
        serve_time = (
            carry.now
            + (r_idx + 0.5) * window / c.subrounds
            + (grids.order.astype(jnp.float32) + 1.0) * intervals[:, None, None]
        )
        clients = cl.account_switch_served(
            clients, client_cfg,
            grids.served.reshape(-1, c.max_serves),
            grids.req_kidx.reshape(-1, c.max_serves),
            grids.ts.reshape(-1, c.max_serves),
            grids.kidx.reshape(-1),
            serve_time.reshape(-1, c.max_serves),
        )
        hits = jnp.sum(stats.n_hit)
        overflow = jnp.sum(stats.n_overflow) + jnp.sum(stats.n_invalid_fwd)
        installs = jnp.sum(stats.n_install)
        crn = jnp.sum(stats.n_crn)
        rx_sw = jnp.sum(stats.n_served)
    elif c.scheme == "netcache":
        def one_subround(st, pk):
            st2, route, flag, srep, n_hit = netcache_step(st, pk)
            return st2, (route, flag, srep, n_hit)

        policy, (routes, flags, sreps, n_hits) = jax.lax.scan(
            one_subround, carry.policy, sub, unroll=c.subrounds
        )
        switch_reply = sreps.reshape(-1)
        hits = jnp.sum(n_hits)
        overflow = jnp.zeros((), jnp.int32)
        installs = jnp.zeros((), jnp.int32)
        crn = jnp.zeros((), jnp.int32)
        lat = jnp.full((pad_to,), 1.0, jnp.float32) + client_cfg.base_rtt_us
        bucket = jnp.where(switch_reply, cl.lat_bucket(lat), cl.LAT_BUCKETS)
        clients = clients._replace(
            hist_switch=sat_add(clients.hist_switch, cl._bucket_counts(bucket)),
            rx_switch=sat_add(clients.rx_switch,
                              jnp.sum(switch_reply.astype(jnp.int32))),
        )
        rx_sw = jnp.sum(switch_reply.astype(jnp.int32))
    else:  # nocache
        def one_subround(st, pk):
            st2, route, flag = nocache_step(st, pk)
            return st2, (route, flag)

        policy, (routes, flags) = jax.lax.scan(one_subround, carry.policy,
                                        sub, unroll=c.subrounds)
        switch_reply = jnp.zeros((pad_to,), bool)
        hits = overflow = installs = crn = jnp.zeros((), jnp.int32)
        rx_sw = jnp.zeros((), jnp.int32)

    route_flat = routes.reshape(-1)
    flag_flat = flags.reshape(-1)
    ing_flat = jax.tree.map(lambda a: a.reshape((pad_to,) + a.shape[2:]), sub)

    to_server = (route_flat == ROUTE_SERVER) & ing_flat.valid
    servers, sout = server_step(
        carry.servers, server_cfg, ing_flat, to_server, flag_flat,
        carry.now,
    )

    to_client = (route_flat == ROUTE_CLIENT) & ing_flat.valid & ~switch_reply
    rx_srv_before = clients.rx_server
    clients = cl.account_server_replies(
        clients, client_cfg, ing_flat, to_client, carry.now + window
    )
    rx_srv = clients.rx_server - rx_srv_before

    reply_w, reply_pad = sim_mod._reply_width(cfg, server_cfg)
    rep = sout.replies
    if reply_pad:
        pad_b = empty_batch(reply_pad, c.value_pad)
        rep = jax.tree.map(lambda a, p: jnp.concatenate([a, p]), rep, pad_b)
    pending = sim_mod.interleave(rep, c.subrounds)

    metrics = sim_mod.WindowMetrics(
        tx=jnp.sum((reqs.valid & (reqs.op != OP_NONE)).astype(jnp.int32)),
        rx_switch=rx_sw,
        rx_server=rx_srv,
        served=sout.served_now,
        dropped=sout.dropped_now,
        backlog=sout.backlog,
        hits=hits,
        overflow=overflow,
        installs=installs,
        crn=crn,
        mismatches=clients.mismatches,
        fwd=jnp.sum(to_server.astype(jnp.int32)),
    )
    new_carry = sim_mod.SimCarry(
        policy=policy,
        servers=servers,
        clients=clients,
        pending=pending,
        fetch=sim_mod.interleave(empty_batch(c.fetch_lanes, c.value_pad),
                                 c.subrounds),
        rng=rng,
        now=carry.now + window,
        offered=carry.offered,
        write_ratio=carry.write_ratio,
    )
    return new_carry, metrics


@pytest.mark.parametrize("scheme", ["orbitcache", "netcache", "nocache"])
def test_window_step_bit_identical_to_composed(scheme):
    from repro.kvstore import simulator as sim_mod
    from repro.kvstore.fleet import RackSimulator
    from repro.kvstore.simulator import RackConfig
    from repro.kvstore.workload import Workload, WorkloadConfig

    wl = Workload(WorkloadConfig(num_keys=5_000, offered_rps=1.5e6,
                                 write_ratio=0.1))
    cfg = RackConfig(scheme=scheme, cache_entries=32, num_servers=4,
                     client_batch=128, fetch_lanes=32, value_pad=64,
                     server_queue=32, subrounds=2)
    sim = RackSimulator(cfg, wl)
    if scheme == "orbitcache":
        sim.preload(wl.hottest_keys(32))
    elif scheme == "netcache":
        sim.preload(wl.hottest_keys(500))

    fused = jax.jit(lambda w, cr: sim_mod.window_step(
        cfg, sim.server_cfg, sim.client_cfg, sim.key_size, w, cr))
    composed = jax.jit(lambda w, cr: _composed_window_step(
        cfg, sim.server_cfg, sim.client_cfg, sim.key_size, w, cr))

    carry_a = carry_b = sim.carry
    for w in range(4):
        carry_a, met_a = fused(wl.arrays, carry_a)
        carry_b, met_b = composed(wl.arrays, carry_b)
        _assert_trees_equal(met_a, met_b, f"{scheme} window {w} metrics")
        _assert_trees_equal(carry_a, carry_b, f"{scheme} window {w} carry")


def test_subround_carry_has_no_orbit_value_bytes():
    """The hoist is structural: the scan carry type holds no value bytes,
    and reattaching them roundtrips the SwitchState exactly."""
    sw = init_switch_state(8, queue_size=4, value_pad=128, max_frags=2)
    carry, val = pipe.strip_val(sw)
    assert val.shape == (16, 128) and val.dtype == jnp.uint8
    for path, leaf in jax.tree_util.tree_leaves_with_path(carry):
        assert leaf.dtype != jnp.uint8, (
            f"orbit value bytes leaked into the subround carry at "
            f"{jax.tree_util.keystr(path)}")
    _assert_trees_equal(pipe.with_val(carry, val), sw, "strip/with_val")


def test_window_step_routes_through_pipeline(monkeypatch):
    """window_step's orbitcache branch runs on core.pipeline (trace-time
    spy), i.e. the value-light PipelineCarry scan, not the composed path."""
    from repro.kvstore import simulator as sim_mod
    from repro.kvstore.fleet import RackSimulator
    from repro.kvstore.simulator import RackConfig
    from repro.kvstore.workload import Workload, WorkloadConfig

    calls = []
    orig = pipe.window_pipeline

    def spy(*args, **kwargs):
        calls.append(1)
        return orig(*args, **kwargs)

    monkeypatch.setattr(sim_mod.pipeline, "window_pipeline", spy)
    wl = Workload(WorkloadConfig(num_keys=1_000, offered_rps=5e5))
    cfg = RackConfig(scheme="orbitcache", cache_entries=16, num_servers=2,
                     client_batch=64, fetch_lanes=16, value_pad=64,
                     server_queue=16, subrounds=2)
    sim = RackSimulator(cfg, wl)
    sim.run_windows(1)
    assert calls, "window_step did not route through pipeline.window_pipeline"


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_switch_step_bit_identical_to_seed(backend):
    kn.set_kernel_backend(backend)
    try:
        rng = np.random.default_rng(0)
        sw_new, pk0 = _boot()
        sw_old = sw_new
        # boot step itself must agree
        sw_new, out_new = swm.switch_step(sw_new, pk0, jnp.int32(100), 4)
        sw_old, out_old = _seed_switch_step(sw_old, pk0, jnp.int32(100), 4)
        _assert_trees_equal(out_new, out_old, "boot StepOutput")
        _assert_trees_equal(sw_new, sw_old, "boot SwitchState")
        for step in range(6):
            pk = _traffic(rng)
            budget = jnp.int32([100, 3, 0, 100, 7, 100][step])
            sw_new, out_new = swm.switch_step(sw_new, pk, budget, 4)
            sw_old, out_old = _seed_switch_step(sw_old, pk, budget, 4)
            _assert_trees_equal(out_new, out_old, f"step {step} StepOutput")
            _assert_trees_equal(sw_new, sw_old, f"step {step} SwitchState")
    finally:
        kn.set_kernel_backend(None)


# ---------------------------------------------------------------------------
# subround edge cases through the fused path: each scenario replayed against
# the verbatim seed composition on BOTH kernel-capable backends
# ---------------------------------------------------------------------------
def _run_compare(sw, steps, backend, label, max_serves=4):
    kn.set_kernel_backend(backend)
    try:
        sw_new = sw_old = sw
        for i, (pk, budget) in enumerate(steps):
            sw_new, out_new = swm.switch_step(sw_new, pk, jnp.int32(budget),
                                              max_serves)
            sw_old, out_old = _seed_switch_step(sw_old, pk, jnp.int32(budget),
                                                max_serves)
            _assert_trees_equal(out_new, out_old, f"{label} step {i} output")
            _assert_trees_equal(sw_new, sw_old, f"{label} step {i} state")
        return sw_new
    finally:
        kn.set_kernel_backend(None)


def _read_batch(keys, width=16, clients=None, start_seq=0):
    k = jnp.asarray(keys, jnp.int32)
    n = len(keys)
    pk = empty_batch(max(width, n), value_pad=PAD)
    cl = jnp.asarray(clients if clients is not None
                     else np.arange(n) % 4, jnp.int32)
    return pk._replace(
        op=pk.op.at[:n].set(OP_R_REQ),
        kidx=pk.kidx.at[:n].set(k),
        hkey=pk.hkey.at[:n].set(hash128_u32(k)),
        seq=pk.seq.at[:n].set(jnp.arange(start_seq, start_seq + n,
                                         dtype=jnp.int32)),
        client=pk.client.at[:n].set(cl),
        ts=pk.ts.at[:n].set(jnp.arange(n, dtype=jnp.float32)),
        valid=pk.valid.at[:n].set(True),
    )


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_fused_zero_recirc_budget(backend):
    """Zero budget: queues fill, nothing serves, nothing pops."""
    sw, boot = _boot()
    steps = [(boot, 100)]
    steps += [(_read_batch([0, 1, 1, 2, 3], start_seq=9 * i), 0)
              for i in range(3)]
    sw_end = _run_compare(sw, steps, backend, "zero-budget")
    assert int(jnp.sum(sw_end.reqtab.qlen)) > 0  # queues really filled


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_fused_full_request_queues(backend):
    """Completely full queues: same-key floods overflow to the server while
    full, then a budgeted round drains the fronts."""
    sw, boot = _boot()
    flood = _read_batch([0] * 10 + [1] * 6, width=16)
    steps = [(boot, 100), (flood, 0), (flood, 0), (flood, 100),
             (_read_batch([0, 1, 2]), 100)]
    sw_end = _run_compare(sw, steps, backend, "full-queues")
    # queue size is 4: the flood can never leave more than S queued
    assert int(jnp.max(sw_end.reqtab.qlen)) <= sw_end.reqtab.queue_size


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_fused_multi_fragment_lines(backend):
    """max_frags > 1: entries serve only when every fragment is live, and a
    half-installed entry stays quiet."""
    entries, f = 8, 2
    sw = init_switch_state(entries, queue_size=4, value_pad=PAD, max_frags=f)
    ctrl = CacheController(ControllerConfig(active_size=entries))
    keys = np.asarray([0, 1, 2], np.int32)
    sw, fetches = ctrl.preload(sw, keys)
    ks = jnp.asarray([k for k, _ in fetches], jnp.int32)

    def frep(keys_arr, frags, nfrag):
        k = jnp.asarray(keys_arr, jnp.int32)
        n = len(keys_arr)
        pk = empty_batch(max(8, n), value_pad=PAD)
        return pk._replace(
            op=pk.op.at[:n].set(OP_F_REP),
            kidx=pk.kidx.at[:n].set(k),
            hkey=pk.hkey.at[:n].set(hash128_u32(k)),
            seq=pk.seq.at[:n].set(jnp.asarray(frags, jnp.int32)),
            flag=pk.flag.at[:n].set(nfrag),
            vlen=pk.vlen.at[:n].set(24),
            val=pk.val.at[:n].set(synth_value(k, jnp.asarray(frags, jnp.int32),
                                              PAD)),
            valid=pk.valid.at[:n].set(True),
        )

    # keys 0/1 get both fragments; key 2 only fragment 0 (incomplete)
    both = frep(np.repeat(np.asarray(ks)[:2], 2), [0, 1, 0, 1], 2)
    half = frep([int(ks[2])], [0], 2)
    steps = [(both, 100), (half, 100),
             (_read_batch(list(np.asarray(ks)) * 2), 100),
             (_read_batch(list(np.asarray(ks))), 100)]
    sw_end = _run_compare(sw, steps, backend, "multi-frag")
    live = np.asarray(sw_end.orbit.live).reshape(entries, f)
    frags = np.asarray(sw_end.orbit.frags)
    complete = live.sum(axis=1) >= frags
    # the half-installed entry must NOT count as complete
    kidx_of = {int(k): c for c, k in enumerate(np.asarray(sw_end.lookup.kidx))}
    assert not complete[kidx_of[int(ks[2])]]


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_fused_all_invalid_ingress(backend):
    """An all-invalid batch must leave every table untouched but still run
    the serving round (budget drains queued requests)."""
    rng = np.random.default_rng(3)
    sw, boot = _boot()
    dead = _traffic(rng)._replace(valid=jnp.zeros(24, bool))
    steps = [(boot, 100), (_read_batch([0, 1, 2, 3]), 0),
             (dead, 0), (dead, 100)]
    _run_compare(sw, steps, backend, "all-invalid")


# ---------------------------------------------------------------------------
# structural guarantees: one pallas_call per subround; wrap-safe counters
# (the walker lives in repro.analysis — the lint subsystem — so the
# regression test and the linter can never disagree on what counts)
# ---------------------------------------------------------------------------
from repro.analysis import count_pallas_calls as _count_pallas_calls  # noqa: E402


def test_subround_is_single_pallas_call():
    """On the kernel backends the whole subround lowers to exactly ONE
    pallas_call — and a window traces one per subround (inside the scan
    body), nothing more.  The ref backend stays kernel-free."""
    sw = init_switch_state(8, queue_size=4, value_pad=PAD)
    carry, _ = pipe.strip_val(sw)
    pk = empty_batch(16, value_pad=PAD)

    kn.set_kernel_backend("interpret")
    try:
        jx = jax.make_jaxpr(
            lambda c, p: pipe.subround_pipeline(c, p, jnp.int32(10), 4)
        )(carry, pk)
        assert _count_pallas_calls(jx.jaxpr) == 1
        sub = jax.tree.map(lambda a: jnp.stack([a, a]), pk)
        jw = jax.make_jaxpr(
            lambda s, b: pipe.window_pipeline(
                s, b, recirc_gbps=100.0, window_us=100.0, subrounds=2,
                max_serves=4, key_size=16)
        )(sw, sub)
        # the scan body holds the one-and-only pallas_call per subround
        assert _count_pallas_calls(jw.jaxpr) == 1
    finally:
        kn.set_kernel_backend(None)

    kn.set_kernel_backend("ref")
    try:
        jx = jax.make_jaxpr(
            lambda c, p: pipe.subround_pipeline(c, p, jnp.int32(10), 4)
        )(carry, pk)
        assert _count_pallas_calls(jx.jaxpr) == 0
    finally:
        kn.set_kernel_backend(None)


def test_running_counters_saturate_instead_of_wrapping():
    """Counters.popularity / hits / overflow / cached_reqs accumulate in
    uint32 and clamp at the max — a counter pushed near the ceiling by a
    long run must never wrap negative or backwards."""
    from repro.core.types import COUNTER_DTYPE
    top = jnp.iinfo(COUNTER_DTYPE).max
    near = jnp.asarray(top - 2, COUNTER_DTYPE)
    assert int(sat_add(near, jnp.int32(1))) == top - 1
    assert int(sat_add(near, jnp.int32(100))) == top      # clamps, no wrap
    assert int(sat_add(jnp.asarray(top, COUNTER_DTYPE), jnp.int32(7))) == top

    sw, boot = _boot()
    sw, _ = swm.switch_step(sw, boot, jnp.int32(100), 4)
    sw = sw._replace(counters=sw.counters._replace(
        hits=jnp.asarray(top - 1, COUNTER_DTYPE),
        cached_reqs=jnp.asarray(top - 1, COUNTER_DTYPE),
        popularity=jnp.full_like(sw.counters.popularity, top - 1),
    ))
    sw2, out = swm.switch_step(sw, _read_batch([0, 1, 0, 2]), jnp.int32(0), 4)
    assert int(out.stats.n_hit) > 0
    # monotone under pressure: clamped at the ceiling, never wrapped
    assert int(sw2.counters.hits) == top
    assert int(jnp.max(sw2.counters.popularity)) == top
    assert np.all(np.asarray(sw2.counters.popularity)
                  >= np.asarray(sw.counters.popularity))
