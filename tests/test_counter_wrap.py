"""Wrap-safety regressions for the promoted lifetime counters.

Client (`tx`/`rx_*`/`hist_*`/`mismatches`) and server (`served`/`dropped`)
lifetime accumulators were int32 plain-adds — a multi-hour run at paper
rates crosses 2**31 and silently wraps negative.  They now live in
``COUNTER_DTYPE`` and accumulate via ``types.sat_add``; one test per
fixed site pins the counter near the ceiling and asserts it clamps
instead of wrapping.  The netcache direct-accumulate branches are
checked at the jaxpr level with the ``dtype-promotion`` lint rule (the
exact footgun those sites had).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import COUNTER_DTYPE, OP_R_REP, OP_R_REQ, empty_batch
from repro.kvstore import client as cl
from repro.kvstore.server import ServerConfig, init_servers, server_step
from repro.kvstore.workload import WorkloadArrays, zipf_inverse_table

TOP = int(jnp.iinfo(COUNTER_DTYPE).max)


def _near_top(st, **fields):
    return st._replace(**{
        k: jnp.full(getattr(st, k).shape, v, COUNTER_DTYPE)
        for k, v in fields.items()})


def _client_cfg():
    return cl.ClientConfig(batch=8, crn_width=4, subrounds=1, value_pad=8)


# --- client.generate: tx ----------------------------------------------------
def test_generate_tx_saturates():
    cfg = _client_cfg()
    st = _near_top(cl.init_clients(cfg), tx=TOP - 2)
    nk = 16
    cdf = np.linspace(1.0 / nk, 1.0, nk, dtype=np.float32)
    table, fine = zipf_inverse_table(cdf)
    st2, _ = cl.generate(
        st, cfg, jax.random.PRNGKey(0),
        wl=WorkloadArrays(table=jnp.asarray(table), cdf=jnp.asarray(cdf),
                          perm=jnp.arange(nk, dtype=jnp.int32),
                          vlen=jnp.full((nk,), 8, jnp.int32),
                          fine_steps=fine),
        offered_per_window=jnp.float32(1000.0),   # >> batch: n == batch
        write_ratio=jnp.float32(0.0),
        num_servers=2, now=jnp.float32(0.0))
    assert st2.tx.dtype == COUNTER_DTYPE
    assert int(st2.tx) == TOP                      # clamped, not wrapped


# --- client.account_switch_served: rx_switch / mismatches / hist_switch ----
def test_account_switch_served_saturates():
    cfg = _client_cfg()
    st = _near_top(cl.init_clients(cfg), rx_switch=TOP - 1,
                   mismatches=TOP - 1, hist_switch=TOP - 1)
    served = jnp.ones((2, 2), bool)
    st2 = cl.account_switch_served(
        st, cfg, served,
        req_kidx=jnp.zeros((2, 2), jnp.int32),
        ts=jnp.zeros((2, 2), jnp.float32),
        line_kidx=jnp.ones((2,), jnp.int32),       # != req_kidx -> mismatch
        serve_time=jnp.ones((2, 2), jnp.float32))
    assert int(st2.rx_switch) == TOP
    assert int(st2.mismatches) == TOP
    assert st2.hist_switch.dtype == COUNTER_DTYPE
    assert int(jnp.max(st2.hist_switch)) == TOP    # bucket clamped
    assert int(jnp.min(st2.hist_switch)) == TOP - 1


# --- client.account_server_replies: rx_server / hist_server ----------------
def test_account_server_replies_saturates():
    cfg = _client_cfg()
    st = _near_top(cl.init_clients(cfg), rx_server=TOP - 1,
                   hist_server=TOP - 1)
    pk = empty_batch(4, value_pad=8)._replace(
        op=jnp.full((4,), OP_R_REP, jnp.int32),
        valid=jnp.ones((4,), bool))
    st2 = cl.account_server_replies(st, cfg, pk, jnp.ones((4,), bool),
                                    jnp.float32(1.0))
    assert int(st2.rx_server) == TOP
    assert int(jnp.max(st2.hist_server)) == TOP


# --- server_step: served / dropped -----------------------------------------
def test_server_counters_saturate():
    cfg = ServerConfig(num_servers=1, queue_depth=2, cap_per_window=2,
                       value_pad=8, max_frags=1)
    st = init_servers(cfg, num_keys=4)
    st = st._replace(served=jnp.full((1,), TOP - 1, COUNTER_DTYPE),
                     dropped=jnp.full((1,), TOP - 1, COUNTER_DTYPE))
    pk = empty_batch(4, value_pad=8)._replace(
        op=jnp.full((4,), OP_R_REQ, jnp.int32),
        kidx=jnp.arange(4, dtype=jnp.int32) % 4,
        vlen=jnp.full((4,), 4, jnp.int32),
        server=jnp.zeros((4,), jnp.int32),
        valid=jnp.ones((4,), bool))
    st2, out = server_step(st, cfg, pk, jnp.ones((4,), bool),
                           jnp.zeros((4,), jnp.int32), jnp.float32(0.0))
    assert int(out.dropped_now[0]) == 2            # 4 arrivals, depth 2
    assert int(out.served_now[0]) == 2
    assert int(st2.dropped[0]) == TOP              # TOP-1 + 2, clamped
    assert int(st2.served[0]) == TOP
    # monotone under pressure on a second window too
    st3, _ = server_step(st2, cfg, pk, jnp.ones((4,), bool),
                         jnp.zeros((4,), jnp.int32), jnp.float32(100.0))
    assert int(st3.served[0]) == TOP and int(st3.dropped[0]) == TOP


# --- the netcache direct-accumulate branches: lint-clean at jaxpr level ----
def _dtype_rule_findings(name, fn, *args):
    from repro.analysis.entry_points import EntryPoint
    from repro.analysis.rules import RULES
    ep = EntryPoint(name, lambda: jax.make_jaxpr(fn)(*args))
    return RULES["dtype-promotion"](ep)


def test_netcache_window_accounting_lint_clean():
    from repro.kvstore import simulator as sim
    from repro.kvstore.workload import Workload, WorkloadConfig
    cfg = sim.RackConfig(scheme="netcache", cache_entries=8, num_servers=2,
                         client_batch=16, fetch_lanes=8, value_pad=64,
                         server_queue=8, subrounds=2, max_serves=4,
                         queue_size=4, netcache_entries=16,
                         netcache_table=1 << 8)
    wl = Workload(WorkloadConfig(num_keys=64, offered_rps=1e5))
    scfg = sim.make_server_config(cfg)
    ccfg = sim.make_client_config(cfg)
    carry = sim.init_carry(cfg, scfg, ccfg, wl.cfg.num_keys,
                           wl.cfg.offered_rps, wl.cfg.write_ratio, 0)
    found = _dtype_rule_findings(
        "netcache.window_step",
        lambda w, c: sim.window_step(cfg, scfg, ccfg, wl.cfg.key_size, w, c),
        wl.arrays, carry)
    assert found == [], "\n".join(f.format() for f in found)


def test_netcache_spine_accounting_lint_clean():
    from repro.kvstore import fabric_sim as fs
    from repro.kvstore import simulator as sim
    from repro.kvstore.fleet import FabricSimulator
    from repro.kvstore.workload import Workload, WorkloadConfig
    cfg = sim.RackConfig(scheme="orbitcache", cache_entries=8, num_servers=2,
                         client_batch=16, fetch_lanes=8, value_pad=64,
                         server_queue=8, subrounds=2, max_serves=4,
                         queue_size=4)
    fcfg = fs.FabricConfig(n_racks=2, spine_scheme="netcache",
                           spine_lanes=8, fwd_lanes=8,
                           spine_netcache_entries=16,
                           spine_netcache_table=1 << 8)
    wl = Workload(WorkloadConfig(num_keys=64, offered_rps=1e5))
    fsim = FabricSimulator(cfg, fcfg, wl)
    found = _dtype_rule_findings(
        "fabric.netcache_spine",
        lambda w, c: fs.fabric_window_step(cfg, fcfg, fsim.server_cfg,
                                           fsim.client_cfg, wl.cfg.key_size,
                                           w, c),
        wl.arrays, fsim.carry)
    assert found == [], "\n".join(f.format() for f in found)
