"""Two-tier cross-rack fabric simulator (beyond-paper: shared spine switch).

Topology: R racks — each a full :mod:`repro.kvstore.simulator` rack
(clients + ToR switch policy + rate-limited server shard) — hang off one
shared **spine switch**.  Each rack owns a copy of the keyspace; a request
targets its own rack with probability ``local_frac`` (sweepable without
retrace) and a uniformly random other rack otherwise.  Per window:

  1. every rack draws its open-loop client batch (the *same* RNG stream a
     standalone rack would use — the locality-1.0 bit-identity guarantee);
  2. remote request lanes are diverted off the rack ingress and compacted
     into the spine ingress by a one-hot permutation
     (:func:`repro.core.fabric.exchange_to_spine`), re-keyed to their
     *global* identity ``kidx * R + home`` so same-``kidx`` keys of
     different racks never collide in the spine cache;
  3. the spine runs its own scheme over the global hot set — OrbitCache
     (another ``PipelineCarry`` scanned through the same fused
     ``window_pipeline`` subround loop, spine-cached items recirculating
     on the spine's own port budget), NetCache, or NoCache — and serves
     spine hits directly;
  4. spine misses/overflows fall through to the owning rack: the spine's
     ROUTE_SERVER egress is scattered to per-rack forward lanes (one-hot
     permutation per rack), translated back to local keys, and appended
     to the home rack's ToR ingress for the same window;
  5. every rack runs the standard :func:`simulator.process_window`
     (vmapped over the rack axis): ToR scheme pass, server FIFOs, client
     accounting, next-window pending.

Latency model: ``spine_hop_us`` is ONE rack<->spine traversal.  A
spine-served request pays two crossings (up + the reply back down); a
fall-through packet's timestamp is debited four (down via the spine plus
the reply's unmodeled return via the spine), so the latency accounted at
the serving rack spans the whole fabric round trip.

Deliberate simplifications (documented, metrics-visible):

* Replies do not transit back through the spine data plane — they are
  accounted at the rack that served them (totals and latency are correct;
  the source rack's per-client attribution is approximated).  As a
  consequence the spine cache installs only via preload, and a remote
  write permanently invalidates its spine entry (subsequent readers fall
  through to the owning rack) — read-mostly workloads, the paper's
  regime, are unaffected.
* Lane buffers are fixed-width: compaction overflow is dropped and
  counted (``spine_drops``), the same open-loop UDP semantics as the
  server FIFOs.

With ``local_frac == 1.0`` no lane ever crosses the fabric and each
rack's full state evolution (policy, servers, clients, RNG) is
bit-identical to R independent racks of a
:class:`fleet.BatchedRackSimulator` — regression-tested in
``tests/test_fabric.py``.

This module holds the fabric's config, carry, step functions, chunk
builders and results; ``fleet.BatchedFabricSimulator`` runs them.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.baselines.netcache import init_netcache, netcache_install, netcache_step
from repro.baselines.nocache import nocache_step
from repro.core import fabric as fb
from repro.core import pipeline
from repro.core.controller import ControllerConfig, controller_step
from repro.core.hashing import hash128_u32, hash128_u32_np, server_of_key
from repro.core.types import (
    COUNTER_DTYPE,
    OP_R_REQ,
    OP_W_REQ,
    ROUTE_SERVER,
    empty_batch,
    init_switch_state,
    sat_add,
)

from . import client as cl
from .server import server_reports_traced
from .simulator import (
    RackConfig,
    SimCarry,
    SimResult,
    controller_window_apply,
    generate_requests,
    init_carry,
    process_window,
    tree_stack,
)
from .workload import Workload, WorkloadArrays


@dataclass(frozen=True)
class FabricConfig:
    """Static spine/fabric geometry (hashable: part of the jit cache key)."""

    n_racks: int = 4
    local_frac: float = 0.9         # initial value; dynamic via the carry
    spine_scheme: str = "orbitcache"   # orbitcache | netcache | nocache
    spine_lanes: int = 256          # spine ingress lanes per window
    fwd_lanes: int = 128            # per-rack spine-forward lanes per window
    spine_cache_entries: int = 256  # spine OrbitCache lookup capacity
    spine_queue_size: int = 8
    spine_max_serves: int = 8
    spine_max_frags: int = 1
    spine_recirc_gbps: float = 400.0   # spine recirculation port bandwidth
    spine_netcache_table: int = 1 << 15
    spine_netcache_entries: int = 10_000   # netcache spine preload size
    spine_netcache_value_limit: int = 64
    spine_hop_us: float = 2.0       # one fabric traversal (each way)
    spine_k_report: int = 16        # per-server report slice the global
                                    # spine controller merges (bounds its
                                    # candidate-dedup matrix at R*n_srv*k)


class FabricCarry(NamedTuple):
    racks: SimCarry             # every leaf stacked over the rack axis [R]
    spine: Any                  # SwitchState | NetCacheState | () per scheme
    spine_clients: cl.ClientState  # spine-tier serve accounting
    fabric_rng: jax.Array       # homing draws — separate stream, so the
                                # rack RNG streams match standalone racks
    local_frac: jnp.ndarray     # float32[] (dynamic, sweepable)
    spine_drops: jnp.ndarray    # uint32[] cumulative lane-exchange drops
                                # (sat_add — running counters never wrap)


class FabricWindowMetrics(NamedTuple):
    racks: Any                  # WindowMetrics, leaves [R, ...]
    spine_remote: jnp.ndarray   # remote requests offered to the spine
    spine_hits: jnp.ndarray     # spine cache hits (valid-entry R-REQ hits)
    spine_served: jnp.ndarray   # requests answered at the spine this window
    spine_fwd: jnp.ndarray      # spine egress forwarded down to racks
    spine_in_drops: jnp.ndarray   # remote lanes dropped at the spine ingress
    spine_fwd_drops: jnp.ndarray  # forwarded lanes dropped at rack buffers


def init_spine_policy(cfg: RackConfig, fcfg: FabricConfig):
    if fcfg.spine_scheme == "orbitcache":
        return init_switch_state(
            fcfg.spine_cache_entries, fcfg.spine_queue_size, cfg.value_pad,
            fcfg.spine_max_frags,
        )
    if fcfg.spine_scheme == "netcache":
        return init_netcache(fcfg.spine_netcache_table,
                             fcfg.spine_netcache_value_limit)
    if fcfg.spine_scheme == "nocache":
        return ()
    raise ValueError(f"unknown spine scheme {fcfg.spine_scheme!r}")


def init_fabric_carry(cfg: RackConfig, fcfg: FabricConfig, server_cfg,
                      client_cfg: cl.ClientConfig, num_keys: int,
                      offered_rps: float, write_ratio: float,
                      local_frac: float, seed: int) -> FabricCarry:
    """One fabric's carry: rack ``r`` is seeded ``seed + r`` and the
    homing draws ``seed + 0x0FAB``, so every rack's RNG stream is a
    standalone rack's."""
    racks = tree_stack([
        init_carry(cfg, server_cfg, client_cfg, num_keys, offered_rps,
                   write_ratio, seed + r)
        for r in range(fcfg.n_racks)
    ])
    return FabricCarry(
        racks=racks,
        spine=init_spine_policy(cfg, fcfg),
        spine_clients=cl.init_clients(client_cfg),
        fabric_rng=jax.random.PRNGKey(seed + 0x0FAB),
        local_frac=jnp.float32(local_frac),
        spine_drops=jnp.zeros((), COUNTER_DTYPE),
    )


# ---------------------------------------------------------------------------
# the fabric window step (pure; the fabric fleet vmaps it over points)
# ---------------------------------------------------------------------------
def fabric_window_step(
    cfg: RackConfig,
    fcfg: FabricConfig,
    server_cfg,
    client_cfg: cl.ClientConfig,
    key_size: int,
    wl: WorkloadArrays,
    carry: FabricCarry,
    _=None,
) -> tuple[FabricCarry, FabricWindowMetrics]:
    r_fab = fcfg.n_racks
    subrounds = cfg.subrounds
    window = jnp.float32(cfg.window_us)
    hop = jnp.float32(fcfg.spine_hop_us)
    now = carry.racks.now[0]  # racks advance in lockstep

    # ---- 1. per-rack client generation (standalone RNG streams) -----------
    frng, h_rng = jax.random.split(carry.fabric_rng)
    rngs, clientss, reqss = jax.vmap(
        lambda c_i: generate_requests(cfg, client_cfg, wl, c_i)
    )(carry.racks)

    # ---- 2. locality draws + spine-bound diversion -------------------------
    tgt = fb.draw_targets(h_rng, r_fab, carry.local_frac, reqss.op.shape)
    src = jnp.arange(r_fab, dtype=jnp.int32)[:, None, None]
    is_req = reqss.valid & ((reqss.op == OP_R_REQ) | (reqss.op == OP_W_REQ))
    remote = is_req & (tgt != src)
    local_reqs = reqss._replace(valid=reqss.valid & ~remote)

    spine_row = empty_batch(fcfg.spine_lanes // subrounds, cfg.value_pad)
    spine_sub, s_writer, s_written, in_drops = fb.exchange_to_spine(
        reqss, remote, spine_row)
    tgt_s = jax.vmap(lambda t, wr, wn: jnp.where(wn, t[wr], 0))(
        fb.racks_to_rows(tgt), s_writer, s_written)
    # re-key to the global identity: the spine caches (kidx, home) pairs
    gk = fb.global_key(spine_sub.kidx, tgt_s, r_fab)
    spine_sub = spine_sub._replace(
        kidx=gk, hkey=hash128_u32(gk), server=tgt_s)

    # ---- 3. the spine switch pass ------------------------------------------
    spine_clients = carry.spine_clients
    if fcfg.spine_scheme == "orbitcache":
        spine2, outs, intervals = pipeline.window_pipeline(
            carry.spine, spine_sub,
            recirc_gbps=fcfg.spine_recirc_gbps, window_us=cfg.window_us,
            subrounds=subrounds, max_serves=fcfg.spine_max_serves,
            key_size=key_size,
        )
        routes, flags, grids, stats = (outs.route, outs.flag, outs.grid,
                                       outs.stats)
        r_idx = jnp.arange(subrounds, dtype=jnp.float32)[:, None, None]
        serve_time = (
            now + 2.0 * hop  # up to the spine and the reply back down
            + (r_idx + 0.5) * window / subrounds
            + (grids.order.astype(jnp.float32) + 1.0)
            * intervals[:, None, None]
        )
        j = fcfg.spine_max_serves
        spine_clients = cl.account_switch_served(
            spine_clients, client_cfg,
            grids.served.reshape(-1, j),
            grids.req_kidx.reshape(-1, j),
            grids.ts.reshape(-1, j),
            grids.kidx.reshape(-1),
            serve_time.reshape(-1, j),
        )
        spine_hits = jnp.sum(stats.n_hit)
        spine_served = jnp.sum(stats.n_served)
    elif fcfg.spine_scheme == "netcache":
        def one_subround(st, pk):
            st2, route, flag, srep, n_hit = netcache_step(st, pk)
            return st2, (route, flag, srep, n_hit)

        spine2, (routes, flags, sreps, n_hits) = jax.lax.scan(
            one_subround, carry.spine, spine_sub, unroll=subrounds)
        srep_flat = sreps.reshape(-1)
        lat = jnp.full(srep_flat.shape, 1.0, jnp.float32) \
            + client_cfg.base_rtt_us + 2.0 * hop
        bucket = jnp.where(srep_flat, cl.lat_bucket(lat), cl.LAT_BUCKETS)
        spine_clients = spine_clients._replace(
            hist_switch=sat_add(spine_clients.hist_switch,
                                cl._bucket_counts(bucket)),
            rx_switch=sat_add(spine_clients.rx_switch,
                              jnp.sum(srep_flat.astype(jnp.int32))),
        )
        spine_hits = jnp.sum(n_hits)
        spine_served = jnp.sum(srep_flat.astype(jnp.int32))
    else:  # nocache spine: pure forwarding fabric
        def one_subround(st, pk):
            st2, route, flag = nocache_step(st, pk)
            return st2, (route, flag)

        spine2, (routes, flags) = jax.lax.scan(
            one_subround, carry.spine, spine_sub, unroll=subrounds)
        spine_hits = spine_served = jnp.zeros((), jnp.int32)

    # ---- 4. spine misses fall through to the owning rack's ToR -------------
    fwd_mask = (routes == ROUTE_SERVER) & spine_sub.valid
    lk, home = fb.split_global_key(spine_sub.kidx, r_fab)
    fwd_pk = spine_sub._replace(
        kidx=lk,
        hkey=hash128_u32(lk),
        server=server_of_key(lk, cfg.num_servers),
        flag=flags,
        ts=spine_sub.ts - 4.0 * hop,  # down via the spine + the reply's
                                      # return via the spine: 4 crossings
        valid=fwd_mask,
    )
    fwd_row = empty_batch(fcfg.fwd_lanes // subrounds, cfg.value_pad)
    rack_fwd, fwd_drops = fb.exchange_to_racks(
        fwd_pk, fwd_mask, home, r_fab, fwd_row)
    spine_fwd = jnp.sum(fwd_mask.astype(jnp.int32))

    # ---- 5. per-rack ToR + servers + clients (the standalone window) -------
    def rack_one(c_i, rng_i, clients_i, reqs_i, local_i, fwd_i):
        sub = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=1),
            local_i, c_i.pending, c_i.fetch, fwd_i,
        )
        return process_window(cfg, server_cfg, client_cfg, key_size, c_i,
                              rng_i, clients_i, reqs_i, sub)

    racks2, rack_metrics = jax.vmap(rack_one)(
        carry.racks, rngs, clientss, reqss, local_reqs, rack_fwd)

    new_carry = FabricCarry(
        racks=racks2,
        spine=spine2,
        spine_clients=spine_clients,
        fabric_rng=frng,
        local_frac=carry.local_frac,
        spine_drops=sat_add(carry.spine_drops, in_drops + fwd_drops),
    )
    metrics = FabricWindowMetrics(
        racks=rack_metrics,
        spine_remote=jnp.sum(remote.astype(jnp.int32)),
        spine_hits=spine_hits,
        spine_served=spine_served,
        spine_fwd=spine_fwd,
        spine_in_drops=in_drops,
        spine_fwd_drops=fwd_drops,
    )
    return new_carry, metrics


def fabric_controller_apply(
    cfg: RackConfig,
    fcfg: FabricConfig,
    ctrl_cfg: ControllerConfig,
    spine_ctrl_cfg: ControllerConfig,
    wl: WorkloadArrays,
    carry: FabricCarry,
    rack_active: jnp.ndarray,   # int32[R] per-rack active sizes
    spine_active: jnp.ndarray,  # int32[]  spine active size
) -> tuple[FabricCarry, jnp.ndarray, jnp.ndarray]:
    """One traced control-plane period across the whole fabric.

    Every rack's storage servers report their top-k (trackers reset), then

    * each orbitcache ToR runs its own :func:`controller_step` (vmapped
      over the rack axis) with F-REQ injection, exactly like a standalone
      rack; and
    * the **global spine controller** merges the per-rack reports — each
      rack's keys re-keyed to their global identity ``kidx * R + home`` —
      with the spine's own cached-key popularity and updates the spine
      cache in ``install_live`` mode: there is no F-REQ path through the
      spine (replies bypass it), so inserts go live immediately as
      metadata-served lines, and kept entries that a remote write had
      invalidated are re-validated (previously they stayed dead forever).

    Reports are truncated to the spine controller's ``k_report`` per
    server before the merge (they arrive estimate-sorted), bounding the
    spine's candidate-dedup matrix.
    """
    r_fab = fcfg.n_racks
    if cfg.scheme == "orbitcache":
        # the standalone rack period boundary, vmapped over the rack axis
        # — ONE implementation, so fabric racks can never drift from
        # standalone racks
        racks, rack_active, _upds, (top_k, top_e) = jax.vmap(
            lambda c_i, a_i: controller_window_apply(cfg, ctrl_cfg, wl,
                                                     c_i, a_i)
        )(carry.racks, rack_active)
    else:
        # baseline ToRs have no cache to update; the spine still needs
        # the per-rack server reports (trackers reset)
        servers2, top_k, top_e = jax.vmap(
            lambda s: server_reports_traced(s, ctrl_cfg.k_report)
        )(carry.racks.servers)
        racks = carry.racks._replace(servers=servers2)

    if fcfg.spine_scheme == "orbitcache":
        k_spine = min(spine_ctrl_cfg.k_report, ctrl_cfg.k_report)
        tk = top_k[:, :, :k_spine]
        te = top_e[:, :, :k_spine]
        rid = jnp.arange(r_fab, dtype=jnp.int32)[:, None, None]
        rv = tk >= 0
        gk = jnp.where(rv, tk * r_fab + rid, -1)
        gvlen = jnp.where(rv, wl.vlen[jnp.clip(tk, 0)], 0)
        sp = carry.spine
        sp2, spine_active, _upd = controller_step(
            sp, gk.reshape(-1), te.reshape(-1),
            sp.counters.overflow, sp.counters.cached_reqs, spine_active,
            spine_ctrl_cfg, install_live=True,
            report_vlen=gvlen.reshape(-1))
        carry = carry._replace(spine=sp2)

    return carry._replace(racks=racks), rack_active, spine_active


def fabric_controller_chunk(cfg: RackConfig, fcfg: FabricConfig,
                            ctrl_cfg: ControllerConfig,
                            spine_ctrl_cfg: ControllerConfig,
                            server_cfg, client_cfg, key_size: int,
                            period_w: int, n_periods: int):
    """Jitted fabric chunk of ``n_periods`` control-plane periods, mapped
    over a leading point axis.

    Period structure mirrors ``simulator.controller_chunk_body``:
    ``period_w`` fabric windows, then :func:`fabric_controller_apply` —
    all inside one compiled scan, with the per-rack (``[N, R]``) and
    spine (``[N]``) active sizes carried alongside the fabric carry.
    """
    from repro.kernels import kernel_backend
    return _fabric_controller_chunk(
        replace(cfg, seed=0), replace(fcfg, local_frac=0.0), ctrl_cfg,
        spine_ctrl_cfg, server_cfg, client_cfg, key_size, period_w,
        n_periods, kernel_backend())


@functools.lru_cache(maxsize=None)
def _fabric_controller_chunk(cfg, fcfg, ctrl_cfg, spine_ctrl_cfg, server_cfg,
                             client_cfg, key_size, period_w, n_periods,
                             kernel_backend):
    def one(wl: WorkloadArrays, carry_i, ra_i, sa_i):
        def step(c, x):
            return fabric_window_step(cfg, fcfg, server_cfg, client_cfg,
                                      key_size, wl, c, x)

        def one_period(cas, _):
            fc, ra, sa = cas
            fc, ys = jax.lax.scan(step, fc, None, length=period_w)
            fc, ra, sa = fabric_controller_apply(
                cfg, fcfg, ctrl_cfg, spine_ctrl_cfg, wl, fc, ra, sa)
            return (fc, ra, sa), ys

        (fc, ra, sa), ys = jax.lax.scan(
            one_period, (carry_i, ra_i, sa_i), None, length=n_periods)
        metrics = jax.tree.map(
            lambda a: a.reshape((n_periods * period_w,) + a.shape[2:]), ys)
        return fc, ra, sa, metrics

    def body(wl: WorkloadArrays, carry: FabricCarry, rack_active,
             spine_active):
        return jax.vmap(one, in_axes=(None, 0, 0, 0))(
            wl, carry, rack_active, spine_active)

    return jax.jit(body, donate_argnums=(1,))


def fabric_chunk(cfg: RackConfig, fcfg: FabricConfig, server_cfg, client_cfg,
                 key_size: int, n: int):
    """Jitted ``n``-window fabric chunk (donated carry, shared per config).

    The scan body maps over a leading point axis on every carry leaf
    (``fleet.BatchedFabricSimulator``).  ``seed`` and ``local_frac`` are
    normalized out of the cache key: the seed is host-side only and the
    locality fraction is a dynamic carry scalar — fabrics differing only
    in those share one compilation.
    """
    from repro.kernels import kernel_backend
    return _fabric_chunk(replace(cfg, seed=0), replace(fcfg, local_frac=0.0),
                         server_cfg, client_cfg, key_size, n,
                         kernel_backend())


@functools.lru_cache(maxsize=None)
def _fabric_chunk(cfg, fcfg, server_cfg, client_cfg, key_size, n,
                  kernel_backend):
    def body(wl: WorkloadArrays, carry: FabricCarry):
        def one(carry_i):
            def step(c, x):
                return fabric_window_step(cfg, fcfg, server_cfg, client_cfg,
                                          key_size, wl, c, x)
            return jax.lax.scan(step, carry_i, None, length=n)
        return jax.vmap(one)(carry)

    return jax.jit(body, donate_argnums=(1,))


def fabric_metrics_dict(ys: FabricWindowMetrics) -> dict[str, np.ndarray]:
    """Flatten a chunk's FabricWindowMetrics into the trace-dict idiom:
    rack metrics as ``rack_<name>``, spine counters under their own names
    (derived from the NamedTuple fields, so new counters can't be
    silently dropped by a stale key list)."""
    out = {f"rack_{k}": np.asarray(v) for k, v in ys.racks._asdict().items()}
    for k in FabricWindowMetrics._fields:
        if k != "racks":
            out[k] = np.asarray(getattr(ys, k))
    return out


# ---------------------------------------------------------------------------
# spine preload (host-side controller surgery, like the rack preloads)
# ---------------------------------------------------------------------------
def preload_spine(policy, cfg: RackConfig, fcfg: FabricConfig,
                  wl: Workload):
    """Install the *global* hot set into the spine cache.

    The hottest ``spine_cache_entries // n_racks`` local keys of every
    rack (racks share the workload, so the global head is symmetric) are
    installed under their global identities.  OrbitCache entries are
    installed live with version-0 lines (the evaluation preloads warm, as
    the paper does); NetCache goes through its own install path with its
    hardware value-size limits.
    """
    r_fab = fcfg.n_racks
    if fcfg.spine_scheme == "nocache":
        return policy
    per_rack = max(1, (fcfg.spine_cache_entries
                       if fcfg.spine_scheme == "orbitcache"
                       else fcfg.spine_netcache_entries) // r_fab)
    local = wl.hottest_keys(per_rack)
    gkeys = np.concatenate(
        [local.astype(np.int64) * r_fab + t for t in range(r_fab)]
    ).astype(np.int32)
    vlens = np.concatenate([wl.vlen_np[local]] * r_fab)
    # interleave by popularity rank so truncation keeps every rack's head
    order = np.argsort(np.tile(np.arange(len(local)), r_fab), kind="stable")
    gkeys, vlens = gkeys[order], vlens[order]

    if fcfg.spine_scheme == "netcache":
        st, _ = netcache_install(policy, gkeys, vlens, key_size=wl.cfg.key_size,
                                 value_limit=fcfg.spine_netcache_value_limit)
        return st

    c = fcfg.spine_cache_entries
    f = fcfg.spine_max_frags
    n = min(len(gkeys), c)
    gk = gkeys[:n]
    hkeys = np.asarray(policy.lookup.hkeys).copy()
    hkeys[:n] = hash128_u32_np(gk)
    occupied = np.asarray(policy.lookup.occupied).copy()
    occupied[:n] = True
    kidx = np.asarray(policy.lookup.kidx).copy()
    kidx[:n] = gk
    valid = np.asarray(policy.state.valid).copy()
    valid[:n] = True
    live = np.asarray(policy.orbit.live).copy()
    okidx = np.asarray(policy.orbit.kidx).copy()
    ovlen = np.asarray(policy.orbit.vlen).copy()
    # fragment-0 line per entry carries the whole value (spine lines are
    # metadata-served; value bytes stay zero like any un-fetched line)
    lines = np.arange(n) * f
    live[lines] = True
    okidx[lines] = gk
    ovlen[lines] = vlens[:n]
    return policy._replace(
        lookup=policy.lookup._replace(
            hkeys=jnp.asarray(hkeys), occupied=jnp.asarray(occupied),
            kidx=jnp.asarray(kidx)),
        state=policy.state._replace(valid=jnp.asarray(valid)),
        orbit=policy.orbit._replace(
            live=jnp.asarray(live), kidx=jnp.asarray(okidx),
            vlen=jnp.asarray(ovlen)),
    )


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------
@dataclass
class FabricResult:
    """Host-side aggregation of a fabric run."""
    window_us: float
    racks: list[SimResult] = field(default_factory=list)
    spine: dict = field(default_factory=dict)

    def throughput_rps(self, burn_frac: float = 0.25) -> float:
        """Fabric-wide delivered requests/sec: rack tiers + the spine tier."""
        total = sum(r.throughput_rps(burn_frac) for r in self.racks)
        sp = self.spine.get("served")
        if sp is not None:
            n = len(sp)
            b = int(n * burn_frac)
            total += float(sp[b:].sum() / ((n - b) * self.window_us * 1e-6))
        return total

    def offered_rps(self, burn_frac: float = 0.25) -> float:
        return sum(r.offered_rps(burn_frac) for r in self.racks)

    def spine_hit_ratio(self, burn_frac: float = 0.25) -> float:
        rem = self.spine["remote"]
        srv = self.spine["served"]
        b = int(len(rem) * burn_frac)
        return float(srv[b:].sum() / max(rem[b:].sum(), 1))
