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
bit-identical to R independent :class:`simulator.RackSimulator` /
:class:`fleet.BatchedRackSimulator` racks — regression-tested in
``tests/test_fabric.py``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import Any, NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.baselines.netcache import init_netcache, netcache_install, netcache_step
from repro.baselines.nocache import nocache_step
from repro.core import fabric as fb
from repro.core import pipeline
from repro.core.controller import (
    CacheController,
    ControllerConfig,
    controller_step,
)
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
    build_fetch_batch,
    controller_window_apply,
    init_carry,
    make_client_config,
    make_server_config,
    process_window,
    generate_requests,
    tree_stack as _tree_stack,
    tree_take as _tree_take,
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


# ---------------------------------------------------------------------------
# the fabric window step (pure; shared by serial and batched simulators)
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
                            period_w: int, n_periods: int,
                            vmapped: bool = False):
    """Jitted fabric chunk of ``n_periods`` control-plane periods.

    Period structure mirrors ``simulator.compiled_controller_chunk``:
    ``period_w`` fabric windows, then :func:`fabric_controller_apply` —
    all inside one compiled scan, with the per-rack and spine
    ``active_size`` scalars carried alongside the fabric carry.
    """
    from repro.kernels import kernel_backend
    return _fabric_controller_chunk(
        replace(cfg, seed=0), replace(fcfg, local_frac=0.0), ctrl_cfg,
        spine_ctrl_cfg, server_cfg, client_cfg, key_size, period_w,
        n_periods, kernel_backend(), vmapped)


@functools.lru_cache(maxsize=None)
def _fabric_controller_chunk(cfg, fcfg, ctrl_cfg, spine_ctrl_cfg, server_cfg,
                             client_cfg, key_size, period_w, n_periods,
                             kernel_backend, vmapped):
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
        if vmapped:
            return jax.vmap(one, in_axes=(None, 0, 0, 0))(
                wl, carry, rack_active, spine_active)
        return one(wl, carry, rack_active, spine_active)

    return jax.jit(body, donate_argnums=(1,))


def fabric_chunk(cfg: RackConfig, fcfg: FabricConfig, server_cfg, client_cfg,
                 key_size: int, n: int, vmapped: bool = False):
    """Jitted ``n``-window fabric chunk (donated carry, shared per config).

    With ``vmapped`` the same scan body maps over a leading sweep axis on
    every carry leaf (``fleet.BatchedFabricSimulator``).  ``seed`` and
    ``local_frac`` are normalized out of the cache key: the seed is
    host-side only and the locality fraction is a dynamic carry scalar —
    fabrics differing only in those share one compilation.
    """
    from repro.kernels import kernel_backend
    return _fabric_chunk(replace(cfg, seed=0), replace(fcfg, local_frac=0.0),
                         server_cfg, client_cfg, key_size, n,
                         kernel_backend(), vmapped)


@functools.lru_cache(maxsize=None)
def _fabric_chunk(cfg, fcfg, server_cfg, client_cfg, key_size, n,
                  kernel_backend, vmapped):
    def body(wl: WorkloadArrays, carry: FabricCarry):
        def one(carry_i):
            def step(c, x):
                return fabric_window_step(cfg, fcfg, server_cfg, client_cfg,
                                          key_size, wl, c, x)
            return jax.lax.scan(step, carry_i, None, length=n)
        if vmapped:
            return jax.vmap(one)(carry)
        return one(carry)

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
# host-side drivers
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


class FabricSimulator:
    """R racks + one spine switch advancing in lockstep."""

    def __init__(self, cfg: RackConfig, fcfg: FabricConfig, wl: Workload,
                 seeds: Sequence[int] | None = None):
        if fcfg.spine_lanes % cfg.subrounds or fcfg.fwd_lanes % cfg.subrounds:
            raise ValueError(
                f"spine_lanes ({fcfg.spine_lanes}) and fwd_lanes "
                f"({fcfg.fwd_lanes}) must be multiples of subrounds "
                f"({cfg.subrounds})")
        self.cfg = cfg
        self.fcfg = fcfg
        self.wl = wl
        self.server_cfg = make_server_config(cfg)
        self.client_cfg = make_client_config(cfg)
        self.key_size = wl.cfg.key_size
        r = fcfg.n_racks
        seeds = (list(seeds) if seeds is not None
                 else [cfg.seed + i for i in range(r)])
        if len(seeds) != r:
            raise ValueError(f"need {r} seeds, got {len(seeds)}")
        self.controllers = [
            CacheController(ControllerConfig(
                active_size=cfg.cache_entries, max_size=cfg.cache_entries))
            for _ in range(r)
        ]
        self.spine_controller = CacheController(ControllerConfig(
            active_size=fcfg.spine_cache_entries,
            max_size=fcfg.spine_cache_entries,
            k_report=fcfg.spine_k_report))
        racks = _tree_stack([
            init_carry(cfg, self.server_cfg, self.client_cfg,
                       wl.cfg.num_keys, wl.cfg.offered_rps,
                       wl.cfg.write_ratio, seeds[i])
            for i in range(r)
        ])
        self.carry = FabricCarry(
            racks=racks,
            spine=init_spine_policy(cfg, fcfg),
            spine_clients=cl.init_clients(self.client_cfg),
            fabric_rng=jax.random.PRNGKey(cfg.seed + 0x0FAB),
            local_frac=jnp.float32(fcfg.local_frac),
            spine_drops=jnp.zeros((), COUNTER_DTYPE),
        )

    # -- dynamic knobs (no recompilation) ------------------------------------
    def set_local_frac(self, frac: float) -> None:
        self.carry = self.carry._replace(local_frac=jnp.float32(frac))

    def set_offered(self, rps: float) -> None:
        lam = jnp.full((self.fcfg.n_racks,),
                       rps * self.cfg.window_us * 1e-6, jnp.float32)
        self.carry = self.carry._replace(
            racks=self.carry.racks._replace(offered=lam))

    def reset_stats(self) -> None:
        fresh = cl.init_clients(self.client_cfg)
        stacked = jax.tree.map(
            lambda x: jnp.stack([x] * self.fcfg.n_racks), fresh)
        racks = self.carry.racks
        self.carry = self.carry._replace(
            racks=racks._replace(clients=stacked._replace(
                next_seq=racks.clients.next_seq,
                crn_kidx=racks.clients.crn_kidx,
                crn_n=racks.clients.crn_n,
            )),
            spine_clients=fresh._replace(
                next_seq=self.carry.spine_clients.next_seq,
                crn_kidx=self.carry.spine_clients.crn_kidx,
                crn_n=self.carry.spine_clients.crn_n,
            ),
        )

    # ------------------------------------------------------------- preload
    def preload(self, warm_windows: int = 16) -> None:
        """Install rack hot sets + the global spine hot set, then warm up."""
        c = self.cfg
        fcfg = self.fcfg
        if c.scheme == "orbitcache":
            pols, fbs = [], []
            for i in range(fcfg.n_racks):
                pol, fetches = self.controllers[i].preload(
                    _tree_take(self.carry.racks.policy, i),
                    self.wl.hottest_keys(c.cache_entries))
                pols.append(pol)
                fbs.append(build_fetch_batch(c, self.wl.vlen, fetches))
            self.carry = self.carry._replace(
                racks=self.carry.racks._replace(
                    policy=_tree_stack(pols), fetch=_tree_stack(fbs)))
        elif c.scheme == "netcache":
            pols = []
            ks = self.wl.hottest_keys(c.netcache_entries)
            for i in range(fcfg.n_racks):
                st, _ = netcache_install(
                    _tree_take(self.carry.racks.policy, i), ks,
                    self.wl.vlen_np[ks], key_size=self.key_size,
                    value_limit=c.netcache_value_limit)
                pols.append(st)
            self.carry = self.carry._replace(
                racks=self.carry.racks._replace(policy=_tree_stack(pols)))
        self.carry = self.carry._replace(
            spine=preload_spine(self.carry.spine, c, fcfg, self.wl))
        if c.scheme != "nocache" and warm_windows > 0:
            # let rack F-REQs reach servers and F-REPs install orbit lines;
            # NetCache racks warm alike, as a rack fleet's preload does
            self.run_windows(warm_windows)

    # ------------------------------------------------------------------ run
    def _chunk(self, n: int):
        return fabric_chunk(self.cfg, self.fcfg, self.server_cfg,
                            self.client_cfg, self.key_size, n)

    def run_windows(self, n: int) -> dict[str, np.ndarray]:
        """Advance the fabric ``n`` windows.  Rack traces are [n, R, ...]."""
        carry, ys = self._chunk(n)(self.wl.arrays, self.carry)
        self.carry = carry
        return fabric_metrics_dict(ys)

    def run_periods(self, n_periods: int, period_w: int) -> dict[str, np.ndarray]:
        """Advance ``n_periods`` control-plane periods of ``period_w``
        windows: per-rack ToR controllers AND the global spine controller
        run inside the compiled scan (:func:`fabric_controller_apply`)."""
        chunk = fabric_controller_chunk(
            self.cfg, self.fcfg, self.controllers[0].cfg,
            self.spine_controller.cfg, self.server_cfg, self.client_cfg,
            self.key_size, period_w, n_periods)
        ra = jnp.asarray([c.active_size for c in self.controllers],
                         jnp.int32)
        sa = jnp.asarray(self.spine_controller.active_size, jnp.int32)
        carry, ra, sa, ys = chunk(self.wl.arrays, self.carry, ra, sa)
        self.carry = carry
        for i, c in enumerate(self.controllers):
            c.active_size = int(ra[i])
        self.spine_controller.active_size = int(sa)
        return fabric_metrics_dict(ys)

    def run(self, sim_seconds: float, chunk_windows: int = 256,
            controller_period_s: float | None = None) -> FabricResult:
        from .simulator import chunked_run, period_windows
        c = self.cfg
        total = int(round(sim_seconds / (c.window_us * 1e-6)))
        period_w = period_windows(controller_period_s, c.window_us)
        has_ctrl = (c.scheme == "orbitcache"
                    or self.fcfg.spine_scheme == "orbitcache")
        traces = chunked_run(total, chunk_windows, period_w, has_ctrl,
                             self.run_periods, self.run_windows)
        merged = {k: np.concatenate([t[k] for t in traces], axis=0)
                  for k in traces[0]}
        hist_sw = np.asarray(self.carry.racks.clients.hist_switch)
        hist_srv = np.asarray(self.carry.racks.clients.hist_server)
        res = FabricResult(window_us=c.window_us)
        for i in range(self.fcfg.n_racks):
            r = SimResult(
                window_us=c.window_us,
                traces={k[len("rack_"):]: v[:, i] for k, v in merged.items()
                        if k.startswith("rack_")},
            )
            r.hist_switch = hist_sw[i]
            r.hist_server = hist_srv[i]
            r.info = dict(scheme=c.scheme, rack=i)
            res.racks.append(r)
        res.spine = dict(
            scheme=self.fcfg.spine_scheme,
            active_size=self.spine_controller.active_size,
            remote=merged["spine_remote"],
            hits=merged["spine_hits"],
            served=merged["spine_served"],
            fwd=merged["spine_fwd"],
            in_drops=merged["spine_in_drops"],
            fwd_drops=merged["spine_fwd_drops"],
            hist_switch=np.asarray(self.carry.spine_clients.hist_switch),
            rx_switch=int(self.carry.spine_clients.rx_switch),
            mismatches=int(self.carry.spine_clients.mismatches),
        )
        return res
