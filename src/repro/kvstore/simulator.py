"""Discrete-time rack simulator (paper §5: testbed = clients + ToR switch +
rate-limited storage servers).

Time advances in windows (default 100 µs).  Each window:

  1. clients generate an open-loop Poisson batch of requests (+ pending
     correction requests);
  2. the switch policy (OrbitCache / NetCache / NoCache) processes the
     ingress — client requests, last window's server replies, and any
     controller-injected F-REQs — in ``subrounds`` sequential sub-batches
     (emulating pipeline-serialized arrival order so queues drain while
     they fill);
  3. ROUTE_SERVER packets enter per-server FIFOs drained at the configured
     rate (the bottleneck, as in the paper); ROUTE_CLIENT packets are
     accounted by clients; OrbitCache's orbit-served grid is accounted with
     a recirculation-interval latency model;
  4. server replies become next window's switch ingress.

The inner loop is one jitted ``lax.scan`` per chunk.  The control plane
(cache updates, top-k reports, dynamic sizing) runs traced inside that
scan at every period boundary (:func:`controller_window_apply`), as the
paper's switch-CPU controller runs beside the data plane; only workload
churn runs on the host between chunks.

Each stage of the window is a named scope of :mod:`repro.obs` (``repro.gen``,
``repro.switch``, ``repro.server``, ``repro.account``, ``repro.controller``),
so a device trace attributes every op to its stage.

Hot-path layout: every ingress source is kept **subround-major** ``[R, L]``
(clients emit it directly, server replies are interleaved once before they
enter the carry), so the per-window ingress assembly is a single axis-1
concatenation with no transposes of the value payload.  ``window_step`` is
a module-level pure function over (configs, WorkloadArrays, carry): the
workload arrays are explicit jit arguments (host-side churn needs no
retrace) and the same compiled chunk is shared by every fleet with the
same static config.  This module holds the configs, carries, step
functions, chunk bodies and results; the fleets that compile and run
them are in ``repro.kvstore.fleet``.

The orbitcache switch pass is ONE fused ``kernels.subround`` op per
subround (a single ``pallas_call`` on the kernel backends); the orbit
value buffer rides the window scan carry and is updated by a row scatter
of each window's install winners — with the chunk carry donated, XLA
applies it in place, so untouched ``[C*F, value_pad]`` bytes are never
copied window to window.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.baselines.netcache import init_netcache, netcache_install, netcache_step
from repro.core import pipeline
from repro.core.controller import (
    CacheController,
    ControllerConfig,
    TracedUpdate,
    controller_step,
)
from repro.core.hashing import hash128_u32, server_of_key
from repro.core.types import (
    OP_F_REQ,
    OP_NONE,
    ROUTE_CLIENT,
    ROUTE_SERVER,
    PacketBatch,
    empty_batch,
    init_switch_state,
    sat_add,
)
from repro.baselines.nocache import nocache_step
from repro.obs import stage

from . import client as cl
from .server import (
    ServerConfig,
    ServerState,
    init_servers,
    server_reports_traced,
    server_step,
)
from .workload import Workload, WorkloadArrays

HDR_BYTES = pipeline.HDR_BYTES  # canonical definition lives with the budget model


@dataclass(frozen=True)
class RackConfig:
    scheme: str = "orbitcache"          # orbitcache | netcache | nocache
    window_us: float = 100.0
    subrounds: int = 4
    max_serves: int = 8                 # J per subround (= queue size S)
    cache_entries: int = 128            # OrbitCache lookup capacity
    queue_size: int = 8                 # paper prototype: S = 8
    value_pad: int = 1438               # max payload per packet (paper §3.2)
    max_frags: int = 1
    recirc_gbps: float = 100.0          # recirculation port bandwidth
    netcache_entries: int = 10_000      # paper §5.1 preload size
    netcache_table: int = 1 << 15
    netcache_value_limit: int = 64      # paper's NetCache impl: 64 B across 8 stages
    num_servers: int = 32
    server_rps: float = 100_000.0       # per-server Rx rate limit
    server_queue: int = 64
    client_batch: int = 768
    num_clients: int = 4
    fetch_lanes: int = 256
    track_popularity: bool = False   # enable for dynamic workloads (Fig. 18)
    seed: int = 0


class WindowMetrics(NamedTuple):
    tx: jnp.ndarray             # offered requests this window
    rx_switch: jnp.ndarray      # replies served by the switch
    rx_server: jnp.ndarray      # uint32[] replies delivered from servers
                                # (delta of the wrap-safe client counter)
    served: jnp.ndarray         # int32[n_srv] per-server serves
    dropped: jnp.ndarray        # int32[n_srv] per-server drops
    backlog: jnp.ndarray        # int32[n_srv]
    hits: jnp.ndarray           # cache hits
    overflow: jnp.ndarray      # overflow requests (cached -> server)
    installs: jnp.ndarray
    crn: jnp.ndarray            # correction requests issued
    mismatches: jnp.ndarray
    fwd: jnp.ndarray            # packets this tier forwarded down
                                # (ROUTE_SERVER egress — the per-tier
                                # forward counter of the fabric topology)


class SimCarry(NamedTuple):
    policy: Any                 # SwitchState | NetCacheState | () for nocache
    servers: ServerState
    clients: cl.ClientState
    pending: PacketBatch        # server replies awaiting the switch, [R, Lp]
    fetch: PacketBatch          # controller-injected F-REQs, [R, Lf]
    rng: jax.Array
    now: jnp.ndarray            # float32 µs
    offered: jnp.ndarray        # float32 mean requests per window (Poisson λ)
    write_ratio: jnp.ndarray    # float32


# ---------------------------------------------------------------------------
# shared construction helpers (used by fleet.py and fabric_sim.py)
# ---------------------------------------------------------------------------
def tree_stack(trees):
    """Stack matching pytrees along a new leading axis (sweep/rack axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def tree_take(tree, i):
    """Slice index ``i`` off every leaf's leading axis."""
    return jax.tree.map(lambda x: x[i], tree)


def make_server_config(cfg: RackConfig) -> ServerConfig:
    return ServerConfig(
        num_servers=cfg.num_servers,
        queue_depth=cfg.server_queue,
        cap_per_window=max(1, int(round(cfg.server_rps * cfg.window_us * 1e-6))),
        value_pad=cfg.value_pad,
        max_frags=cfg.max_frags,
        track_popularity=cfg.track_popularity,
    )


def make_client_config(cfg: RackConfig) -> cl.ClientConfig:
    return cl.ClientConfig(
        batch=cfg.client_batch,
        num_clients=cfg.num_clients,
        value_pad=cfg.value_pad,
        subrounds=cfg.subrounds,
    )


def interleave(batch: PacketBatch, subrounds: int) -> PacketBatch:
    """Flat [W] lanes -> subround-major [R, W // R] (lane i -> row i % R)."""
    def f(a):
        return a.reshape((a.shape[0] // subrounds, subrounds) + a.shape[1:]
                         ).swapaxes(0, 1)
    return jax.tree.map(f, batch)


def _reply_width(cfg: RackConfig, server_cfg: ServerConfig) -> tuple[int, int]:
    """(flat server-reply width, static pad to a subround multiple)."""
    w = cfg.num_servers * server_cfg.cap_per_window * cfg.max_frags
    return w, (-w) % cfg.subrounds


def init_policy(cfg: RackConfig):
    if cfg.scheme == "orbitcache":
        return init_switch_state(
            cfg.cache_entries, cfg.queue_size, cfg.value_pad, cfg.max_frags
        )
    if cfg.scheme == "netcache":
        return init_netcache(cfg.netcache_table, cfg.netcache_value_limit)
    if cfg.scheme == "nocache":
        return ()
    raise ValueError(f"unknown scheme {cfg.scheme!r}")


def init_carry(cfg: RackConfig, server_cfg: ServerConfig,
               client_cfg: cl.ClientConfig, num_keys: int,
               offered_rps: float, write_ratio: float, seed: int) -> SimCarry:
    if cfg.fetch_lanes % cfg.subrounds:
        raise ValueError(f"fetch_lanes ({cfg.fetch_lanes}) must be a "
                         f"multiple of subrounds ({cfg.subrounds})")
    reply_w, reply_pad = _reply_width(cfg, server_cfg)
    return SimCarry(
        policy=init_policy(cfg),
        servers=init_servers(server_cfg, num_keys),
        clients=cl.init_clients(client_cfg),
        pending=interleave(empty_batch(reply_w + reply_pad, cfg.value_pad),
                           cfg.subrounds),
        fetch=interleave(empty_batch(cfg.fetch_lanes, cfg.value_pad),
                         cfg.subrounds),
        rng=jax.random.PRNGKey(seed),
        now=jnp.float32(0.0),
        offered=jnp.float32(offered_rps * cfg.window_us * 1e-6),
        write_ratio=jnp.float32(write_ratio),
    )


def build_fetch_batch(cfg: RackConfig, vlen_table: jnp.ndarray,
                      fetches: list[tuple[int, int]]) -> PacketBatch:
    """Controller F-REQs as a subround-major fetch batch (paper §3.8)."""
    fb = empty_batch(cfg.fetch_lanes, cfg.value_pad)
    n = min(len(fetches), cfg.fetch_lanes)
    if n:
        ks = np.asarray([k for k, _ in fetches[:n]], np.int32)
        kj = jnp.asarray(ks)
        fb = fb._replace(
            op=fb.op.at[:n].set(OP_F_REQ),
            kidx=fb.kidx.at[:n].set(kj),
            hkey=fb.hkey.at[:n].set(hash128_u32(kj)),
            vlen=fb.vlen.at[:n].set(vlen_table[kj]),
            server=fb.server.at[:n].set(server_of_key(kj, cfg.num_servers)),
            valid=fb.valid.at[:n].set(True),
        )
    return interleave(fb, cfg.subrounds)


def preload_policy(cfg: RackConfig, policy, controller: CacheController,
                   wl: Workload, keys: np.ndarray | None = None):
    """Install one point's hot set before measuring (paper §5.1).

    ``keys`` defaults to the workload's hottest keys, as many as the
    scheme's cache holds.  OrbitCache installs lookup entries through the
    host ``controller`` and fetches their values by F-REQs; NetCache
    installs keys and values directly; NoCache has nothing to install.
    Returns ``(policy', fetch)``, the F-REQ batch of the next window.
    """
    if keys is None:
        keys = wl.hottest_keys(cfg.cache_entries if cfg.scheme == "orbitcache"
                               else cfg.netcache_entries)
    keys = np.asarray(keys)
    fetches = []
    if cfg.scheme == "orbitcache":
        policy, fetches = controller.preload(policy, keys)
    elif cfg.scheme == "netcache":
        policy, _ = netcache_install(
            policy, keys, wl.vlen_np[keys], key_size=wl.cfg.key_size,
            value_limit=cfg.netcache_value_limit)
    return policy, build_fetch_batch(cfg, wl.vlen, fetches)


def traced_fetch_batch(cfg: RackConfig, vlen_table: jnp.ndarray,
                       fetch_kidx: jnp.ndarray, fetch_valid: jnp.ndarray,
                       ) -> PacketBatch:
    """Traced twin of :func:`build_fetch_batch` for in-scan cache updates.

    ``fetch_kidx``/``fetch_valid`` are the rank-compacted F-REQ lanes a
    :func:`repro.core.controller.controller_step` emits; lanes beyond
    ``fetch_lanes`` are dropped exactly like the host path truncates its
    fetch list.  Empty lanes match :func:`~repro.core.types.empty_batch`
    field-for-field, so the assembled ingress is indistinguishable from a
    host-built one.
    """
    w = cfg.fetch_lanes
    n = fetch_kidx.shape[0]
    if n < w:
        fetch_kidx = jnp.pad(fetch_kidx, (0, w - n), constant_values=-1)
        fetch_valid = jnp.pad(fetch_valid, (0, w - n))
    else:
        fetch_kidx, fetch_valid = fetch_kidx[:w], fetch_valid[:w]
    safe_k = jnp.where(fetch_valid, fetch_kidx, 0)
    fb = empty_batch(w, cfg.value_pad)
    fb = fb._replace(
        op=jnp.where(fetch_valid, OP_F_REQ, fb.op),
        kidx=jnp.where(fetch_valid, fetch_kidx, fb.kidx),
        hkey=jnp.where(fetch_valid[:, None], hash128_u32(safe_k), fb.hkey),
        vlen=jnp.where(fetch_valid, vlen_table[safe_k], fb.vlen),
        server=jnp.where(fetch_valid,
                         server_of_key(safe_k, cfg.num_servers), fb.server),
        valid=fetch_valid,
    )
    return interleave(fb, cfg.subrounds)


def controller_window_apply(
    cfg: RackConfig,
    ctrl_cfg: ControllerConfig,
    wl: WorkloadArrays,
    carry: SimCarry,
    active_size: jnp.ndarray,
) -> tuple[SimCarry, jnp.ndarray, TracedUpdate, tuple[jnp.ndarray, jnp.ndarray]]:
    """One traced control-plane period boundary (orbitcache racks).

    Pulls the per-server top-k reports (resetting the trackers), runs the
    pure :func:`~repro.core.controller.controller_step` cache update over
    the switch state's period counters, and queues the resulting F-REQs
    for the next window — the in-scan form of
    ``fleet.RackSimulator._control_plane_update``.  Returns ``(carry',
    active')`` plus the period's :class:`TracedUpdate` and the raw
    ``(top_kidx, top_est)`` report arrays (the fabric's spine controller
    merges them across racks).
    """
    with stage("repro.controller"):
        servers, top_k, top_e = server_reports_traced(carry.servers,
                                                      ctrl_cfg.k_report)
        sw = carry.policy
        sw2, active2, upd = controller_step(
            sw, top_k.reshape(-1), top_e.reshape(-1),
            sw.counters.overflow, sw.counters.cached_reqs, active_size,
            ctrl_cfg,
        )
        fetch = traced_fetch_batch(cfg, wl.vlen, upd.fetch_kidx,
                                   upd.fetch_valid)
    return (carry._replace(policy=sw2, servers=servers, fetch=fetch),
            active2, upd, (top_k, top_e))


# ---------------------------------------------------------------------------
# the window step (pure; shared by the rack and fabric fleets)
# ---------------------------------------------------------------------------
def generate_requests(
    cfg: RackConfig,
    client_cfg: cl.ClientConfig,
    wl: WorkloadArrays,
    carry: SimCarry,
):
    """Draw this window's open-loop client batch: ``(rng', clients', reqs)``.

    The generation half of :func:`generate_ingress`, split out so the
    cross-rack fabric (``repro.kvstore.fabric_sim``) can divert remote
    request lanes to the spine switch BEFORE the rack ingress is assembled
    while consuming exactly the same per-rack RNG stream as a standalone
    rack — the rack-local-fraction-1.0 bit-identity guarantee rests on
    this shared code path.
    """
    with stage("repro.gen"):
        rng, r_gen = jax.random.split(carry.rng)
        clients, reqs = cl.generate(
            carry.clients, client_cfg, r_gen, wl,
            carry.offered, carry.write_ratio, cfg.num_servers, carry.now,
        )
    return rng, clients, reqs


def generate_ingress(
    cfg: RackConfig,
    client_cfg: cl.ClientConfig,
    wl: WorkloadArrays,
    carry: SimCarry,
):
    """Draw this window's client batch and assemble the switch ingress.

    Every source is already subround-major [R, L], so assembly is a single
    lane-axis concat (client requests + pending server replies +
    controller F-REQs — no per-window transposes of value payloads).
    Generation and the concat are the ``repro.gen`` stage.
    Returns ``(rng', clients', reqs, sub)``.
    """
    rng, clients, reqs = generate_requests(cfg, client_cfg, wl, carry)
    with stage("repro.gen"):
        sub = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=1), reqs, carry.pending,
            carry.fetch,
        )
    return rng, clients, reqs, sub


def window_step(
    cfg: RackConfig,
    server_cfg: ServerConfig,
    client_cfg: cl.ClientConfig,
    key_size: int,
    wl: WorkloadArrays,
    carry: SimCarry,
    _=None,
) -> tuple[SimCarry, WindowMetrics]:
    rng, clients, reqs, sub = generate_ingress(cfg, client_cfg, wl, carry)
    return process_window(cfg, server_cfg, client_cfg, key_size, carry,
                          rng, clients, reqs, sub)


def process_window(
    cfg: RackConfig,
    server_cfg: ServerConfig,
    client_cfg: cl.ClientConfig,
    key_size: int,
    carry: SimCarry,
    rng: jax.Array,
    clients: cl.ClientState,
    reqs: PacketBatch,
    sub: PacketBatch,
) -> tuple[SimCarry, WindowMetrics]:
    """Run one window over a pre-assembled subround-major ingress ``sub``.

    The processing half of :func:`window_step` (switch scheme pass, server
    FIFOs, client accounting, next-window pending assembly).  Split out so
    the cross-rack fabric can append spine-forwarded lanes to the ingress
    before the rack pipeline runs; extra all-invalid lanes leave every
    table update, stat and metric bit-identical (state updates are
    mask-gated), which is what keeps the fabric's rack-local-fraction-1.0
    mode bit-identical to this standalone path.  ``reqs`` is the window's
    client batch (used for the offered-load metric only).
    """
    c = cfg
    pad_to = sub.op.shape[0] * sub.op.shape[1]

    window = jnp.float32(c.window_us)
    if c.scheme == "orbitcache":
        # The whole subround is one fused kernel call (single pallas_call on
        # the kernel backends); orbit value bytes stay out of the scan carry
        # and scatter-install once per window (core.pipeline).
        with stage("repro.switch"):
            policy, outs, intervals = pipeline.window_pipeline(
                carry.policy, sub,
                recirc_gbps=c.recirc_gbps, window_us=c.window_us,
                subrounds=c.subrounds, max_serves=c.max_serves,
                key_size=key_size,
            )
        routes, flags, grids, stats = outs.route, outs.flag, outs.grid, outs.stats
        switch_reply = jnp.zeros((pad_to,), bool)
        with stage("repro.account"):
            # account orbit-served replies (flatten subround dim into C)
            r_idx = jnp.arange(c.subrounds, dtype=jnp.float32)[:, None, None]
            serve_time = (
                carry.now
                + (r_idx + 0.5) * window / c.subrounds
                + (grids.order.astype(jnp.float32) + 1.0)
                * intervals[:, None, None]
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

        with stage("repro.switch"):
            policy, (routes, flags, sreps, n_hits) = jax.lax.scan(
                one_subround, carry.policy, sub, unroll=c.subrounds
            )
        switch_reply = sreps.reshape(-1)
        with stage("repro.account"):
            hits = jnp.sum(n_hits)
            overflow = jnp.zeros((), jnp.int32)
            installs = jnp.zeros((), jnp.int32)
            crn = jnp.zeros((), jnp.int32)
            # switch-served latency ~ switch pipeline (sub-microsecond + wire)
            lat = jnp.full((pad_to,), 1.0, jnp.float32) + client_cfg.base_rtt_us
            bucket = jnp.where(switch_reply, cl.lat_bucket(lat), cl.LAT_BUCKETS)
            clients = clients._replace(
                hist_switch=sat_add(clients.hist_switch,
                                    cl._bucket_counts(bucket)),
                rx_switch=sat_add(clients.rx_switch,
                                  jnp.sum(switch_reply.astype(jnp.int32))),
            )
            rx_sw = jnp.sum(switch_reply.astype(jnp.int32))
    else:  # nocache
        def one_subround(st, pk):
            st2, route, flag = nocache_step(st, pk)
            return st2, (route, flag)

        with stage("repro.switch"):
            policy, (routes, flags) = jax.lax.scan(one_subround, carry.policy,
                                                   sub, unroll=c.subrounds)
        switch_reply = jnp.zeros((pad_to,), bool)
        hits = overflow = installs = crn = jnp.zeros((), jnp.int32)
        rx_sw = jnp.zeros((), jnp.int32)

    with stage("repro.server"):
        route_flat = routes.reshape(-1)
        flag_flat = flags.reshape(-1)
        ing_flat = jax.tree.map(lambda a: a.reshape((pad_to,) + a.shape[2:]),
                                sub)
        to_server = (route_flat == ROUTE_SERVER) & ing_flat.valid
        servers, sout = server_step(
            carry.servers, server_cfg, ing_flat, to_server, flag_flat,
            carry.now,
        )

    with stage("repro.account"):
        # replies forwarded to clients this window (previous window's server
        # output routed through the switch)
        to_client = (route_flat == ROUTE_CLIENT) & ing_flat.valid & ~switch_reply
        rx_srv_before = clients.rx_server
        clients = cl.account_server_replies(
            clients, client_cfg, ing_flat, to_client, carry.now + window
        )
        rx_srv = clients.rx_server - rx_srv_before

        # next window's pending: server replies, statically padded to a
        # subround multiple once, then interleaved into the subround-major
        # carry layout
        reply_w, reply_pad = _reply_width(cfg, server_cfg)
        rep = sout.replies
        if reply_pad:
            pad_b = empty_batch(reply_pad, c.value_pad)
            rep = jax.tree.map(lambda a, p: jnp.concatenate([a, p]), rep, pad_b)
        pending = interleave(rep, c.subrounds)

        metrics = WindowMetrics(
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
        fetch = interleave(empty_batch(c.fetch_lanes, c.value_pad), c.subrounds)
    new_carry = SimCarry(
        policy=policy,
        servers=servers,
        clients=clients,
        pending=pending,
        fetch=fetch,
        rng=rng,
        now=carry.now + window,
        offered=carry.offered,
        write_ratio=carry.write_ratio,
    )
    return new_carry, metrics


def controller_chunk_body(cfg: RackConfig, ctrl_cfg: ControllerConfig,
                          server_cfg: ServerConfig,
                          client_cfg: cl.ClientConfig, key_size: int,
                          period_w: int, n_periods: int):
    """The one period-structured scan body: ``n_periods`` iterations of
    (``period_w`` windows, one traced cache update).  No ``lax.cond`` —
    the update sits at a static position, so the body vmaps over a point
    axis unchanged (``fleet.compiled_batched_controller_chunk``).

    Signature: ``(wl, carry, active_size) -> (carry', active', metrics,
    TracedUpdate)`` with metrics flattened to a ``[n_periods * period_w,
    ...]`` window axis and the update info stacked per period.
    """
    def body(wl: WorkloadArrays, carry: SimCarry, active_size: jnp.ndarray):
        def step(c, x):
            return window_step(cfg, server_cfg, client_cfg, key_size, wl, c, x)

        def one_period(c_a, _):
            carry, active = c_a
            carry, ys = jax.lax.scan(step, carry, None, length=period_w)
            carry, active, upd, _reports = controller_window_apply(
                cfg, ctrl_cfg, wl, carry, active)
            return (carry, active), (ys, upd)

        (carry, active), (ys, upds) = jax.lax.scan(
            one_period, (carry, active_size), None, length=n_periods)
        metrics = jax.tree.map(
            lambda a: a.reshape((n_periods * period_w,) + a.shape[2:]), ys)
        return carry, active, metrics, upds

    return body


def period_windows(controller_period_s: float | None,
                   window_us: float) -> int | None:
    """Control-plane period length in windows (None = no periodic
    controller).  The one rounding rule both fleets' ``run()`` share."""
    if not controller_period_s:
        return None
    return max(1, int(round(controller_period_s / (window_us * 1e-6))))


def chunked_run(total_windows: int, chunk_windows: int,
                period_w: int | None, use_traced_controller: bool,
                run_periods_fn, run_windows_fn) -> list[dict[str, np.ndarray]]:
    """The one chunking loop behind every fleet's ``run()``.

    Three modes:

    * traced controller (``period_w`` set, the scheme has one): chunks of
      whole periods through ``run_periods_fn``;
    * ``period_w`` without a traced controller (baseline schemes): plain
      window chunks of the same whole periods, so a baseline simulates
      the same windows as a cache at equal arguments;
    * no period: window chunks rounded to whole chunks (one compilation
      shared across sweep points and schemes).

    Period modes run whole periods: the requested window count rounds to
    the NEAREST multiple of ``period_w`` (minimum one period — a
    controller run needs a full period of traffic), so the duration error
    is bounded by half a period; the no-period mode likewise rounds to
    whole chunks.  Rates normalize per window either way.  Returns the
    per-chunk trace dicts.
    """
    traces: list[dict[str, np.ndarray]] = []
    if period_w:
        total_periods = max(1, int(round(total_windows / period_w)))
        periods_per_chunk = max(1, chunk_windows // period_w)
        # shrink to a divisor of total_periods: every chunk then carries
        # the same n_periods, so the (lru-cached, n_periods-keyed) scan
        # compiles exactly once per run — a remainder chunk would compile
        # the whole period scan a second time
        while total_periods % periods_per_chunk:
            periods_per_chunk -= 1
        step = (run_periods_fn if use_traced_controller
                else (lambda n_p, pw: run_windows_fn(n_p * pw)))
        for _ in range(total_periods // periods_per_chunk):
            traces.append(step(periods_per_chunk, period_w))
    else:
        total = max(chunk_windows,
                    (total_windows // chunk_windows) * chunk_windows)
        done = 0
        while done < total:
            n = min(chunk_windows, total - done)
            traces.append(run_windows_fn(n))
            done += n
    return traces


@dataclass
class SimResult:
    """Host-side aggregation of a run."""
    window_us: float
    traces: dict[str, np.ndarray] = field(default_factory=dict)
    hist_switch: np.ndarray | None = None
    hist_server: np.ndarray | None = None
    info: dict = field(default_factory=dict)

    # -- throughput -----------------------------------------------------------
    def throughput_rps(self, burn_frac: float = 0.25) -> float:
        rx = self.traces["rx_switch"] + self.traces["rx_server"]
        n = len(rx)
        b = int(n * burn_frac)
        return float(rx[b:].sum() / ((n - b) * self.window_us * 1e-6))

    def offered_rps(self, burn_frac: float = 0.25) -> float:
        tx = self.traces["tx"]
        n = len(tx)
        b = int(n * burn_frac)
        return float(tx[b:].sum() / ((n - b) * self.window_us * 1e-6))

    def per_server_rps(self, burn_frac: float = 0.25) -> np.ndarray:
        s = self.traces["served"]
        n = s.shape[0]
        b = int(n * burn_frac)
        return s[b:].sum(axis=0) / ((n - b) * self.window_us * 1e-6)

    def balancing_efficiency(self, burn_frac: float = 0.25) -> float:
        """Paper Fig. 13b: min server throughput / max server throughput."""
        rps = self.per_server_rps(burn_frac)
        return float(rps.min() / max(rps.max(), 1e-9))

    def max_server_drop_frac(self, burn_frac: float = 0.25) -> float:
        """Worst per-server drop fraction — a single saturated server (the
        hot-key server) shows here long before total loss moves."""
        b = int(self.traces["served"].shape[0] * burn_frac)
        served = self.traces["served"][b:].sum(axis=0)
        dropped = self.traces["dropped"][b:].sum(axis=0)
        denom = np.maximum(served + dropped, 1)
        return float((dropped / denom).max())

    def overflow_ratio(self, burn_frac: float = 0.25) -> float:
        n = len(self.traces["hits"])
        b = int(n * burn_frac)
        ov = self.traces["overflow"][b:].sum()
        hits = self.traces["hits"][b:].sum()
        return float(ov / max(ov + hits, 1))

    def latency_percentile(self, q: float, which: str = "all") -> float:
        edges = np.asarray(cl.bucket_edges_us())
        if which == "switch":
            h = self.hist_switch
        elif which == "server":
            h = self.hist_server
        else:
            h = self.hist_switch + self.hist_server
        total = h.sum()
        if total == 0:
            return float("nan")
        cum = np.cumsum(h) / total
        i = int(np.searchsorted(cum, q))
        return float(edges[min(i + 1, len(edges) - 1)])
