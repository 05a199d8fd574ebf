"""One fleet per topology: N sweep points advance in one jitted scan.

The paper's evaluation (Figs. 9–18) — like NetCache's and TurboKV's — is
dominated by wide parameter sweeps: offered load x zipf skew x value-size
mix x scheme seeds.  :class:`BatchedRackSimulator` ``vmap``s the shared
:func:`~repro.kvstore.simulator.window_step` over a leading point axis, so
a whole sweep advances in lockstep inside a single compiled ``lax.scan``
chunk.  It is the only rack simulator: :class:`RackSimulator` is a view of a
one-point fleet, and :class:`FabricSimulator` likewise of a one-point
:class:`BatchedFabricSimulator`.  A view hides the point axis and nothing
else, so every run of either drives the program the chip runs.

Sweep axes that change *data* (offered load, write ratio, Zipf table, value
sizes, RNG seed) batch freely.  Axes that change *shapes or control flow*
(scheme, cache_entries, num_servers, subrounds, ...) are static: group
points by RackConfig and run one fleet per group.

Workload arrays are stacked per-leaf only where points actually differ;
leaves shared by every point (e.g. the rank permutation in a skew sweep,
or everything in a load sweep) are passed unbatched (``in_axes=None``) so
a 16-point sweep over a 10M-key workload does not hold 16 copies of it.

Under vmap the orbitcache pass stays one fused ``kernels.subround`` call
per subround (batched over the rack axis), and the batched orbit value
buffers update by per-window winner scatters on the donated chunk carry —
untouched rows of the ``[N, C*F, value_pad]`` byte stack are never
rewritten between windows.

**Fabric mode** — :class:`BatchedFabricSimulator` vmaps the whole two-tier
:func:`repro.kvstore.fabric_sim.fabric_window_step` (R racks + spine) over
a leading sweep axis: the rack-local fraction is a carry scalar, so a
locality sweep (the Fig-9-style ``benchmarks.fabric_locality``) advances
every locality point's entire fabric in one compiled scan.  The inter-tier
lane exchange is a one-hot permutation, so it vmaps like everything else.
"""
from __future__ import annotations

import functools
from dataclasses import replace
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.controller import CacheController, ControllerConfig
from repro.obs import span

from . import client as cl
from .fabric_sim import (
    FabricConfig,
    FabricResult,
    fabric_chunk,
    fabric_controller_chunk,
    fabric_metrics_dict,
    init_fabric_carry,
    preload_spine,
)
from .server import server_reports
from .simulator import (
    RackConfig,
    SimCarry,
    SimResult,
    build_fetch_batch,
    chunked_run,
    controller_chunk_body,
    init_carry,
    make_client_config,
    make_server_config,
    period_windows,
    preload_policy,
    tree_stack as _tree_stack,
    tree_take as _tree_take,
    window_step,
)
from .workload import Workload, WorkloadArrays


def compiled_batched_chunk(cfg: RackConfig, server_cfg, client_cfg,
                           key_size: int, n: int,
                           wl_axes: WorkloadArrays):
    """Jitted vmapped ``n``-window chunk: ``(wl, carry) -> (carry, metrics)``.

    ``wl_axes`` is a WorkloadArrays of vmap in_axes (0 = stacked per point,
    None = shared); the batched carry is donated.
    The RNG seed is host-side only, so fleets differing only by seed share
    one compilation; the active kernel backend is part of the cache key
    because it is baked in at trace time.
    """
    from repro.kernels import kernel_backend
    return _compiled_batched_chunk(replace(cfg, seed=0), server_cfg,
                                   client_cfg, key_size, n, wl_axes,
                                   kernel_backend())


@functools.lru_cache(maxsize=None)
def _compiled_batched_chunk(cfg: RackConfig, server_cfg, client_cfg,
                            key_size: int, n: int,
                            wl_axes: WorkloadArrays, kernel_backend: str):
    def body(wl: WorkloadArrays, carry: SimCarry):
        def one(wl_i, carry_i):
            def step(c, x):
                return window_step(cfg, server_cfg, client_cfg, key_size,
                                   wl_i, c, x)
            return jax.lax.scan(step, carry_i, None, length=n)
        return jax.vmap(one, in_axes=(wl_axes, 0))(wl, carry)

    return jax.jit(body, donate_argnums=(1,))


def compiled_batched_controller_chunk(cfg: RackConfig, ctrl_cfg,
                                      server_cfg, client_cfg, key_size: int,
                                      period_w: int, n_periods: int,
                                      wl_axes: WorkloadArrays):
    """Jitted ``simulator.controller_chunk_body`` vmapped over points:
    every sweep point runs ``n_periods`` whole control-plane periods —
    windows AND the traced cache update — inside one compiled scan, with
    ``active_size`` a per-point carry vector.  This is what makes batched
    Fig. 18 churn sweeps possible: no host-side per-point state surgery
    between chunks.
    """
    from repro.kernels import kernel_backend
    return _compiled_batched_controller_chunk(
        replace(cfg, seed=0), ctrl_cfg, server_cfg, client_cfg, key_size,
        period_w, n_periods, wl_axes, kernel_backend())


@functools.lru_cache(maxsize=None)
def _compiled_batched_controller_chunk(cfg, ctrl_cfg, server_cfg, client_cfg,
                                       key_size, period_w, n_periods,
                                       wl_axes, kernel_backend):
    one = controller_chunk_body(cfg, ctrl_cfg, server_cfg, client_cfg,
                                key_size, period_w, n_periods)

    def body(wl: WorkloadArrays, carry: SimCarry, active_size):
        return jax.vmap(one, in_axes=(wl_axes, 0, 0))(wl, carry, active_size)

    return jax.jit(body, donate_argnums=(1,))


class BatchedRackSimulator:
    """N identically-shaped racks advancing in lockstep (one per sweep point).

    Args:
      cfg: the shared static rack configuration.
      workloads: one Workload per point, or a single Workload shared by all.
      offered_rps / write_ratios: per-point overrides (scalar broadcasts);
        default to each point's workload config.
      seeds: per-point RNG seeds (default: ``cfg.seed + point index`` so
        replicated points decorrelate).
      n_points: batch width when every other argument is scalar/shared.
    """

    def __init__(
        self,
        cfg: RackConfig,
        workloads: Workload | Sequence[Workload],
        offered_rps: float | Sequence[float] | None = None,
        write_ratios: float | Sequence[float] | None = None,
        seeds: Sequence[int] | None = None,
        n_points: int | None = None,
    ):
        if isinstance(workloads, Workload):
            workloads = [workloads]
        workloads = list(workloads)

        def _aslist(x):
            if x is None or np.isscalar(x):
                return None if x is None else [float(x)]
            return [float(v) for v in x]

        offered = _aslist(offered_rps)
        ratios = _aslist(write_ratios)
        n = max(
            len(workloads),
            len(offered) if offered else 1,
            len(ratios) if ratios else 1,
            len(seeds) if seeds is not None else 1,
            n_points or 1,
        )

        def _bcast(xs, what):
            if len(xs) == 1:
                return xs * n
            if len(xs) != n:
                raise ValueError(f"{what}: got {len(xs)} entries for "
                                 f"{n} sweep points")
            return xs

        workloads = _bcast(workloads, "workloads")
        if any(w.cfg.num_keys != workloads[0].cfg.num_keys for w in workloads):
            raise ValueError("all sweep points must share num_keys "
                             "(array shapes are static)")
        if any(w.cfg.key_size != workloads[0].cfg.key_size for w in workloads):
            raise ValueError("all sweep points must share key_size")
        offered = (_bcast(offered, "offered_rps") if offered
                   else [w.cfg.offered_rps for w in workloads])
        ratios = (_bcast(ratios, "write_ratios") if ratios
                  else [w.cfg.write_ratio for w in workloads])
        seeds = (list(seeds) if seeds is not None
                 else [cfg.seed + i for i in range(n)])
        seeds = _bcast(seeds, "seeds")

        self.cfg = cfg
        self.workloads = workloads
        self.n_points = n
        self.server_cfg = make_server_config(cfg)
        self.client_cfg = make_client_config(cfg)
        self.key_size = workloads[0].cfg.key_size
        self.controllers = [
            CacheController(ControllerConfig(
                active_size=cfg.cache_entries, max_size=cfg.cache_entries))
            for _ in range(n)
        ]
        self.carry = _tree_stack([
            init_carry(cfg, self.server_cfg, self.client_cfg,
                       workloads[i].cfg.num_keys, offered[i], ratios[i],
                       seeds[i])
            for i in range(n)
        ])
        # Stack/share workload leaves once up front; host-side churn
        # (``Workload.hot_in_swap``) is picked up by ``refresh_workloads``.
        self.refresh_workloads()

    def refresh_workloads(self) -> None:
        """Re-stack workload arrays after host-side churn (Fig. 18).

        ``hot_in_swap`` mutates the rank permutation on the Workload
        objects; the stacked device arrays are rebuilt here.  The
        stacked-vs-shared axes normally come out unchanged (churn does not
        change which points differ), so the compiled chunks are reused."""
        with span("repro.churn"):
            self._wl, self._wl_axes = self._wl_and_axes()

    # ---------------------------------------------------------- workload axes
    def _wl_and_axes(self) -> tuple[WorkloadArrays, WorkloadArrays]:
        """Stack workload leaves only where points differ (else share)."""
        ws = self.workloads
        same_zipf = all((w.cfg.zipf_alpha, w.cfg.num_keys)
                        == (ws[0].cfg.zipf_alpha, ws[0].cfg.num_keys)
                        for w in ws)
        same_vlen = all((w.cfg.value_sizes, w.cfg.value_seed, w.cfg.num_keys)
                        == (ws[0].cfg.value_sizes, ws[0].cfg.value_seed,
                            ws[0].cfg.num_keys)
                        for w in ws)
        same_perm = all(w is ws[0] or np.array_equal(w._perm_np, ws[0]._perm_np)
                        for w in ws)

        def leaf(same, get):  # (leaf, its in_axes)
            if same or get(ws[0]) is None:
                return get(ws[0]), None
            return jnp.stack([get(w) for w in ws]), 0

        leaves = dict(table=leaf(same_zipf, lambda w: w.zipf_table),
                      cdf=leaf(same_zipf, lambda w: w.cdf),
                      perm=leaf(same_perm, lambda w: w.perm),
                      vlen=leaf(same_vlen, lambda w: w.vlen))
        # one static search depth for every point: the deepest one's
        fine = max(w.zipf_fine_steps for w in ws)
        return (WorkloadArrays(**{k: v for k, (v, _) in leaves.items()},
                               fine_steps=fine),
                WorkloadArrays(**{k: a for k, (_, a) in leaves.items()},
                               fine_steps=fine))

    # -------------------------------------------------------- dynamic knobs
    def _per_point(self, x, dtype=jnp.float32):
        arr = jnp.asarray(x, dtype)
        return jnp.broadcast_to(arr, (self.n_points,)).astype(dtype)

    def set_offered(self, rps) -> None:
        """Per-point offered load (scalar broadcasts to every point)."""
        lam = self._per_point(rps) * jnp.float32(self.cfg.window_us * 1e-6)
        self.carry = self.carry._replace(offered=lam)

    def set_write_ratio(self, r) -> None:
        self.carry = self.carry._replace(write_ratio=self._per_point(r))

    def reset_stats(self) -> None:
        fresh = cl.init_clients(self.client_cfg)
        fresh = jax.tree.map(
            lambda x: jnp.stack([x] * self.n_points), fresh)
        self.carry = self.carry._replace(clients=fresh._replace(
            next_seq=self.carry.clients.next_seq,
            crn_kidx=self.carry.clients.crn_kidx,
            crn_n=self.carry.clients.crn_n,
        ))

    # ------------------------------------------------------------- preload
    def preload(self, keys: Sequence[np.ndarray] | None = None) -> None:
        """Install each point's hot set (``keys[i]``, default its
        workload's hottest), then run the 16 warm-up windows."""
        if self.cfg.scheme == "nocache":
            return
        pols, fetches = zip(*(
            preload_policy(self.cfg, _tree_take(self.carry.policy, i),
                           self.controllers[i], self.workloads[i],
                           None if keys is None else keys[i])
            for i in range(self.n_points)))
        self.carry = self.carry._replace(policy=_tree_stack(pols),
                                         fetch=_tree_stack(fetches))
        # warm: F-REQs reach servers and F-REPs install orbit lines; both
        # caches start measuring with the same windows behind them
        self.run_windows(16)

    # ------------------------------------------------------------------ run
    def _chunk(self, n: int, wl_axes: WorkloadArrays):
        return compiled_batched_chunk(self.cfg, self.server_cfg,
                                      self.client_cfg, self.key_size, n,
                                      wl_axes)

    def run_windows(self, n: int) -> dict[str, np.ndarray]:
        """Advance every point ``n`` windows; traces are [N, n, ...]."""
        carry, ys = self._chunk(n, self._wl_axes)(self._wl, self.carry)
        self.carry = carry
        with span("repro.fetch"):
            return {k: np.asarray(v) for k, v in ys._asdict().items()}

    def run_periods(self, n_periods: int, period_w: int) -> dict[str, np.ndarray]:
        """Advance every point ``n_periods`` control-plane periods of
        ``period_w`` windows each, cache updates INSIDE the compiled scan
        (per-point ``active_size`` is a carried vector — no host-side
        per-point surgery).  Traces are [N, n_periods * period_w, ...]."""
        chunk = compiled_batched_controller_chunk(
            self.cfg, self.controllers[0].cfg, self.server_cfg,
            self.client_cfg, self.key_size, period_w, n_periods,
            self._wl_axes)
        act = jnp.asarray([c.active_size for c in self.controllers],
                          jnp.int32)
        carry, act, ys, upds = chunk(self._wl, self.carry, act)
        self.carry = carry
        with span("repro.fetch"):
            for i, c in enumerate(self.controllers):
                c.active_size = int(act[i])
            self._last_update = jax.tree.map(np.asarray, upds)
            return {k: np.asarray(v) for k, v in ys._asdict().items()}

    def run(self, sim_seconds: float, chunk_windows: int = 256,
            controller_period_s: float | None = None) -> list[SimResult]:
        """Run every point for ``sim_seconds``; one SimResult per point.

        With ``controller_period_s`` set on an orbitcache fleet, the run is
        structured as whole periods and every point's cache updates happen
        inside the jitted period scan (batched Fig. 18 churn sweeps);
        otherwise the hot set stays as preloaded (all fixed-cache sweeps:
        Figs. 9, 13, 16).
        """
        c = self.cfg
        total = int(round(sim_seconds / (c.window_us * 1e-6)))
        period_w = period_windows(controller_period_s, c.window_us)
        traces = chunked_run(total, chunk_windows, period_w,
                             c.scheme == "orbitcache", self.run_periods,
                             self.run_windows)
        merged = {k: np.concatenate([t[k] for t in traces], axis=1)
                  for k in traces[0]}
        with span("repro.fetch"):
            hist_sw = np.asarray(self.carry.clients.hist_switch)
            hist_srv = np.asarray(self.carry.clients.hist_server)
        results = []
        for i in range(self.n_points):
            res = SimResult(
                window_us=c.window_us,
                traces={k: v[i] for k, v in merged.items()},
            )
            res.hist_switch = hist_sw[i]
            res.hist_server = hist_srv[i]
            res.info = dict(scheme=c.scheme, point=i,
                            active_size=self.controllers[i].active_size)
            results.append(res)
        return results


# ---------------------------------------------------------------------------
# fabric mode: vmapped two-tier (racks + spine) sweeps
# ---------------------------------------------------------------------------
class BatchedFabricSimulator:
    """N whole fabrics (R racks + spine each) advancing in lockstep.

    One fabric per sweep point; the points share the rack/fabric geometry
    and the workload but may differ in rack-local fraction, offered load
    and RNG seeds — the locality-sweep benchmark runs all its points in
    one compiled scan this way.  Point ``i``'s rack ``r`` is seeded
    ``seeds[i] + r`` and its homing draws ``seeds[i] + 0x0FAB``; the
    default is ``seeds[i] = cfg.seed + 1000 * i``.
    """

    def __init__(self, cfg: RackConfig, fcfg: FabricConfig, wl: Workload,
                 local_fracs: Sequence[float] | None = None,
                 offered_rps: Sequence[float] | float | None = None,
                 seeds: Sequence[int] | None = None,
                 n_points: int | None = None):
        if fcfg.spine_lanes % cfg.subrounds or fcfg.fwd_lanes % cfg.subrounds:
            raise ValueError(
                f"spine_lanes ({fcfg.spine_lanes}) and fwd_lanes "
                f"({fcfg.fwd_lanes}) must be multiples of subrounds "
                f"({cfg.subrounds})")
        n = max(len(local_fracs) if local_fracs is not None else 1,
                len(offered_rps) if isinstance(offered_rps, (list, tuple))
                else 1,
                len(seeds) if seeds is not None else 1,
                n_points or 1)

        def _bcast(xs, what):
            xs = list(xs)
            if len(xs) == 1:
                return xs * n
            if len(xs) != n:
                raise ValueError(f"{what}: got {len(xs)} entries for "
                                 f"{n} sweep points")
            return xs

        fracs = _bcast(local_fracs if local_fracs is not None
                       else [fcfg.local_frac], "local_fracs")
        seeds = _bcast(seeds if seeds is not None
                       else [cfg.seed + 1000 * i for i in range(n)], "seeds")
        if offered_rps is None:
            offered_rps = wl.cfg.offered_rps
        if np.isscalar(offered_rps):
            offered_rps = [float(offered_rps)]
        offered = _bcast(offered_rps, "offered_rps")
        self.cfg = cfg
        self.fcfg = fcfg
        self.wl = wl
        self.n_points = n
        self.server_cfg = make_server_config(cfg)
        self.client_cfg = make_client_config(cfg)
        self.key_size = wl.cfg.key_size
        self.controllers = [
            [CacheController(ControllerConfig(
                active_size=cfg.cache_entries, max_size=cfg.cache_entries))
             for _ in range(fcfg.n_racks)]
            for _ in range(n)
        ]
        self.spine_controllers = [
            CacheController(ControllerConfig(
                active_size=fcfg.spine_cache_entries,
                max_size=fcfg.spine_cache_entries,
                k_report=fcfg.spine_k_report))
            for _ in range(n)
        ]
        self.carry = _tree_stack([
            init_fabric_carry(cfg, fcfg, self.server_cfg, self.client_cfg,
                              wl.cfg.num_keys, offered[i],
                              wl.cfg.write_ratio, fracs[i], seeds[i])
            for i in range(n)
        ])

    def preload(self, warm_windows: int = 16) -> None:
        """Install every point's rack hot sets and global spine hot set,
        then warm up: the warm windows run through the SAME vmapped chunk
        as the measurement."""
        c, racks = self.cfg, self.carry.racks
        installs = [[preload_policy(c, _tree_take(racks.policy, (i, r)),
                                    self.controllers[i][r], self.wl)
                     for r in range(self.fcfg.n_racks)]
                    for i in range(self.n_points)]

        def stack(j):  # [point][rack] -> leaves [N, R, ...]
            return _tree_stack([_tree_stack([rack[j] for rack in point])
                                for point in installs])

        spine = _tree_stack([
            preload_spine(_tree_take(self.carry.spine, i), c, self.fcfg,
                          self.wl)
            for i in range(self.n_points)])
        self.carry = self.carry._replace(
            racks=racks._replace(policy=stack(0), fetch=stack(1)),
            spine=spine)
        if c.scheme != "nocache" and warm_windows > 0:
            # let rack F-REQs reach servers and F-REPs install orbit lines;
            # NetCache racks warm alike, as a rack fleet's preload does
            self.run_windows(warm_windows)

    def run_windows(self, n: int) -> dict[str, np.ndarray]:
        """Advance every fabric ``n`` windows; rack traces are
        [N, n, R, ...], spine traces [N, n]."""
        chunk = fabric_chunk(self.cfg, self.fcfg, self.server_cfg,
                             self.client_cfg, self.key_size, n)
        carry, ys = chunk(self.wl.arrays, self.carry)
        self.carry = carry
        return fabric_metrics_dict(ys)

    def run_periods(self, n_periods: int, period_w: int) -> dict[str, np.ndarray]:
        """Advance every fabric ``n_periods`` control-plane periods: all
        per-rack ToR controllers and every point's global spine controller
        run inside one vmapped compiled scan (active sizes carried as
        [N, R] / [N] vectors)."""
        chunk = fabric_controller_chunk(
            self.cfg, self.fcfg, self.controllers[0][0].cfg,
            self.spine_controllers[0].cfg, self.server_cfg,
            self.client_cfg, self.key_size, period_w, n_periods)
        ra = jnp.asarray([[c.active_size for c in ctrls]
                          for ctrls in self.controllers], jnp.int32)
        sa = jnp.asarray([s.active_size for s in self.spine_controllers],
                         jnp.int32)
        carry, ra, sa, ys = chunk(self.wl.arrays, self.carry, ra, sa)
        self.carry = carry
        for i, ctrls in enumerate(self.controllers):
            for j, c in enumerate(ctrls):
                c.active_size = int(ra[i, j])
            self.spine_controllers[i].active_size = int(sa[i])
        return fabric_metrics_dict(ys)

    def run(self, sim_seconds: float, chunk_windows: int = 256,
            controller_period_s: float | None = None) -> list[FabricResult]:
        """Run every fabric for ``sim_seconds``; one FabricResult per
        point.  With ``controller_period_s`` set and a cache on either
        tier, the run is whole periods with every controller in the scan."""
        c = self.cfg
        total = int(round(sim_seconds / (c.window_us * 1e-6)))
        period_w = period_windows(controller_period_s, c.window_us)
        has_ctrl = (c.scheme == "orbitcache"
                    or self.fcfg.spine_scheme == "orbitcache")
        traces = chunked_run(total, chunk_windows, period_w, has_ctrl,
                             self.run_periods, self.run_windows)
        merged = {k: np.concatenate([t[k] for t in traces], axis=1)
                  for k in traces[0]}
        racks, spine = jax.device_get((self.carry.racks.clients,
                                       self.carry.spine_clients))
        results = []
        for i in range(self.n_points):
            res = FabricResult(window_us=c.window_us)
            for r in range(self.fcfg.n_racks):
                rr = SimResult(
                    window_us=c.window_us,
                    traces={k[len("rack_"):]: v[i][:, r]
                            for k, v in merged.items()
                            if k.startswith("rack_")},
                )
                rr.hist_switch = racks.hist_switch[i, r]
                rr.hist_server = racks.hist_server[i, r]
                rr.info = dict(scheme=c.scheme, rack=r)
                res.racks.append(rr)
            res.spine = dict(
                scheme=self.fcfg.spine_scheme,
                active_size=self.spine_controllers[i].active_size,
                remote=merged["spine_remote"][i],
                hits=merged["spine_hits"][i],
                served=merged["spine_served"][i],
                fwd=merged["spine_fwd"][i],
                in_drops=merged["spine_in_drops"][i],
                fwd_drops=merged["spine_fwd_drops"][i],
                hist_switch=spine.hist_switch[i],
                rx_switch=int(spine.rx_switch[i]),
                mismatches=int(spine.mismatches[i]),
            )
            results.append(res)
        return results


# ---------------------------------------------------------------------------
# one-point views
# ---------------------------------------------------------------------------
class _OnePointView:
    """A fleet of one point with the point axis hidden: ``carry`` and the
    traces are point 0's, and ``run`` returns its one result."""

    def __init__(self, fleet):
        self.fleet = fleet
        self.cfg = fleet.cfg
        self.server_cfg = fleet.server_cfg
        self.client_cfg = fleet.client_cfg
        self.key_size = fleet.key_size

    @property
    def carry(self):
        return _tree_take(self.fleet.carry, 0)

    @carry.setter
    def carry(self, carry) -> None:
        self.fleet.carry = _tree_stack([carry])

    def run_windows(self, n: int) -> dict[str, np.ndarray]:
        return {k: v[0] for k, v in self.fleet.run_windows(n).items()}

    def run_periods(self, n_periods: int, period_w: int) -> dict[str, np.ndarray]:
        return {k: v[0] for k, v in
                self.fleet.run_periods(n_periods, period_w).items()}

    def run(self, sim_seconds: float, chunk_windows: int = 256,
            controller_period_s: float | None = None):
        return self.fleet.run(sim_seconds, chunk_windows,
                              controller_period_s)[0]


class RackSimulator(_OnePointView):
    """One storage rack under a switch policy: a one-point
    :class:`BatchedRackSimulator`.  After host-side churn of ``wl``
    (``Workload.hot_in_swap``), call :meth:`refresh_workloads`, as on the
    fleet."""

    def __init__(self, cfg: RackConfig, wl: Workload):
        super().__init__(BatchedRackSimulator(cfg, wl, n_points=1))
        self.wl = wl

    @property
    def controller(self) -> CacheController:
        return self.fleet.controllers[0]

    def set_offered(self, rps: float) -> None:
        self.fleet.set_offered(rps)

    def set_write_ratio(self, r: float) -> None:
        self.fleet.set_write_ratio(r)

    def reset_stats(self) -> None:
        """Zero client histograms/counters (per-phase measurements)."""
        self.fleet.reset_stats()

    def refresh_workloads(self) -> None:
        self.fleet.refresh_workloads()

    def preload(self, keys: np.ndarray) -> None:
        """Install the hot set ``keys`` before measuring (paper §5.1), then
        run the 16 warm-up windows."""
        self.fleet.preload([keys])

    def _control_plane_update(self) -> None:
        """Host-side cache update of an orbitcache rack (switch counters +
        server top-k reports, §3.8) — the oracle form of
        :func:`~repro.kvstore.simulator.controller_window_apply`, kept for
        tests; runs use the traced in-scan path (:meth:`run_periods`)."""
        carry = self.carry
        servers, reports = server_reports(carry.servers,
                                          self.controller.cfg.k_report)
        sw = carry.policy
        sw2, info = self.controller.update(
            sw, reports, int(sw.counters.overflow),
            int(sw.counters.cached_reqs))
        self.carry = carry._replace(
            policy=sw2, servers=servers,
            fetch=build_fetch_batch(self.cfg, self.wl.vlen, info.fetches))


class FabricSimulator(_OnePointView):
    """R racks + one spine switch advancing in lockstep: a one-point
    :class:`BatchedFabricSimulator`."""

    def __init__(self, cfg: RackConfig, fcfg: FabricConfig, wl: Workload):
        super().__init__(BatchedFabricSimulator(cfg, fcfg, wl, n_points=1))
        self.fcfg = fcfg
        self.wl = wl

    def preload(self, warm_windows: int = 16) -> None:
        """Install rack hot sets + the global spine hot set, then warm up."""
        self.fleet.preload(warm_windows)
