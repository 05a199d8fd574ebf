"""Batched multi-rack sweeps: one jitted scan runs N sweep points at once.

The paper's evaluation (Figs. 9–18) — like NetCache's and TurboKV's — is
dominated by wide parameter sweeps: offered load x zipf skew x value-size
mix x scheme seeds.  Running each point as its own serial
:class:`~repro.kvstore.simulator.RackSimulator` leaves the accelerator
idle between many small dispatches; :class:`BatchedRackSimulator` instead
``vmap``s the shared :func:`~repro.kvstore.simulator.window_step` over a
leading rack axis, so a whole sweep advances in lockstep inside a single
compiled ``lax.scan`` chunk.

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

from repro.baselines.netcache import netcache_install
from repro.core.controller import CacheController, ControllerConfig
from repro.obs import span

from . import client as cl
from .simulator import (
    RackConfig,
    SimCarry,
    SimResult,
    build_fetch_batch,
    controller_chunk_body,
    init_carry,
    make_client_config,
    make_server_config,
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
    None = shared); the batched carry is donated like the serial path.
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
    """Vmapped twin of ``simulator.compiled_controller_chunk``: every sweep
    point runs ``n_periods`` whole control-plane periods — windows AND the
    traced cache update — inside one compiled scan, with ``active_size``
    a per-point carry vector.  This is what makes batched Fig. 18 churn
    sweeps possible: no host-side per-point state surgery between chunks.
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
        """Install each point's hot set, then run warm-up windows."""
        c = self.cfg
        if c.scheme == "nocache":
            return
        if keys is None:
            k = (c.cache_entries if c.scheme == "orbitcache"
                 else c.netcache_entries)
            keys = [w.hottest_keys(k) for w in self.workloads]
        if c.scheme == "orbitcache":
            pols, fbs = [], []
            for i in range(self.n_points):
                pol, fetches = self.controllers[i].preload(
                    _tree_take(self.carry.policy, i), np.asarray(keys[i]))
                pols.append(pol)
                fbs.append(build_fetch_batch(c, self.workloads[i].vlen,
                                             fetches))
            self.carry = self.carry._replace(
                policy=_tree_stack(pols), fetch=_tree_stack(fbs))
        elif c.scheme == "netcache":
            pols = []
            for i in range(self.n_points):
                ks = np.asarray(keys[i])
                st, _ = netcache_install(
                    _tree_take(self.carry.policy, i), ks,
                    self.workloads[i].vlen_np[ks],
                    key_size=self.key_size,
                    value_limit=c.netcache_value_limit,
                )
                pols.append(st)
            self.carry = self.carry._replace(policy=_tree_stack(pols))
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
        from .simulator import chunked_run, period_windows
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
    one compiled scan this way.
    """

    def __init__(self, cfg: RackConfig, fcfg, wl: Workload,
                 local_fracs: Sequence[float] | None = None,
                 offered_rps: Sequence[float] | float | None = None,
                 seeds: Sequence[int] | None = None,
                 n_points: int | None = None):
        from .fabric_sim import FabricSimulator

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
        if offered_rps is not None and np.isscalar(offered_rps):
            offered_rps = [float(offered_rps)]
        offered = (_bcast(offered_rps, "offered_rps")
                   if offered_rps is not None else None)
        self.cfg = cfg
        self.fcfg = fcfg
        self.wl = wl
        self.n_points = n
        # build each point as a serial FabricSimulator (host-side preload
        # surgery is per point), then stack the carries
        self._sims = [
            FabricSimulator(replace(cfg, seed=seeds[i]), fcfg, wl)
            for i in range(n)
        ]
        for i, sim in enumerate(self._sims):
            sim.set_local_frac(fracs[i])
        self.server_cfg = self._sims[0].server_cfg
        self.client_cfg = self._sims[0].client_cfg
        self.key_size = self._sims[0].key_size
        self.carry = None  # stacked after preload
        if offered is not None:
            for sim, rps in zip(self._sims, offered):
                sim.set_offered(rps)

    def preload(self, warm_windows: int = 16) -> None:
        if self._sims is None:
            raise RuntimeError("fabric sweep already stacked — preload once, "
                               "before the first run_windows()")
        # host-side table surgery per point, warm-up batched: the warm
        # windows run through the SAME vmapped chunk as the measurement,
        # so no serial fabric step is ever compiled for a sweep
        warm = any(s.cfg.scheme != "nocache" for s in self._sims)
        for sim in self._sims:
            sim.preload(warm_windows=0)
        self._stack()
        if warm and warm_windows > 0:
            self.run_windows(warm_windows)

    def _stack(self) -> None:
        self.carry = _tree_stack([s.carry for s in self._sims])
        self._controllers = [s.controllers for s in self._sims]
        self._spine_controllers = [s.spine_controller for s in self._sims]
        # the per-point carries are dead once stacked (and stale after the
        # first run) — drop them so device state isn't held twice
        self._sims = None

    def run_windows(self, n: int) -> dict[str, np.ndarray]:
        """Advance every fabric ``n`` windows; rack traces are
        [N, n, R, ...], spine traces [N, n]."""
        if self.carry is None:
            self._stack()
        from .fabric_sim import fabric_chunk, fabric_metrics_dict
        chunk = fabric_chunk(self.cfg, self.fcfg, self.server_cfg,
                             self.client_cfg, self.key_size, n, vmapped=True)
        carry, ys = chunk(self.wl.arrays, self.carry)
        self.carry = carry
        return fabric_metrics_dict(ys)

    def run_periods(self, n_periods: int, period_w: int) -> dict[str, np.ndarray]:
        """Advance every fabric ``n_periods`` control-plane periods: all
        per-rack ToR controllers and every point's global spine controller
        run inside one vmapped compiled scan (active sizes carried as
        [N, R] / [N] vectors)."""
        if self.carry is None:
            self._stack()
        from .fabric_sim import fabric_controller_chunk, fabric_metrics_dict
        chunk = fabric_controller_chunk(
            self.cfg, self.fcfg, self._controllers[0][0].cfg,
            self._spine_controllers[0].cfg, self.server_cfg,
            self.client_cfg, self.key_size, period_w, n_periods,
            vmapped=True)
        ra = jnp.asarray([[c.active_size for c in ctrls]
                          for ctrls in self._controllers], jnp.int32)
        sa = jnp.asarray([s.active_size for s in self._spine_controllers],
                         jnp.int32)
        carry, ra, sa, ys = chunk(self.wl.arrays, self.carry, ra, sa)
        self.carry = carry
        for i, ctrls in enumerate(self._controllers):
            for j, c in enumerate(ctrls):
                c.active_size = int(ra[i, j])
            self._spine_controllers[i].active_size = int(sa[i])
        return fabric_metrics_dict(ys)
