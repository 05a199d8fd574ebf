"""The production entry points the linter covers.

Each :class:`EntryPoint` builds a *tiny but production-shaped* instance of
one compiled surface — same code paths, minimal geometry — and exposes:

  * ``jaxpr()``       — the traced ClosedJaxpr (cached) for jaxpr rules;
  * ``expected_pallas`` — trace-time ``pallas_call`` counts per backend
    kind (``"kernel"`` = pallas/interpret, ``"ref"`` = 0 everywhere);
  * ``donation()``    — optional ``(jit_fn, example_args)`` for the
    donation rule (entry points whose carry must be donated);
  * ``retrace()``     — optional ``(jit_fn, thunk_a, thunk_b, axis)`` for
    the retrace-guard rule: both thunks build full argument tuples that
    differ ONLY in the documented traced axis (fresh carries each call —
    donation invalidates the previous one).

The chunk entry points are the fleets' own compiled chunks, mapped over
two sweep points as the chip runs them; a vmapped ``pallas_call`` is still
one equation.  The kernel-backend expectation is a measured architectural
constant, not a tolerance: the fused subround is ONE ``pallas_call``; the
controller chunk adds the server cms track kernel and the three
hot-gather uses of the traced report/merge path (5 total); a fabric chunk
runs rack + spine subround kernels (2 — no controller, so no tracking);
the fabric controller chunk runs both tiers' subrounds, the rack-server
cms track, and both tiers' hot-gather triples (9).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

PAD = 32  # tiny value payload for lint builds


@dataclass
class EntryPoint:
    name: str
    make_jaxpr: Callable[[], jex_core.ClosedJaxpr]
    expected_pallas: dict = field(default_factory=lambda: {"ref": 0})
    donation: Callable | None = None   # () -> (jit_fn, args)
    retrace: Callable | None = None    # () -> (jit_fn, thunk_a, thunk_b, axis)
    _jaxpr: object = field(default=None, repr=False)

    def jaxpr(self):
        if self._jaxpr is None:
            self._jaxpr = self.make_jaxpr()
        return self._jaxpr


def backend_kind() -> str:
    """``"ref"`` or ``"kernel"`` for the active REPRO_KERNEL_BACKEND."""
    from repro.kernels import kernel_backend
    return "ref" if kernel_backend() == "ref" else "kernel"


# ---------------------------------------------------------------------------
# tiny shared geometry
# ---------------------------------------------------------------------------
def _rack_cfg(**kw):
    from repro.kvstore.simulator import RackConfig
    base = dict(scheme="orbitcache", cache_entries=8, num_servers=2,
                client_batch=16, fetch_lanes=8, value_pad=PAD,
                server_queue=8, subrounds=2, max_serves=4, queue_size=4)
    base.update(kw)
    return RackConfig(**base)


@functools.lru_cache(maxsize=None)
def _workload():
    from repro.kvstore.workload import Workload, WorkloadConfig
    return Workload(WorkloadConfig(num_keys=256, offered_rps=1e5))


def _rack_parts(**kw):
    from repro.kvstore import simulator as sim
    cfg = _rack_cfg(**kw)
    wl = _workload()
    scfg = sim.make_server_config(cfg)
    ccfg = sim.make_client_config(cfg)
    return cfg, wl, scfg, ccfg


def _rack_carry(cfg, scfg, ccfg, seed=0):
    from repro.kvstore import simulator as sim
    wl = _workload()
    return sim.init_carry(cfg, scfg, ccfg, wl.cfg.num_keys,
                          wl.cfg.offered_rps, wl.cfg.write_ratio, seed)


def _fleet_carry(cfg, scfg, ccfg, points=2):
    from repro.kvstore.simulator import tree_stack
    return tree_stack([_rack_carry(cfg, scfg, ccfg, seed=i)
                       for i in range(points)])


def _ctrl_cfg():
    from repro.core.controller import ControllerConfig
    return ControllerConfig(active_size=8, max_size=8, k_report=4)


# ---------------------------------------------------------------------------
# entry builders
# ---------------------------------------------------------------------------
def _subround_pipeline() -> EntryPoint:
    from repro.core import pipeline
    from repro.core.types import empty_batch, init_switch_state

    def mk():
        sw = init_switch_state(8, queue_size=4, value_pad=PAD)
        carry, _ = pipeline.strip_val(sw)
        pk = empty_batch(16, value_pad=PAD)
        return jax.make_jaxpr(
            lambda c, p: pipeline.subround_pipeline(c, p, jnp.int32(10), 4)
        )(carry, pk)

    return EntryPoint("subround_pipeline", mk,
                      expected_pallas={"ref": 0, "kernel": 1})


def _window_pipeline() -> EntryPoint:
    from repro.core import pipeline
    from repro.core.types import empty_batch, init_switch_state

    def mk():
        sw = init_switch_state(8, queue_size=4, value_pad=PAD)
        pk = empty_batch(16, value_pad=PAD)
        sub = jax.tree.map(lambda a: jnp.stack([a, a]), pk)
        return jax.make_jaxpr(
            lambda s, b: pipeline.window_pipeline(
                s, b, recirc_gbps=100.0, window_us=100.0, subrounds=2,
                max_serves=4, key_size=16)
        )(sw, sub)

    return EntryPoint("window_pipeline", mk,
                      expected_pallas={"ref": 0, "kernel": 1})


def _controller_chunk() -> EntryPoint:
    from repro.kvstore import fleet

    cfg, wl, scfg, ccfg = _rack_parts(track_popularity=True)
    ctrl = _ctrl_cfg()
    wl_axes = jax.tree.map(lambda _: None, wl.arrays)  # shared leaves

    def fn():
        return fleet.compiled_batched_controller_chunk(
            cfg, ctrl, scfg, ccfg, wl.cfg.key_size, period_w=2, n_periods=1,
            wl_axes=wl_axes)

    def args(active=8):
        return (wl.arrays, _fleet_carry(cfg, scfg, ccfg),
                jnp.full((2,), active, jnp.int32))

    def mk():
        return jax.make_jaxpr(fn())(*args())

    return EntryPoint(
        "fleet.controller_chunk", mk,
        # fused subround + server cms track + 3x hot_gather (report/merge)
        expected_pallas={"ref": 0, "kernel": 5},
        donation=lambda: (fn(), args()),
        retrace=lambda: (fn(), lambda: args(8), lambda: args(5),
                         "active_size"),
    )


def _fleet_window_step() -> EntryPoint:
    from repro.kvstore import fleet

    cfg, wl, scfg, ccfg = _rack_parts()
    wl_axes = jax.tree.map(lambda _: None, wl.arrays)  # shared leaves

    def fn():
        return fleet.compiled_batched_chunk(cfg, scfg, ccfg, wl.cfg.key_size,
                                            2, wl_axes)

    def args(offered=None):
        carry = _fleet_carry(cfg, scfg, ccfg)
        if offered is not None:
            carry = carry._replace(
                offered=jnp.full_like(carry.offered, offered))
        return (wl.arrays, carry)

    def mk():
        return jax.make_jaxpr(fn())(*args())

    return EntryPoint(
        "fleet.window_step", mk,
        expected_pallas={"ref": 0, "kernel": 1},
        donation=lambda: (fn(), args()),
        retrace=lambda: (fn(), lambda: args(40.0), lambda: args(90.0),
                         "offered_rps"),
    )


def _fabric_parts(**kw):
    from repro.kvstore import fabric_sim as fs
    cfg, wl, scfg, ccfg = _rack_parts(**kw)
    fcfg = fs.FabricConfig(n_racks=2, spine_scheme="orbitcache",
                           spine_cache_entries=8, spine_lanes=8, fwd_lanes=8)
    return fs, cfg, fcfg, wl, scfg, ccfg


def _fabric_carry(cfg, fcfg, local_frac=None):
    """A two-point fabric fleet's carry (rack leaves [2, R, ...])."""
    from repro.kvstore.fleet import BatchedFabricSimulator
    carry = BatchedFabricSimulator(cfg, fcfg, _workload(), n_points=2).carry
    if local_frac is not None:
        carry = carry._replace(local_frac=jnp.full((2,), local_frac,
                                                   jnp.float32))
    return carry


def _fabric_chunk() -> EntryPoint:
    fs, cfg, fcfg, wl, scfg, ccfg = _fabric_parts()

    def fn():
        return fs.fabric_chunk(cfg, fcfg, scfg, ccfg, wl.cfg.key_size, 2)

    def args(local_frac=None):
        return (wl.arrays, _fabric_carry(cfg, fcfg, local_frac))

    def mk():
        return jax.make_jaxpr(fn())(*args())

    return EntryPoint(
        "fabric_chunk", mk,
        # rack-tier + spine-tier fused subround kernels (no controller,
        # so the server cms track kernel is off)
        expected_pallas={"ref": 0, "kernel": 2},
        donation=lambda: (fn(), args()),
        retrace=lambda: (fn(), lambda: args(0.9), lambda: args(0.5),
                         "local_frac"),
    )


def _fabric_controller_chunk() -> EntryPoint:
    fs, cfg, fcfg, wl, scfg, ccfg = _fabric_parts(track_popularity=True)
    ctrl = _ctrl_cfg()

    def fn():
        return fs.fabric_controller_chunk(
            cfg, fcfg, ctrl, ctrl, scfg, ccfg, wl.cfg.key_size,
            period_w=2, n_periods=1)

    def args(local_frac=None):
        ra = jnp.full((2, fcfg.n_racks), 8, jnp.int32)
        sa = jnp.full((2,), 8, jnp.int32)
        return (wl.arrays, _fabric_carry(cfg, fcfg, local_frac), ra, sa)

    def mk():
        return jax.make_jaxpr(fn())(*args())

    return EntryPoint(
        "fabric_controller_chunk", mk,
        # both tiers' subrounds (2) + rack-server cms track (1) + both
        # tiers' hot_gather report/merge triples (6)
        expected_pallas={"ref": 0, "kernel": 9},
        donation=lambda: (fn(), args()),
        retrace=lambda: (fn(), lambda: args(0.9), lambda: args(0.5),
                         "local_frac"),
    )


_BUILDERS = (
    _subround_pipeline,
    _window_pipeline,
    _controller_chunk,
    _fleet_window_step,
    _fabric_chunk,
    _fabric_controller_chunk,
)


def build_entry_points(names=None) -> list[EntryPoint]:
    """All six production entry points (optionally filtered by name)."""
    eps = [b() for b in _BUILDERS]
    if names:
        wanted = set(names)
        unknown = wanted - {e.name for e in eps}
        if unknown:
            raise ValueError(f"unknown entry points: {sorted(unknown)}")
        eps = [e for e in eps if e.name in wanted]
    return eps
