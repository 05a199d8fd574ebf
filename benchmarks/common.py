"""Shared benchmark infrastructure: saturation-knee methodology.

The paper reports *sustainable* throughput (the knee of the latency-
throughput curve, Fig. 11): we sweep offered load as an ascending
staircase on one simulator instance (no recompiles) and report the
largest Rx with loss <= ``loss_tol`` (falling back to max Rx when every
point saturates).

Scale notes vs the paper's testbed (documented deviations):
  * key space 10M, 32 servers x 100K RPS — as the paper;
  * sim seconds per point: 0.03–0.05 s (paper: minutes) — steady state is
    reached within milliseconds at these rates;
  * ``recirc_gbps = 150`` is the single calibration constant, chosen so
    the cache-size knee lands between 128 and 256 entries as in Fig. 16.
"""
from __future__ import annotations

import time

from repro.kvstore.fleet import BatchedRackSimulator, RackSimulator
from repro.kvstore.simulator import RackConfig
from repro.kvstore.workload import Workload, WorkloadConfig

NUM_KEYS = 10_000_000   # paper §5.1: 10M key-value pairs
RECIRC_GBPS = 150.0
DEFAULT_LOADS = (0.5e6, 1e6, 1.5e6, 2e6, 2.5e6, 3e6, 3.5e6, 4e6, 4.5e6,
                 5e6, 5.5e6, 6e6)


def make_sim(scheme: str, wl: Workload, cache_entries: int = 128,
             preload: bool = True, **cfg_kw) -> RackSimulator:
    cfg = RackConfig(scheme=scheme, cache_entries=cache_entries,
                     recirc_gbps=RECIRC_GBPS, **cfg_kw)
    sim = RackSimulator(cfg, wl)
    if preload:
        if scheme == "orbitcache":
            sim.preload(wl.hottest_keys(cache_entries))
        elif scheme == "netcache":
            sim.preload(wl.hottest_keys(10_000))
    return sim


def make_batched_sim(scheme: str, workloads, cache_entries: int = 128,
                     preload: bool = True, offered=None, seeds=None,
                     n_points: int | None = None,
                     **cfg_kw) -> BatchedRackSimulator:
    """One fleet of identically-shaped racks (one per sweep point)."""
    cfg = RackConfig(scheme=scheme, cache_entries=cache_entries,
                     recirc_gbps=RECIRC_GBPS, **cfg_kw)
    bsim = BatchedRackSimulator(cfg, workloads, offered_rps=offered,
                                seeds=seeds, n_points=n_points)
    if preload:
        bsim.preload()
    return bsim


def _row(res, burn_frac=0.3):
    rx = res.throughput_rps(burn_frac=burn_frac)
    tx = res.offered_rps(burn_frac=burn_frac)
    return dict(
        offered=tx, rx=rx, loss=1.0 - rx / max(tx, 1.0),
        srv_drop=res.max_server_drop_frac(burn_frac=burn_frac),
        p50=res.latency_percentile(0.5),
        p99=res.latency_percentile(0.99),
        baleff=res.balancing_efficiency(burn_frac=burn_frac),
        overflow_ratio=res.overflow_ratio(burn_frac=burn_frac),
        switch_p99=res.latency_percentile(0.99, "switch"),
    )


def _knee_of(rows, loss_tol, srv_drop_tol):
    ok = [r["rx"] for r in rows
          if r["loss"] <= loss_tol and r["srv_drop"] <= srv_drop_tol]
    return max(ok) if ok else rows[0]["rx"]


def knee_throughput_batched(bsim: BatchedRackSimulator, loads=DEFAULT_LOADS,
                            seconds: float = 0.03, loss_tol: float = 0.02,
                            srv_drop_tol: float = 0.05):
    """Ascending staircase over a fleet: every point climbs the load ladder
    simultaneously (one batched run per rung; the methodology is
    ``knee_throughput``'s).

    Returns one ``(knee_rps, rows)`` per sweep point.
    """
    per_point_rows = [[] for _ in range(bsim.n_points)]
    for rps in loads:
        bsim.set_offered(rps)
        bsim.reset_stats()
        for i, res in enumerate(bsim.run(seconds)):
            per_point_rows[i].append(_row(res))
    return [(_knee_of(rows, loss_tol, srv_drop_tol), rows)
            for rows in per_point_rows]


def knee_throughput_parallel(scheme: str, wl: Workload, loads=DEFAULT_LOADS,
                             seconds: float = 0.03, loss_tol: float = 0.02,
                             srv_drop_tol: float = 0.05,
                             cache_entries: int = 128, **cfg_kw):
    """Whole knee search as ONE batched run: each load rung is its own
    sweep point (preloaded warm, independently seeded), so the full
    latency-throughput curve comes out of a single vmapped scan.

    Returns ``(knee_rps, rows)`` like ``knee_throughput``.
    """
    bsim = make_batched_sim(scheme, wl, cache_entries=cache_entries,
                            offered=loads, seeds=range(len(loads)), **cfg_kw)
    bsim.reset_stats()
    rows = [_row(res) for res in bsim.run(seconds)]
    return _knee_of(rows, loss_tol, srv_drop_tol), rows


def knee_throughput(sim: RackSimulator, loads=DEFAULT_LOADS,
                    seconds: float = 0.03, loss_tol: float = 0.02,
                    srv_drop_tol: float = 0.05):
    """Ascending staircase; returns (knee_rps, curve rows).

    Knee = largest Rx that is *sustainable*: total loss under ``loss_tol``
    AND no single server dropping more than ``srv_drop_tol`` of its
    arrivals.  The per-server criterion is the point: one saturated
    hot-key server is the failure mode in-network caching exists to fix,
    and it barely moves *total* loss (it owns only a few % of traffic)
    while its latency/drops explode — the paper's Fig. 11 knee.  The
    staircase is :func:`knee_throughput_batched` on the view's fleet."""
    [knee] = knee_throughput_batched(sim.fleet, loads, seconds, loss_tol,
                                     srv_drop_tol)
    return knee


def workload(alpha=0.99, write_ratio=0.0, value_sizes=((64, 0.82), (1024, 0.18)),
             num_keys=NUM_KEYS, seed=0) -> Workload:
    return Workload(WorkloadConfig(
        num_keys=num_keys, zipf_alpha=alpha, write_ratio=write_ratio,
        value_sizes=value_sizes, offered_rps=1e6, seed=seed))


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.s = time.time() - self.t0


def emit(name: str, value, derived: str = ""):
    print(f"{name},{value},{derived}", flush=True)
