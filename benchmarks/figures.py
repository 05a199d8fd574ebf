"""One benchmark per paper table/figure (Figs. 9–18).

Each ``figNN_*`` returns a dict of results and prints CSV rows
(name,value,derived).  ``quick=True`` trims grids for smoke runs.
"""
from __future__ import annotations

import numpy as np

from repro.kvstore.workload import Workload, WorkloadConfig, production_workload

from .common import (DEFAULT_LOADS, NUM_KEYS, emit,
                     knee_throughput, knee_throughput_batched,
                     knee_throughput_parallel, make_batched_sim, make_sim,
                     workload)

SCHEMES = ("nocache", "netcache", "orbitcache")


# ---------------------------------------------------------------------------
def fig09_skew(quick=False):
    """Throughput vs skewness (paper: OrbitCache 3.59x NoCache, 1.95x
    NetCache at zipf-0.99).  The skew sweep is one fleet per scheme: every
    zipf point climbs the load staircase in lockstep."""
    alphas = (0.9, 0.95, 0.99) if quick else (0.8, 0.9, 0.95, 0.99, 1.2)
    wls = [workload(alpha=a) for a in alphas]
    out = {}
    for scheme in SCHEMES:
        bsim = make_batched_sim(scheme, wls)
        for a, (knee, _) in zip(alphas, knee_throughput_batched(bsim)):
            out[(scheme, a)] = knee
            emit(f"fig09/{scheme}/zipf-{a}", f"{knee/1e6:.2f}", "Mrps_knee")
    for a in alphas:
        r_no = out[("orbitcache", a)] / max(out[("nocache", a)], 1)
        r_nc = out[("orbitcache", a)] / max(out[("netcache", a)], 1)
        emit(f"fig09/ratio_vs_nocache/zipf-{a}", f"{r_no:.2f}",
             "paper@0.99=3.59")
        emit(f"fig09/ratio_vs_netcache/zipf-{a}", f"{r_nc:.2f}",
             "paper@0.99=1.95")
    return out


def fig10_loads(quick=False):
    """Per-server load at high offered load (paper: OrbitCache flat)."""
    wl = workload()
    out = {}
    for scheme in SCHEMES:
        sim = make_sim(scheme, wl)
        sim.set_offered(3.5e6)
        res = sim.run(0.04)
        rps = res.per_server_rps()
        out[scheme] = rps
        emit(f"fig10/{scheme}/cov", f"{rps.std()/max(rps.mean(),1):.3f}",
             "coefficient_of_variation")
        emit(f"fig10/{scheme}/max_min", f"{rps.max()/max(rps.min(),1):.2f}",
             "hottest/coldest")
    return out


def fig11_latency(quick=False):
    """Median + p99 latency vs Rx throughput."""
    wl = workload()
    loads = (1e6, 3e6, 5e6) if quick else (1e6, 2e6, 3e6, 4e6, 5e6, 6e6)
    out = {}
    for scheme in SCHEMES:
        sim = make_sim(scheme, wl)
        for rps in loads:
            sim.set_offered(rps)
            sim.reset_stats()
            res = sim.run(0.03)
            rx = res.throughput_rps(burn_frac=0.3)
            out[(scheme, rps)] = (rx, res.latency_percentile(0.5),
                                  res.latency_percentile(0.99))
            emit(f"fig11/{scheme}/rx-{rx/1e6:.2f}M",
                 f"{res.latency_percentile(0.5):.1f}",
                 f"p50_us,p99={res.latency_percentile(0.99):.1f}")
    return out


def fig12_write_ratio(quick=False):
    """Throughput vs write ratio (OrbitCache converges to NoCache at 100%)."""
    ratios = (0.0, 0.5, 1.0) if quick else (0.0, 0.2, 0.4, 0.6, 0.8, 1.0)
    out = {}
    for wr in ratios:
        wl = workload(write_ratio=wr)
        for scheme in ("nocache", "orbitcache"):
            sim = make_sim(scheme, wl)
            knee, _ = knee_throughput(sim, loads=DEFAULT_LOADS[:5])
            out[(scheme, wr)] = knee
            emit(f"fig12/{scheme}/wr-{wr}", f"{knee/1e6:.2f}", "Mrps_knee")
    return out


def fig13_scalability(quick=False):
    """Linear scaling with server count (50K RPS rate limit, paper §5.2).

    Server count changes array shapes (static), so each count compiles its
    own fleet — but within a count the whole load ladder runs as one
    batched knee search."""
    counts = (16, 32) if quick else (16, 32, 64)
    out = {}
    wl = workload()
    for n in counts:
        for scheme in SCHEMES:
            knee, rows = knee_throughput_parallel(
                scheme, wl, loads=(0.5e6, 1e6, 2e6, 3e6, 4e6),
                num_servers=n, server_rps=50_000.0)
            be = rows[-1]["baleff"]
            out[(scheme, n)] = (knee, be)
            emit(f"fig13/{scheme}/servers-{n}", f"{knee/1e6:.2f}",
                 f"Mrps_knee,baleff={be:.2f}")
    return out


def fig14_production(quick=False):
    """Twitter-like workloads A–E (paper: OrbitCache best on all)."""
    names = ("A", "E") if quick else ("A", "B", "C", "D", "E")
    out = {}
    for nm in names:
        wl = Workload(production_workload(nm, WorkloadConfig(
            num_keys=NUM_KEYS, offered_rps=1e6)))
        for scheme in SCHEMES:
            sim = make_sim(scheme, wl)
            knee, _ = knee_throughput(sim, loads=DEFAULT_LOADS[:6])
            out[(scheme, nm)] = knee
            emit(f"fig14/{scheme}/workload-{nm}", f"{knee/1e6:.2f}", "Mrps_knee")
    return out


def fig15_breakdown(quick=False):
    """Latency breakdown: switch-served vs server-served."""
    wl = workload()
    sim = make_sim("orbitcache", wl)
    out = {}
    for rps in ((2e6,) if quick else (2e6, 4e6)):
        sim.set_offered(rps)
        sim.reset_stats()
        res = sim.run(0.03)
        sw50 = res.latency_percentile(0.5, "switch")
        sv50 = res.latency_percentile(0.5, "server")
        sw99 = res.latency_percentile(0.99, "switch")
        sv99 = res.latency_percentile(0.99, "server")
        out[rps] = (sw50, sv50, sw99, sv99)
        emit(f"fig15/switch/offered-{rps/1e6:.0f}M", f"{sw50:.1f}",
             f"p50_us,p99={sw99:.1f}")
        emit(f"fig15/server/offered-{rps/1e6:.0f}M", f"{sv50:.1f}",
             f"p50_us,p99={sv99:.1f}")
    return out


def fig16_cache_size(quick=False):
    """Cache-size sweep: saturation ~128 entries, overflow soars >=256.

    Cache size is static (table shapes), so each size compiles its own
    fleet; the load ladder per size is one batched knee search, and the
    knee rung's own measurements supply overflow/latency."""
    sizes = (64, 128, 256) if quick else (16, 32, 64, 128, 256, 512)
    wl = workload()
    out = {}
    for c in sizes:
        knee, rows = knee_throughput_parallel("orbitcache", wl,
                                              cache_entries=c)
        knee_row = max((r for r in rows if r["rx"] <= knee),
                       key=lambda r: r["rx"], default=rows[0])
        ovf = knee_row["overflow_ratio"]
        p99 = knee_row["switch_p99"]
        out[c] = (knee, ovf, p99)
        emit(f"fig16/entries-{c}", f"{knee/1e6:.2f}",
             f"Mrps_knee,overflow={ovf:.3f},switch_p99us={p99:.1f}")
    return out


def fig17_item_size(quick=False):
    """Uniform item-size sweep; effective cache size shrinks with size."""
    sizes = (128, 1024) if quick else (128, 256, 512, 1024, 1416)
    out = {}
    for vs in sizes:
        wl = workload(value_sizes=((vs, 1.0),))
        best = (0, None, None)
        for c in ((64,) if quick else (32, 64, 128)):
            sim = make_sim("orbitcache", wl, cache_entries=c)
            knee, rows = knee_throughput(sim)
            if knee > best[0]:
                best = (knee, c, rows[-1]["baleff"])
        out[vs] = best
        emit(f"fig17/value-{vs}B", f"{best[0]/1e6:.2f}",
             f"Mrps_knee,best_cache={best[1]},baleff={best[2]:.2f}")
    return out


def fig18_dynamic(quick=False):
    """Hot-in churn: every phase swaps the 128 hottest/coldest keys; the
    controller (running traced, inside the compiled period scan)
    re-learns within a couple of report periods."""
    wl = Workload(WorkloadConfig(num_keys=200_000, offered_rps=2.5e6))
    sim = make_sim("orbitcache", wl, track_popularity=True)
    phase_s = 0.05 if quick else 0.2
    period = 0.01 if quick else 0.04
    trace = []
    for phase in range(3):
        if phase:
            wl.hot_in_swap(128)
            sim.refresh_workloads()
        res = sim.run(phase_s, controller_period_s=period)
        rx = res.traces["rx_switch"] + res.traces["rx_server"]
        n = len(rx) // 4
        early = rx[:n].sum() / (n * sim.cfg.window_us * 1e-6)
        late = rx[-n:].sum() / (n * sim.cfg.window_us * 1e-6)
        ovf = res.overflow_ratio()
        trace.append((early, late, ovf))
        emit(f"fig18/phase-{phase}/early", f"{early/1e6:.2f}", "Mrps")
        emit(f"fig18/phase-{phase}/late", f"{late/1e6:.2f}",
             f"Mrps,overflow={ovf:.3f}")
    # recovery: late throughput of churned phases near phase-0 levels
    rec = min(trace[1][1], trace[2][1]) / max(trace[0][1], 1)
    emit("fig18/recovery", f"{rec:.2f}", "late/baseline,paper=recovers<few_s")
    return trace


def fig18_dynamic_batched(quick=False):
    """Batched hot-in churn: N independently-seeded racks ride the SAME
    churning workload, with every rack's periodic cache updates (server
    reports, evict/insert, F-REQ injection) running inside one vmapped
    compiled period scan — the traced control plane is what makes this
    sweep batchable at all.  Reports the per-phase recovery spread across
    seeds (the churn statistic Fig. 18's single trace can't show)."""
    n_points = 2 if quick else 4
    wl = Workload(WorkloadConfig(num_keys=200_000, offered_rps=2.5e6))
    bsim = make_batched_sim("orbitcache", wl, track_popularity=True,
                            n_points=n_points)
    phase_s = 0.05 if quick else 0.2
    period = 0.01 if quick else 0.04
    lates = []
    for phase in range(3):
        if phase:
            wl.hot_in_swap(128)
            bsim.refresh_workloads()
        results = bsim.run(phase_s, controller_period_s=period)
        phase_late = []
        for i, res in enumerate(results):
            rx = res.traces["rx_switch"] + res.traces["rx_server"]
            n = len(rx) // 4
            phase_late.append(rx[-n:].sum() / (n * bsim.cfg.window_us * 1e-6))
        lates.append(phase_late)
        mean = float(np.mean(phase_late))
        emit(f"fig18b/phase-{phase}/late", f"{mean/1e6:.2f}",
             f"Mrps_mean_of_{n_points},min={min(phase_late)/1e6:.2f}M")
    recs = [min(l1, l2) / max(l0, 1)
            for l0, l1, l2 in zip(lates[0], lates[1], lates[2])]
    emit("fig18b/recovery", f"{float(np.mean(recs)):.2f}",
         f"late/baseline_mean,min={min(recs):.2f},points={n_points}")
    return lates


ALL_FIGS = [fig09_skew, fig10_loads, fig11_latency, fig12_write_ratio,
            fig13_scalability, fig14_production, fig15_breakdown,
            fig16_cache_size, fig17_item_size, fig18_dynamic,
            fig18_dynamic_batched]
