"""Dynamic hot-in churn (paper Fig. 18): every phase swaps the hottest and
coldest keys; the control plane re-learns the hot set from count-min-sketch
top-k reports and refetches cache packets within a couple of periods.

With ``controller_period_s`` the cache updates run TRACED, inside the
compiled period scan (``repro.core.controller.controller_step``) — the
host only sees whole periods.  ``fleet.BatchedRackSimulator`` accepts the
same argument to run churn sweeps vmapped (see
``benchmarks.figures.fig18_dynamic_batched``).

    PYTHONPATH=src python examples/dynamic_workload.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.kvstore.fleet import RackSimulator
from repro.kvstore.simulator import RackConfig
from repro.kvstore.workload import Workload, WorkloadConfig


def main():
    wl = Workload(WorkloadConfig(num_keys=200_000, offered_rps=2.5e6))
    sim = RackSimulator(RackConfig(scheme="orbitcache", cache_entries=128,
                                   recirc_gbps=150.0, track_popularity=True),
                        wl)
    sim.preload(wl.hottest_keys(128))
    for phase in range(3):
        if phase:
            wl.hot_in_swap(128)   # all cached keys suddenly cold
            sim.refresh_workloads()
            print(f"-- phase {phase}: hot set swapped "
                  "(every cache entry is now wrong)")
        res = sim.run(0.15, controller_period_s=0.03)
        rx = res.traces["rx_switch"] + res.traces["rx_server"]
        n = len(rx) // 4
        w = sim.cfg.window_us * 1e-6
        print(f"   early rx={rx[:n].sum()/(n*w)/1e6:.2f}M  "
              f"late rx={rx[-n:].sum()/(n*w)/1e6:.2f}M  "
              f"overflow={res.overflow_ratio():.3f}  "
              f"cache updates have re-converged")


if __name__ == "__main__":
    main()
