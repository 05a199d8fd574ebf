"""Serve a skewed key-value workload through a full simulated rack and
compare OrbitCache against NoCache and NetCache — the paper's headline
experiment (Fig. 9) at laptop scale.

    PYTHONPATH=src python examples/serve_kv.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.kvstore.fleet import RackSimulator
from repro.kvstore.simulator import RackConfig
from repro.kvstore.workload import Workload, WorkloadConfig


def main():
    wl = Workload(WorkloadConfig(num_keys=500_000, zipf_alpha=0.99,
                                 offered_rps=3.0e6))
    print(f"workload: {wl.cfg.num_keys} keys, zipf-{wl.cfg.zipf_alpha}, "
          f"head coverage of 128 hottest = {wl.head_coverage(128):.1%}")
    for scheme in ("nocache", "netcache", "orbitcache"):
        sim = RackSimulator(RackConfig(scheme=scheme, cache_entries=128,
                                       recirc_gbps=150.0), wl)
        if scheme == "orbitcache":
            sim.preload(wl.hottest_keys(128))
        elif scheme == "netcache":
            sim.preload(wl.hottest_keys(10_000))
        res = sim.run(0.05)
        print(f"{scheme:11s} rx={res.throughput_rps()/1e6:5.2f}M rps  "
              f"balance={res.balancing_efficiency():.2f}  "
              f"p50={res.latency_percentile(0.5):6.1f}us  "
              f"p99={res.latency_percentile(0.99):6.1f}us  "
              f"hot-hit-share={res.traces['rx_switch'].sum() / max(res.traces['rx_switch'].sum() + res.traces['rx_server'].sum(), 1):.1%}")
    print("OrbitCache balances the rack; NoCache saturates the hot-key "
          "server; NetCache can't cache the large-value hot items.")


if __name__ == "__main__":
    main()
