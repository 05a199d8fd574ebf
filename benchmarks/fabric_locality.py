"""Fabric locality sweep: delivered throughput vs rack-local fraction.

A Fig-9-style sweep for the two-tier topology: one fabric (R racks + a
shared spine switch) per (scheme, locality) point, rack-local fractions
{1.0, 0.9, 0.5} — from fully partitioned racks down to half the traffic
crossing the spine.  All three switch schemes run the SAME scheme at both
tiers (OrbitCache ToRs under an OrbitCache spine, etc.), so the sweep
isolates what in-network caching at the spine buys back as locality
degrades: at locality 1.0 the fabric is bit-identical to independent
racks, and every percentage point of remote traffic either hits the
spine's global hot set or pays the fall-through to the owning rack.

Locality points batch through ``fleet.BatchedFabricSimulator`` — the
rack-local fraction is a carry scalar, so each scheme's whole sweep runs
as ONE compiled vmapped scan.

Run: ``PYTHONPATH=src python -m benchmarks.fabric_locality [--quick]``

Output: ``name,value,derived`` CSV rows (the repo's benchmark idiom) —
per point: delivered rps, spine hit ratio, spine forwards/sec, exchange
drops.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


from repro.kvstore.fabric_sim import FabricConfig  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.kvstore.fleet import BatchedFabricSimulator  # noqa: E402
from repro.kvstore.simulator import RackConfig  # noqa: E402
from repro.kvstore.workload import Workload, WorkloadConfig  # noqa: E402

LOCALITIES = (1.0, 0.9, 0.5)
SCHEMES = ("orbitcache", "netcache", "nocache")


def run_sweep(scheme: str, wl: Workload, n_racks: int, windows: int,
              warm: int) -> list[dict]:
    cfg = RackConfig(
        scheme=scheme, cache_entries=64, num_servers=8,
        client_batch=256, fetch_lanes=64, value_pad=256, server_queue=32,
        subrounds=2,
    )
    fcfg = FabricConfig(
        n_racks=n_racks, spine_scheme=scheme,
        spine_lanes=256, fwd_lanes=128, spine_cache_entries=128,
    )
    bf = BatchedFabricSimulator(cfg, fcfg, wl, local_fracs=list(LOCALITIES))
    bf.preload(warm_windows=warm)
    out = bf.run_windows(windows)
    win_s = cfg.window_us * 1e-6
    rows = []
    for i, loc in enumerate(LOCALITIES):
        rx_rack = (out["rack_rx_switch"][i].sum()
                   + out["rack_rx_server"][i].sum())
        rx_spine = out["spine_served"][i].sum()
        remote = out["spine_remote"][i].sum()
        rows.append(dict(
            scheme=scheme, locality=loc,
            delivered_rps=float((rx_rack + rx_spine) / (windows * win_s)),
            offered_rps=float(out["rack_tx"][i].sum() / (windows * win_s)),
            remote_frac=float(remote / max(out["rack_tx"][i].sum(), 1)),
            spine_hit_ratio=float(rx_spine / max(remote, 1)),
            spine_fwd_rps=float(out["spine_fwd"][i].sum()
                                / (windows * win_s)),
            exchange_drops=int(out["spine_in_drops"][i].sum()
                                + out["spine_fwd_drops"][i].sum()),
        ))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="trimmed grid (small keyspace, few windows)")
    ap.add_argument("--racks", type=int, default=4)
    ap.add_argument("--windows", type=int, default=256)
    args = ap.parse_args()
    configure_compile_cache()
    num_keys = 20_000 if args.quick else 1_000_000
    windows = 32 if args.quick else args.windows
    warm = 8 if args.quick else 16
    offered = 1.0e6
    wl = Workload(WorkloadConfig(num_keys=num_keys, offered_rps=offered))

    print(f"# fabric_locality: {args.racks} racks, localities {LOCALITIES}, "
          f"{windows} windows, {num_keys} keys/rack", flush=True)
    for scheme in SCHEMES:
        for row in run_sweep(scheme, wl, args.racks, windows, warm):
            print(
                f"fabric_locality,{row['scheme']},loc_{row['locality']},"
                f"{row['delivered_rps']:.0f},delivered_rps,"
                f"{row['remote_frac']:.3f},remote_frac,"
                f"{row['spine_hit_ratio']:.3f},spine_hit_ratio,"
                f"{row['spine_fwd_rps']:.0f},spine_fwd_rps,"
                f"{row['exchange_drops']},drops", flush=True)


if __name__ == "__main__":
    main()
