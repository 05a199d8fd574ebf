"""Perf smoke: simulated windows/sec and requests/sec, serial vs batched.

A small rack runs the same total work two ways:

  serial     N independent RackSimulator sweeps, one after another
             (they share one compiled chunk — seeds are host-side);
  batched    one N-point BatchedRackSimulator fleet (vmapped scan).

Both paths are warmed first (compile excluded from the timed region,
reported separately).  Because shared CI/container hosts drift on ~10 s
timescales, the two paths are measured in interleaved pairs and the
headline speedup is the **median of per-pair ratios** — each pair is
adjacent in time, so slow host drift cancels.  Results land in
``BENCH_simulator.json`` at the repo root: each run (stamped with host,
git revision, timestamp) is **appended** to the ``history`` list and
mirrored in ``latest``, so the perf trajectory survives across PRs —
regress against the history before touching the hot path.

Run: ``PYTHONPATH=src python -m benchmarks.perf_smoke``

Gate mode (``--check``): after measuring, the fresh batched windows/sec is
compared against the median of the same-host history entries; a >20% drop
exits nonzero (CI-able perf regression gate).  When no same-host history
exists the check only warns — cross-host numbers are not comparable.

Breakdown mode (``--breakdown``): prints a compiled-HLO summary of the
measured chunk via the shared ``repro.analysis.hlo`` tooling
(instruction/fusion counts, custom calls — the fused-kernel count shows
here on the Pallas backends — plus any scatter ops that survived XLA
fusion outside the lint allowlist).  Time per window stage is measured
on the chip, from the program's named stages (``repro.obs``), by
``benchmarks/chip/stage_split.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import platform
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import kernels  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.kvstore.fleet import BatchedRackSimulator  # noqa: E402
from repro.kvstore.simulator import RackConfig, RackSimulator  # noqa: E402
from repro.kvstore.workload import Workload, WorkloadConfig  # noqa: E402

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# A deliberately small rack: state fits in cache, so the benchmark measures
# the simulator machinery (per-window op overhead and how well it batches),
# not DRAM streaming of value payloads.
SMOKE_CFG = RackConfig(
    scheme="orbitcache", cache_entries=32, num_servers=4,
    client_batch=128, fetch_lanes=32, value_pad=64, server_queue=32,
    subrounds=2,
)
SMOKE_KEYS = 10_000


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def append_history(out_path: str, run: dict) -> dict:
    """Append ``run`` to the bench file's history (legacy single-run files
    become the first history entry) and mirror it as ``latest``."""
    data = {"bench": "rack_simulator_smoke", "history": []}
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                old = json.load(f)
        except (OSError, ValueError):
            old = None
        if isinstance(old, dict):
            if isinstance(old.get("history"), list):
                data["history"] = old["history"]
            elif "serial" in old:   # pre-history format: one run at top level
                data["history"] = [old]
    data["history"].append(run)
    data["latest"] = run
    return data


def same_host_median(history: list[dict], run: dict) -> float | None:
    """Median batched windows/sec of prior comparable runs.

    Comparable = same host, same points/windows config AND same jax/kernel
    backends (an interpret-backend run is several times slower than ref —
    mixing them would both false-trip the gate and drag the median).  Runs
    that failed their own ``--check`` gate are excluded so a regressed
    branch retrying in CI cannot vote its own regression into the
    baseline.
    """
    prior = [
        h for h in history
        if h.get("host") == run["host"] and h is not run
        and h.get("config", {}).get("points") == run["config"]["points"]
        and h.get("config", {}).get("windows") == run["config"]["windows"]
        and h.get("env", {}).get("jax_backend") == run["env"]["jax_backend"]
        and (h.get("env", {}).get("kernel_backend")
             == run["env"]["kernel_backend"])
        and not h.get("regressed")
    ]
    if not prior:
        return None
    return statistics.median(
        h["batched"]["windows_per_s_best"] for h in prior)


def check_regression(history: list[dict], run: dict,
                     threshold: float = 0.8) -> int:
    """Exit status for --check: 1 on a >(1-threshold) drop vs the median."""
    med = same_host_median(history, run)
    cur = run["batched"]["windows_per_s_best"]
    if med is None:
        print(f"# check: no same-host history for {run['host']!r} — "
              "nothing to compare against (warn only)", flush=True)
        return 0
    verdict = "OK" if cur >= threshold * med else "REGRESSION"
    print(f"check,{cur:.0f},vs_median_{med:.0f},"
          f"ratio_{cur / med:.3f},{verdict}", flush=True)
    if verdict == "REGRESSION":
        print(f"# batched windows/sec fell >{(1 - threshold) * 100:.0f}% "
              f"below the same-host history median — investigate before "
              f"merging (see --breakdown)", flush=True)
        return 1
    return 0


def run_breakdown(sim, wl) -> dict:
    """Compiled-HLO summary of the serial window chunk.

    The summary (``analysis.hlo``) counts instructions per opcode in the
    compiled measured chunk — on the Pallas backends the fused subround
    shows up as one custom call per subround — and reports scatter ops
    that survived XLA fusion, split by the ``no-scatter`` lint allowlist.
    """
    from repro.analysis.hlo import opcode_summary, scatter_instructions
    from repro.analysis.rules import ALLOWLISTS

    carry = sim.carry
    arrs = wl.arrays

    # compiled-HLO summary of the measured chunk (shared repro.analysis
    # tooling — the same parse the lint subsystem runs on every PR)
    chunk = sim._chunk(8)
    hlo = chunk.lower(arrs, carry).compile().as_text()
    summary = opcode_summary(hlo)
    print(f"hlo,total_instructions,{summary.total},"
          f"computations,{summary.computations},"
          f"custom_calls,{summary.custom_calls}", flush=True)
    print("hlo_top," + ",".join(f"{op}:{n}" for op, n in summary.top(10)),
          flush=True)

    # Scatters that survived XLA fusion in the compiled chunk.  The
    # jaxpr-level no-scatter rule guards trace-time intent; this reports
    # post-fusion reality, split by whether the originating site is on
    # the reviewed allowlist — an unexpected scatter here is a hot-path
    # perf bug even if lint passed (e.g. XLA failing to fuse a one-hot
    # update back into an in-place form).
    allowed = ALLOWLISTS["no-scatter"]
    scatters = scatter_instructions(hlo)
    unexpected = [s for s in scatters
                  if not any(fn in s["source"] or fn in s["op_name"]
                             for fn in allowed)]
    print(f"hlo_scatters,{len(scatters)},surviving_fusion,"
          f"{len(unexpected)},outside_allowlist", flush=True)
    for s in unexpected:
        print(f"hlo_scatter_unexpected,{s['opcode']},"
              f"{s['op_name'] or s['name']},{s['source']}", flush=True)
    return {
        "hlo": {"total_instructions": summary.total,
                "computations": summary.computations,
                "custom_calls": summary.custom_calls,
                "top_opcodes": dict(summary.top(10)),
                "scatters": len(scatters),
                "scatters_outside_allowlist": len(unexpected)},
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, default=16,
                    help="sweep points (serial runs and fleet width)")
    ap.add_argument("--windows", type=int, default=256,
                    help="measured windows per point per rep")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved (serial, batched) measurement pairs")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero on a >20%% batched-windows/sec "
                         "regression vs the same-host history median")
    ap.add_argument("--breakdown", action="store_true",
                    help="also print a compiled-HLO summary of the chunk")
    ap.add_argument("--out", default=os.path.join(REPO_ROOT,
                                                  "BENCH_simulator.json"))
    args = ap.parse_args()
    configure_compile_cache()
    if args.points < 1 or args.windows < 1 or args.reps < 1:
        ap.error("--points, --windows and --reps must be >= 1")

    wl = Workload(WorkloadConfig(num_keys=SMOKE_KEYS, offered_rps=1.0e6))
    n, w = args.points, args.windows
    print(f"# perf_smoke: {n} points x {w} windows x {args.reps} pairs, "
          f"backend={jax.default_backend()}, "
          f"kernels={kernels.kernel_backend()}", flush=True)

    t0 = time.time()
    sims = []
    for i in range(n):
        sim = RackSimulator(dataclasses.replace(SMOKE_CFG, seed=i), wl)
        sim.preload(wl.hottest_keys(SMOKE_CFG.cache_entries))
        sims.append(sim)
    sims[0].run_windows(w)  # compile the measured chunk length
    serial_setup_s = time.time() - t0

    t0 = time.time()
    bsim = BatchedRackSimulator(SMOKE_CFG, wl, n_points=n)
    bsim.preload()
    bsim.run_windows(w)
    batched_setup_s = time.time() - t0

    serial_t, batched_t, ratios = [], [], []
    serial_tx = batched_tx = 0
    for rep in range(args.reps):
        t0 = time.time()
        for sim in sims:
            serial_tx += int(np.sum(sim.run_windows(w)["tx"]))
        ts = time.time() - t0
        t0 = time.time()
        batched_tx += int(np.sum(bsim.run_windows(w)["tx"]))
        tb = time.time() - t0
        serial_t.append(ts)
        batched_t.append(tb)
        ratios.append(ts / tb)
        print(f"pair {rep}: serial {n*w/ts:.0f} w/s, batched {n*w/tb:.0f} "
              f"w/s, ratio {ts/tb:.2f}", flush=True)

    speedup = statistics.median(ratios)
    serial_best = n * w / min(serial_t)
    batched_best = n * w / min(batched_t)
    print(f"serial,{serial_best:.0f},windows_per_s "
          f"({serial_tx/sum(serial_t)/1e6:.2f}M req/s)", flush=True)
    print(f"batched,{batched_best:.0f},windows_per_s "
          f"({batched_tx/sum(batched_t)/1e6:.2f}M req/s)", flush=True)
    print(f"speedup,{speedup:.2f},median of per-pair ratios", flush=True)

    result = {
        "host": platform.node(),
        "git_rev": _git_rev(),
        "config": {
            "points": n, "windows": w, "reps": args.reps,
            "num_keys": SMOKE_KEYS,
            "rack": dataclasses.asdict(SMOKE_CFG),
        },
        "env": {
            "jax_backend": jax.default_backend(),
            "kernel_backend": kernels.kernel_backend(),
            "jax_version": jax.__version__,
            "platform": platform.platform(),
            "cpus": os.cpu_count(),
        },
        "serial": {
            "windows_per_s_best": serial_best,
            "requests_per_s": serial_tx / sum(serial_t),
            "elapsed_s": serial_t,
            "setup_and_compile_s": serial_setup_s,
        },
        "batched": {
            "windows_per_s_best": batched_best,
            "requests_per_s": batched_tx / sum(batched_t),
            "elapsed_s": batched_t,
            "setup_and_compile_s": batched_setup_s,
        },
        "pair_ratios": ratios,
        "speedup_windows_per_s": speedup,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if args.breakdown:
        result["breakdown"] = run_breakdown(sims[0], wl)
    # Gate BEFORE persisting: a run that fails its own check is still
    # recorded (the trajectory should show the dip) but flagged, and
    # flagged entries never enter the baseline median — retries of a
    # regressed branch cannot poison the gate they are failing.
    status = 0
    if args.check:
        prior = []
        if os.path.exists(args.out):
            try:
                with open(args.out) as f:
                    prior = json.load(f).get("history", [])
            except (OSError, ValueError):
                prior = []
        status = check_regression(prior, result)
        if status:
            result["regressed"] = True
    data = append_history(args.out, result)
    with open(args.out, "w") as f:
        json.dump(data, f, indent=1)
    print(f"# wrote {args.out} ({len(data['history'])} runs in history)",
          flush=True)
    if args.check:
        sys.exit(status)


if __name__ == "__main__":
    main()
