"""Benchmark harness: one function per paper figure (Figs. 9-18).

Prints ``name,value,derived`` CSV rows.  ``--quick`` trims grids;
``--fig N`` runs one figure.  Results also land in
results/benchmarks.json.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.launch.compile_cache import configure_compile_cache  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--fig", type=int, default=0, help="9..18; 0 = all")
    ap.add_argument("--out", default="results/benchmarks.json")
    args = ap.parse_args()
    configure_compile_cache()

    from benchmarks import figures

    print("name,value,derived")
    t0 = time.time()
    results = {}
    for fn in figures.ALL_FIGS:
        num = int(fn.__name__[3:5])
        if args.fig and num != args.fig:
            continue
        t = time.time()
        try:
            out = fn(quick=args.quick)
            results[fn.__name__] = {str(k): (list(v) if isinstance(v, tuple)
                                             else (v.tolist() if hasattr(v, "tolist") else v))
                                    for k, v in (out.items() if isinstance(out, dict)
                                                 else enumerate(out))}
        except Exception as e:  # keep the suite going
            print(f"{fn.__name__},ERROR,{type(e).__name__}:{e}", flush=True)
            results[fn.__name__] = {"error": str(e)}
        print(f"# {fn.__name__} done in {time.time()-t:.0f}s", flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"# total {time.time()-t0:.0f}s -> {args.out}", flush=True)


if __name__ == "__main__":
    main()
