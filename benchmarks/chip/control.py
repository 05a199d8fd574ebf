#!/usr/bin/env python3
"""Readings for the limits of ``compare.py``: sound runs and the control.

    python benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

For each seed, in one process on the TPU: the cell's set-up and timed
window as ``run_cell.py`` drives them (``harness.drive``), then the comparison against the
plain reference (the sound reading) and against the reference with
bfloat16 simulated time put in the program's place (the control, which
has to come out not correct).  Prints one JSON line per seed.  The
benchmark's own runs never run the control.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import layout
    cell = layout.cell(args.workload)
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    import compare
    import harness

    clock = harness.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    for seed in (int(s) for s in args.seeds.split(",")):
        d = harness.drive(cell, seed, args.seconds, time.perf_counter(),
                          jax.devices()[0], clock)
        row = {"seed": seed, "chunks": len(d.chunks), "points": d.points}
        for name, tdt in (("sound", "float32"), ("control", "bfloat16")):
            t1 = time.perf_counter()
            ref = harness.replay(cell, d.seeds, len(d.chunks), d.points, tdt=tdt)
            numbers, _, failed = compare.compare(d.outputs, ref)
            row[name] = dict(numbers, correct=compare.verdict(numbers), failed_blocks=failed,
                             seconds=time.perf_counter() - t1)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
