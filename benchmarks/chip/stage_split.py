#!/usr/bin/env python3
"""Split one traced run of a cell by program stage.

    python3 benchmarks/chip/stage_split.py --workload <cell> --seed <n> \
        --seconds <s>

Run from the root of a checkout, on a TPU.  It drives the cell's timed
path as ``run_cell.py --trace 1`` does (``harness.drive``: set-up, then
chunks under the profiler), and prints one JSON line: the device's self
time per program stage in µs per fleet window (``""``: ops under no
``repro.*`` stage), which adds up to ``window_device_us`` (``stages.py``).
The idle time per host span is read by ``run_cell.py --trace 1`` itself
(the ``*_idle_ms_per_chunk`` metrics).  It checks nothing against the
reference.  With no TPU it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import layout
    cell = layout.cell(args.workload)
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"stage_split: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    import harness
    import reduce as trace_reduce
    import stages

    clock = harness.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    with tempfile.TemporaryDirectory() as tmp:
        d = harness.drive(cell, args.seed, args.seconds, T_START, devices[0], clock,
                          trace_dir=tmp)
        red, ops = stages.load(trace_reduce.find_xplane(tmp), harness.SPAN_RUN)
    chunks = len(d.chunks)
    out = {"workload": args.workload, "seed": args.seed,
           "device": {"platform": devices[0].platform, "kind": devices[0].device_kind},
           "chunks": chunks, "chunk_s": d.chunk_s,
           **stages.split(red, ops, chunks * cell.traffic["chunk_windows"])}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
