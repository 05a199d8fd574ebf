"""Record the small trace that ``test_chip_reduce.py`` reads.

Run on one TPU from the root of a checkout:

    python benchmarks/chip/tests/record_trace.py

It drives the churn cell cut to a test's size (``chip_tiny.py``, 8-window
chunks with a control period of 2 windows) through the harness's program
side: set-up and one untimed chunk, then two chunks under the profiler,
with the harness's ``bench.run`` and ``bench.churn`` host spans.  The trace
is written gzipped to ``data/tiny_churn.xplane.pb.gz``, or to the path
given as the one argument.
"""
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

from chip_tiny import tiny_cell

import harness
import reduce as trace_reduce

OUT = Path(__file__).resolve().parent / "data" / "tiny_churn.xplane.pb.gz"


def main() -> int:
    import jax
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else OUT
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    cell = tiny_cell("paper_rack_orbitcache", "hotin_churn", chunk=8)
    prog = harness.Program(cell, harness.point_seeds(7, 2))
    prog.preload()
    prog.chunk(first=True)
    jax.block_until_ready(prog.fleet.carry)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(2):
                prog.chunk(first=False)
        out.parent.mkdir(parents=True, exist_ok=True)
        with open(trace_reduce.find_xplane(d), "rb") as src, gzip.open(out, "wb") as dst:
            shutil.copyfileobj(src, dst)
    print(f"record_trace: wrote {out} ({out.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
