"""The rack with writes equals the benchmark's plain reference.

The benchmark's cells are read-only, so their check never bumps a store
version.  Here the tiny OrbitCache, no-cache and NetCache ladders (the last
with a 1,024-slot table preloaded from the 500 hottest keys) run with a fifth
of the requests writing, through the benchmark's own program side, plain
reference and comparison, and every trace and the whole final state (the
store versions among it) must agree exactly.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "tests"))

from chip_tiny import tiny_cell  # noqa: E402

import compare  # noqa: E402
import harness  # noqa: E402

SEED = 2**33 + 54321
TIMED = 2


@pytest.mark.parametrize("config", ["paper_rack_orbitcache", "paper_rack_nocache",
                                    "paper_rack_netcache"])
def test_program_equals_reference_with_writes(config):
    cell = tiny_cell(config, "ladder12")
    cell.traffic["write_ratio"] = 0.2
    if config == "paper_rack_netcache":
        cell.config["rack"].update(netcache_entries=500, netcache_table=1024)
    seeds = harness.point_seeds(SEED, len(cell.traffic["offered_rps"]))
    prog = harness.Program(cell, seeds)
    prog.preload()
    prog.chunk(first=True)
    chunks = [prog.chunk(first=False) for _ in range(TIMED)]
    points = range(len(seeds))
    out = prog.outputs(chunks, points)
    ref = harness.replay(cell, seeds, TIMED, points)
    numbers, attempted, failed = compare.compare(out, ref)
    assert numbers["state_mismatches"] == 0, numbers
    assert compare.verdict(numbers) and failed == 0
    assert attempted == len(seeds) * (TIMED + 1)
    for p in out:
        versions = p["state"]["servers.key_version"]
        assert versions.shape == (cell.config["workload"]["num_keys"],)
        assert np.any(versions > 0)
