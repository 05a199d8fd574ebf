"""The NetCache rack against its plain reference, at a size a CPU run can
hold.

The tiny ladder keeps NetCache's 64-B value limit and shrinks its table
to 1,024 slots and its preload to the 500 hottest of 5,000 keys, so that
the preload places keys on their second probe, refuses keys whose probes
are both taken and refuses keys whose values are over the limit.  The
program (the fleet under the ``ref`` kernels) must equal the reference
exactly, read-only and with a fifth of the requests writing; the control
(bfloat16 simulated time) and each planted fault of the timed path must
come out not correct.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_tiny import tiny_cell
from test_chip_correct import SEED, altered_answer, drive, half_the_points, unchanged_state

import compare
import harness
import layout
import reference as ref

ENTRIES, TABLE = 500, 1024


def tiny_netcache(write_ratio=0.0):
    cell = tiny_cell("paper_rack_netcache", "ladder12")
    cell.config["rack"].update(netcache_entries=ENTRIES, netcache_table=TABLE)
    cell.traffic["write_ratio"] = write_ratio
    return cell


def test_preload_places_on_both_probes_and_refuses():
    """Where the reference's preload puts each of the hottest keys."""
    cell = tiny_netcache()
    w = cell.config["workload"]
    g = ref.geometry(cell.config["rack"], w["key_size"])
    wl = ref.RefWorkload(w["num_keys"], w["zipf_alpha"], w["value_sizes"], w["value_seed"])
    nc = layout.scheme("netcache")
    st = nc.preload(g, {"switch": nc.init_switch(g)}, wl.perm_np, wl.vlen)
    sw = {k: np.asarray(v) for k, v in st["switch"].items()}
    keys = wl.perm_np[:ENTRIES]
    vlen = np.asarray(wl.vlen)[keys]
    hk = ref.hash128(jnp.asarray(keys))
    first, second = (np.asarray(nc.probe(hk, TABLE, s)) for s in nc.PROBES)
    at_first = sw["occupied"][first] & (sw["kidx"][first] == keys)
    at_second = ~at_first & sw["occupied"][second] & (sw["kidx"][second] == keys)
    over_limit = vlen > g.opt("netcache_value_limit")
    full = ~over_limit & ~at_first & ~at_second
    assert at_second.sum() > 0 and full.sum() > 0 and over_limit.sum() > 0
    assert not np.any(over_limit & (at_first | at_second))
    assert int(sw["occupied"].sum()) == int((at_first | at_second).sum())
    placed = np.where(at_first, first, second)[at_first | at_second]
    assert np.all(sw["valid"][placed])
    assert np.array_equal(sw["vlen"][placed], vlen[at_first | at_second])
    assert np.all(sw["version"] == 0)



def test_reference_last_reply_to_a_slot_wins():
    """Three replies to one cached slot in one subround: the last lane's
    bytes and length stand, and the version counts both writes."""
    cell = tiny_netcache()
    g = ref.geometry(cell.config["rack"], cell.config["workload"]["key_size"])
    nc = layout.scheme("netcache")
    hk = ref.hash128(jnp.full((5,), 7, jnp.int32))
    s = int(nc.probe(hk[:1], TABLE, nc.PROBES[0])[0])
    sw = nc.init_switch(g)
    sw = dict(sw, hkeys=sw["hkeys"].at[s].set(hk[0]), occupied=sw["occupied"].at[s].set(True),
              kidx=sw["kidx"].at[s].set(7), valid=sw["valid"].at[s].set(True))
    op = jnp.array([ref.W_REQ, ref.W_REP, ref.W_REQ, ref.W_REP, ref.W_REP], jnp.int32)
    vlen = jnp.array([0, 40, 0, 24, 9], jnp.int32)
    fill = jnp.array([0, 0xA1, 0, 0xC3, 0xD4], jnp.uint8)
    pk = dict(op=op, valid=jnp.ones(5, bool), hkey=hk, flag=jnp.array([0, 1, 0, 1, 1]),
              vlen=vlen, val=jnp.where(jnp.arange(96) < vlen[:, None], fill[:, None],
                                       jnp.uint8(0)))
    sw, _, flag, answered, _ = nc.subround(g, sw, pk)
    assert int(sw["vlen"][s]) == 9 and bool(sw["valid"][s]) and int(sw["version"][s]) == 2
    np.testing.assert_array_equal(np.asarray(sw["val"][s]),
                                  np.where(np.arange(64) < 9, 0xD4, 0))
    np.testing.assert_array_equal(np.asarray(flag)[[0, 2]], [1, 1])
    assert int(sw["version"].sum()) == 2 and not bool(answered.any())

@pytest.mark.parametrize("write_ratio", [0.0, 0.2])
def test_program_equals_reference(write_ratio):
    cell = tiny_netcache(write_ratio)
    seeds, prog = drive(cell)
    out = harness.replay(cell, seeds, 2, range(len(seeds)))
    numbers, attempted, failed = compare.compare(prog, out)
    assert numbers == {"state_mismatches": 0, "latency_hist_gap": 0.0, "time_gap_us": 0.0}
    assert attempted == 2 * 3 and failed == 0
    for p in prog:
        # the switch answers reads, and writes bump versions where asked
        assert sum(int(np.sum(t["rx_switch"])) for t in p["traces"]) > 0
        bumped = int(np.sum(p["state"]["switch.version"]))
        assert (bumped > 0) == (write_ratio > 0)


def test_control_in_bfloat16_is_rejected():
    cell = tiny_netcache()
    seeds, prog = drive(cell)
    control = harness.replay(cell, seeds, 2, range(len(seeds)), tdt="bfloat16")
    numbers, _, _ = compare.compare(prog, control)
    assert numbers["latency_hist_gap"] > 10 * compare.LIMITS["latency_hist_gap"]
    assert numbers["time_gap_us"] > 10 * compare.LIMITS["time_gap_us"]
    assert not compare.verdict(numbers)


@pytest.mark.parametrize("fault", [unchanged_state, half_the_points, altered_answer])
def test_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result, checks = harness.run(tiny_netcache(), SEED, 0.0, False, time.perf_counter(),
                                 jax.devices()[0])
    assert result["correct"] is False
    assert result["failed"] > 0
    assert checks["state_mismatches"]["value"] > 0
