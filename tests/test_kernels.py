"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.core.hashing import hash128_u32
from repro.kernels.cms import ops as cms_ops
from repro.kernels.cms.ops import rows_for
from repro.kernels.cms.ref import cms_update_query_fast, cms_update_query_ref
from repro.kernels.hot_gather import ops as hot_gather_ops
from repro.kernels.hot_gather.ref import hot_gather_ref
from repro.kernels.orbit_match import ops as orbit_match_ops
from repro.kernels.orbit_match.ref import orbit_match_ref

# the kernels under the Pallas interpreter, against their oracles
cms_update_query = partial(cms_ops.cms_update_query, interpret=True)
hot_gather = partial(hot_gather_ops.hot_gather, interpret=True)
orbit_match = partial(orbit_match_ops.orbit_match, interpret=True)

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("b,c", [(8, 8), (64, 16), (300, 128), (1024, 512),
                                 (17, 5)])
def test_orbit_match_sweep(b, c):
    keys = jnp.asarray(RNG.integers(0, 50, c), jnp.int32)
    table = hash128_u32(keys)
    occ = jnp.asarray(RNG.integers(0, 2, c), jnp.int32)
    val = jnp.asarray(RNG.integers(0, 2, c), jnp.int32)
    q = jnp.asarray(RNG.integers(0, 60, b), jnp.int32)
    hq = hash128_u32(q)
    for got, want in zip(orbit_match(hq, table, occ, val),
                         orbit_match_ref(hq, table, occ, val)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_orbit_match_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 200), st.integers(1, 64), st.integers(8, 64))
    def check(b, c, universe):
        c = min(c, universe)  # table keys distinct (controller invariant)
        keys = jnp.asarray(RNG.choice(universe, c, replace=False), jnp.int32)
        table = hash128_u32(keys)
        occ = jnp.ones(c, jnp.int32)
        val = jnp.ones(c, jnp.int32)
        q = jnp.asarray(RNG.integers(0, universe, b), jnp.int32)
        cidx, hit, vhit, pop = orbit_match(hash128_u32(q), table, occ, val)
        # every reported hit indexes an entry whose key hash matches
        cidx_np, hit_np = np.asarray(cidx), np.asarray(hit)
        keys_np, q_np = np.asarray(keys), np.asarray(q)
        for i in range(b):
            if hit_np[i]:
                assert keys_np[cidx_np[i]] == q_np[i]
            else:
                assert q_np[i] not in set(keys_np.tolist())
        assert int(pop.sum()) == int(hit.sum())

    check()


@pytest.mark.parametrize("b,w,block", [(64, 512, 64), (513, 2048, 256),
                                       (100, 256, 32)])
def test_cms_sweep(b, w, block):
    hk = hash128_u32(jnp.asarray(RNG.integers(0, 1000, b), jnp.int32))
    mask = jnp.asarray(RNG.integers(0, 2, b), jnp.int32)
    counts = jnp.asarray(RNG.integers(0, 5, (5, w)), jnp.int32)
    nk, ek = cms_update_query(hk, mask, counts, block_b=block)
    pad = (-b) % min(block, max(8, b))
    idx = jnp.pad(rows_for(hk, w), ((0, pad), (0, 0)))
    msk = jnp.pad(mask, (0, pad))
    nr, er = cms_update_query_ref(idx, msk, counts, block_b=min(block, max(8, b)))
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(nr))
    np.testing.assert_array_equal(np.asarray(ek), np.asarray(er[:b]))


# ---------------------------------------------------------------------------
# parity edge cases: pad-tail batches, empty tables, all-invalid entries
# ---------------------------------------------------------------------------
def _match_case(b, c, occ, val, mask=None, block_b=256):
    keys = jnp.asarray(RNG.integers(0, 50, c), jnp.int32)
    table = hash128_u32(keys)
    q = jnp.asarray(RNG.integers(0, 60, b), jnp.int32)
    hq = hash128_u32(q)
    got = orbit_match(hq, table, occ, val, mask, block_b=block_b)
    want = orbit_match_ref(hq, table, occ, val, mask)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_orbit_match_batch_not_block_multiple():
    # B % block_b != 0: the wrapper pads, pad lanes must not leak into pop
    mask = jnp.asarray(RNG.integers(0, 2, 37), jnp.int32)
    _match_case(37, 16, jnp.ones(16, jnp.int32), jnp.ones(16, jnp.int32),
                mask=mask, block_b=32)


def test_orbit_match_empty_table():
    # nothing occupied: all misses, zero popularity
    b, c = 40, 8
    occ = jnp.zeros(c, jnp.int32)
    val = jnp.ones(c, jnp.int32)
    keys = jnp.asarray(RNG.integers(0, 50, c), jnp.int32)
    q = jnp.asarray(RNG.integers(0, 50, b), jnp.int32)
    cidx, hit, vhit, pop = orbit_match(hash128_u32(q), hash128_u32(keys),
                                       occ, val)
    assert np.asarray(cidx).tolist() == [-1] * b
    assert int(np.asarray(hit).sum()) == 0
    assert int(np.asarray(vhit).sum()) == 0
    assert int(np.asarray(pop).sum()) == 0
    _match_case(b, c, occ, val)


def test_orbit_match_all_invalid_entries():
    # occupied but invalid: hits happen, valid-hits never
    b, c = 64, 8
    occ = jnp.ones(c, jnp.int32)
    val = jnp.zeros(c, jnp.int32)
    keys = jnp.arange(c, dtype=jnp.int32)
    q = jnp.asarray(RNG.integers(0, c, b), jnp.int32)
    cidx, hit, vhit, pop = orbit_match(hash128_u32(q), hash128_u32(keys),
                                       occ, val)
    assert int(np.asarray(hit).sum()) == b
    assert int(np.asarray(vhit).sum()) == 0
    _match_case(b, c, occ, val)


def test_orbit_match_mask_parity():
    # masked popularity: kernel == oracle == hand count
    b, c = 48, 8
    keys = jnp.arange(c, dtype=jnp.int32)
    q = jnp.asarray(RNG.integers(0, c, b), jnp.int32)
    mask = jnp.asarray(RNG.integers(0, 2, b), jnp.int32)
    occ = jnp.ones(c, jnp.int32)
    val = jnp.ones(c, jnp.int32)
    for fn in (orbit_match, orbit_match_ref):
        _, _, _, pop = fn(hash128_u32(q), hash128_u32(keys), occ, val, mask)
        want = np.bincount(np.asarray(q)[np.asarray(mask) > 0], minlength=c)
        np.testing.assert_array_equal(np.asarray(pop), want)


def _int32_rows(c, d):
    """Rows over the whole int32 range: every 8-bit limb and the sign."""
    info = np.iinfo(np.int32)
    return jnp.asarray(RNG.integers(info.min, info.max, (c, d),
                                    endpoint=True), jnp.int32)


@pytest.mark.parametrize("b,c,d,dup", [
    (64, 32, 128, False),
    (500, 128, 300, True),     # repeated hot ids: matching rows are summed
    (8, 512, 64, False),
    (1024, 64, 1024, True),
])
def test_hot_gather_sweep(b, c, d, dup):
    ids = jnp.asarray(RNG.integers(0, 4 * c, b), jnp.int32)
    hot = jnp.asarray(np.sort(RNG.choice(4 * c, c, replace=dup)), jnp.int32)
    rows = _int32_rows(c, d)
    out, hit = hot_gather(ids, hot, rows)
    want, hit_w = hot_gather_ref(ids, hot, rows)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(hit), np.asarray(hit_w))


def test_cms_batch_not_block_multiple():
    # B % block_b != 0 and masked lanes: kernel pad tail must not count
    b, w, block = 45, 512, 32
    hk = hash128_u32(jnp.asarray(RNG.integers(0, 200, b), jnp.int32))
    mask = jnp.asarray(RNG.integers(0, 2, b), jnp.int32)
    counts = jnp.zeros((5, w), jnp.int32)
    nk, ek = cms_update_query(hk, mask, counts, block_b=block)
    idx = jnp.pad(rows_for(hk, w), ((0, (-b) % block), (0, 0)))
    msk = jnp.pad(mask, (0, (-b) % block))
    nr, er = cms_update_query_ref(idx, msk, counts, block_b=block)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(nr))
    np.testing.assert_array_equal(np.asarray(ek), np.asarray(er[:b]))
    assert int(np.asarray(nk).sum()) == 5 * int(np.asarray(mask).sum())


def test_hot_gather_all_misses():
    # no id in the hot set: zero rows, zero hits (both paths)
    b, c, d = 33, 16, 128
    ids = jnp.asarray(RNG.integers(1000, 2000, b), jnp.int32)
    hot = jnp.arange(c, dtype=jnp.int32)
    rows = _int32_rows(c, d)
    out, hit = hot_gather(ids, hot, rows)
    want, hit_w = hot_gather_ref(ids, hot, rows)
    assert int(np.asarray(hit).sum()) == 0
    assert not np.asarray(out).any()
    np.testing.assert_array_equal(np.asarray(hit), np.asarray(hit_w))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ---------------------------------------------------------------------------
# the admission slice of the fused subround vs the free-standing oracles
# (folded here from the retired kernels.orbit_pipeline op's test suite)
# ---------------------------------------------------------------------------
def test_subround_admission_matches_enqueue_composition():
    """The subround oracle's admission slice == orbit_match +
    request_table.enqueue/apply_winners composed (the guarantee the retired
    ``kernels.orbit_pipeline`` op used to carry)."""
    from repro.core import request_table as rt
    from repro.core.types import RequestTable
    from repro.kernels.subround.ops import SubroundOuts
    from repro.kernels.subround.ref import subround_ref

    b, c, s = 96, 16, 4
    keys = jnp.asarray(RNG.choice(2000, c, replace=False), jnp.int32)
    table = hash128_u32(keys)
    occ = jnp.ones(c, jnp.int32)
    val = jnp.ones(c, jnp.int32)
    q = jnp.asarray(RNG.choice(np.asarray(keys), b), jnp.int32)
    hq = hash128_u32(q)
    mask = jnp.asarray(RNG.integers(0, 2, b), jnp.int32)
    qlen = jnp.asarray(RNG.integers(0, s + 1, c), jnp.int32)
    rear = jnp.asarray(RNG.integers(0, s, c), jnp.int32)
    lanes = jnp.arange(b, dtype=jnp.int32)
    zeros = jnp.zeros(b, jnp.int32)

    # wreq/inst gates off: the subround reduces to match + admission + serve
    got = SubroundOuts(*subround_ref(
        hq, mask, zeros, zeros, zeros, jnp.ones(b, jnp.int32), lanes, lanes,
        lanes, lanes, lanes, lanes.astype(jnp.float32),
        table, occ, val, jnp.zeros(c, jnp.int32),
        jnp.full(c * s, -1, jnp.int32), jnp.zeros(c * s, jnp.int32),
        jnp.zeros(c * s, jnp.int32), jnp.zeros(c * s, jnp.float32),
        jnp.zeros(c * s, jnp.int32), jnp.full(c * s, -1, jnp.int32),
        qlen, jnp.zeros(c, jnp.int32), rear,
        jnp.zeros(c, jnp.int32), jnp.full(c, -1, jnp.int32),
        jnp.zeros(c, jnp.int32), jnp.zeros(c, jnp.int32),
        jnp.ones(c, jnp.int32),
        jnp.int32(0),  # zero budget: the serve round must not pop
        queue_size=s, max_frags=1, max_serves=4))

    m_cidx, m_hit, m_vhit, m_pop = orbit_match_ref(hq, table, occ, val, mask)
    np.testing.assert_array_equal(np.asarray(got.pop), np.asarray(m_pop))
    np.testing.assert_array_equal(np.asarray(got.hit),
                                  np.asarray(m_hit).astype(np.int32))

    tbl = RequestTable(
        client=jnp.full(c * s, -1, jnp.int32), seq=jnp.zeros(c * s, jnp.int32),
        port=jnp.zeros(c * s, jnp.int32), ts=jnp.zeros(c * s, jnp.float32),
        acked=jnp.zeros(c * s, jnp.int32), kidx=jnp.full(c * s, -1, jnp.int32),
        qlen=qlen, front=jnp.zeros(c, jnp.int32), rear=rear)
    want_mask = (mask > 0) & (m_hit > 0) & (m_vhit > 0)
    enq = rt.enqueue(tbl, jnp.where(m_cidx >= 0, m_cidx, 0), want_mask,
                     lanes, lanes, lanes, lanes.astype(jnp.float32),
                     kidx=lanes)
    np.testing.assert_array_equal(np.asarray(got.accepted),
                                  np.asarray(enq.accepted).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(got.overflow),
                                  np.asarray(enq.overflow).astype(np.int32))
    for name, got_leaf, want_leaf in zip(
            ("client", "seq", "port", "ts", "acked", "kidx", "qlen", "front",
             "rear"),
            (got.rt_client, got.rt_seq, got.rt_port, got.rt_ts, got.rt_acked,
             got.rt_kidx, got.qlen, got.front, got.rear),
            enq.table):
        np.testing.assert_array_equal(np.asarray(got_leaf),
                                      np.asarray(want_leaf),
                                      err_msg=f"rt.{name}")


def test_cms_fast_ref_matches_onehot_oracle():
    """The dispatcher's scatter/gather ref path == the one-hot kernel
    transcription, including cross-tile estimate sequencing."""
    for b, w, block in [(45, 512, 32), (513, 2048, 256)]:
        hk = hash128_u32(jnp.asarray(RNG.integers(0, 1000, b), jnp.int32))
        mask = jnp.asarray(RNG.integers(0, 2, b), jnp.int32)
        counts = jnp.asarray(RNG.integers(0, 5, (5, w)), jnp.int32)
        pad = (-b) % block
        idx = jnp.pad(rows_for(hk, w), ((0, pad), (0, 0)))
        msk = jnp.pad(mask, (0, pad))
        for g, r in zip(cms_update_query_fast(idx, msk, counts, block_b=block),
                        cms_update_query_ref(idx, msk, counts, block_b=block)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


# ---------------------------------------------------------------------------
# backend dispatch layer
# ---------------------------------------------------------------------------
def test_dispatch_autodetect_picks_oracle_off_tpu(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    kernels.set_kernel_backend(None)
    expect = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert kernels.kernel_backend() == expect


def test_dispatch_env_and_forced_override(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    kernels.set_kernel_backend(None)
    assert kernels.kernel_backend() == "interpret"
    kernels.set_kernel_backend("ref")
    try:
        assert kernels.kernel_backend() == "ref"  # forced beats env
    finally:
        kernels.set_kernel_backend(None)
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "bogus")
    with pytest.raises(ValueError):
        kernels.kernel_backend()
    with pytest.raises(ValueError):
        kernels.set_kernel_backend("bogus")


def test_dispatch_matches_oracles_on_all_backends():
    b, c = 40, 16
    keys = jnp.asarray(RNG.integers(0, 30, c), jnp.int32)
    occ = jnp.asarray(RNG.integers(0, 2, c), jnp.int32)
    val = jnp.asarray(RNG.integers(0, 2, c), jnp.int32)
    q = jnp.asarray(RNG.integers(0, 40, b), jnp.int32)
    hq, table = hash128_u32(q), hash128_u32(keys)
    mask = jnp.asarray(RNG.integers(0, 2, b), jnp.int32)
    counts = jnp.asarray(RNG.integers(0, 5, (5, 256)), jnp.int32)
    want_match = orbit_match_ref(hq, table, occ, val, mask)
    widx = jnp.pad(rows_for(hq, 256), ((0, 0), (0, 0)))
    want_cms = cms_update_query_ref(widx, mask, counts, block_b=b)
    for be in ("ref", "interpret"):
        kernels.set_kernel_backend(be)
        try:
            got = kernels.orbit_match(hq, table, occ, val, mask)
            for g, w in zip(got, want_match):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            nk, ek = kernels.cms_update_query(hq, mask, counts)
            np.testing.assert_array_equal(np.asarray(nk),
                                          np.asarray(want_cms[0]))
            np.testing.assert_array_equal(np.asarray(ek),
                                          np.asarray(want_cms[1][:b]))
        finally:
            kernels.set_kernel_backend(None)


# ---------------------------------------------------------------------------
# subround: the FULL fused per-subround pass (match + admission + state +
# install + serving round)
# ---------------------------------------------------------------------------
def _subround_case(b, c, s, f, budget):
    """Random-but-consistent full-subround inputs (hit-heavy traffic)."""
    keys = jnp.asarray(RNG.choice(2000, c, replace=False), jnp.int32)
    q = jnp.asarray(RNG.choice(np.asarray(keys), b), jnp.int32)
    front = jnp.asarray(RNG.integers(0, s, c), jnp.int32)
    qlen = jnp.asarray(RNG.integers(0, s + 1, c), jnp.int32)
    return (
        hash128_u32(q),                                            # hkey
        jnp.asarray(RNG.integers(0, 2, b), jnp.int32),             # want
        jnp.asarray((RNG.integers(0, 4, b) == 0), jnp.int32),      # wreq
        jnp.asarray((RNG.integers(0, 4, b) == 1), jnp.int32),      # inst
        jnp.asarray(RNG.integers(0, f + 1, b), jnp.int32),         # frag
        jnp.asarray(RNG.integers(1, f + 1, b), jnp.int32),         # nfrags
        q,                                                         # kidx
        jnp.asarray(RNG.integers(1, 100, b), jnp.int32),           # vlen
        jnp.asarray(RNG.integers(0, 8, b), jnp.int32),             # client
        jnp.arange(b, dtype=jnp.int32),                            # seq
        jnp.asarray(RNG.integers(0, 100, b), jnp.int32),           # port
        jnp.asarray(RNG.random(b), jnp.float32),                   # ts
        hash128_u32(keys),                                         # table
        jnp.asarray(RNG.integers(0, 2, c), jnp.int32),             # occupied
        jnp.asarray(RNG.integers(0, 2, c), jnp.int32),             # st_valid
        jnp.asarray(RNG.integers(0, 5, c), jnp.int32),             # st_version
        jnp.asarray(RNG.integers(-1, 8, c * s), jnp.int32),        # rt_client
        jnp.asarray(RNG.integers(0, 99, c * s), jnp.int32),        # rt_seq
        jnp.asarray(RNG.integers(0, 99, c * s), jnp.int32),        # rt_port
        jnp.asarray(RNG.random(c * s), jnp.float32),               # rt_ts
        jnp.zeros(c * s, jnp.int32),                               # rt_acked
        jnp.asarray(RNG.integers(-1, 2000, c * s), jnp.int32),     # rt_kidx
        qlen, front, (front + qlen) % s,                           # q/f/rear
        jnp.asarray(RNG.integers(0, 2, c * f), jnp.int32),         # ob_live
        jnp.asarray(RNG.integers(-1, 2000, c * f), jnp.int32),     # ob_kidx
        jnp.asarray(RNG.integers(0, 5, c * f), jnp.int32),         # ob_version
        jnp.asarray(RNG.integers(0, 100, c * f), jnp.int32),       # ob_vlen
        jnp.asarray(RNG.integers(1, f + 1, c), jnp.int32),         # ob_frags
        jnp.int32(budget),
    )


@pytest.mark.parametrize("b,c,s,f,j,block,budget", [
    (24, 8, 4, 1, 4, 8, 100),     # multi-tile, generous budget
    (64, 16, 8, 2, 8, 32, 7),     # multi-fragment lines, tight budget
    (17, 5, 3, 2, 4, 8, 0),       # batch pad + zero recirculation budget
    (300, 130, 8, 1, 8, 64, 25),  # C > 128 (table pad)
])
def test_subround_kernel_matches_oracle(b, c, s, f, j, block, budget):
    from repro.kernels.subround.ops import SubroundOuts
    from repro.kernels.subround.ops import subround as subround_op
    from repro.kernels.subround.ref import subround_ref

    args = _subround_case(b, c, s, f, budget)
    want = SubroundOuts(*subround_ref(
        *args, queue_size=s, max_frags=f, max_serves=j))
    got = subround_op(*args, s, f, j, block_b=block, interpret=True)
    for name, g, w in zip(SubroundOuts._fields, got, want):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w),
            err_msg=f"{name} (b={b}, c={c}, s={s}, f={f}, j={j})")


def test_subround_dispatch_matches_oracle_on_all_backends():
    from repro.kernels.subround.ops import SubroundOuts
    from repro.kernels.subround.ref import subround_ref

    b, c, s, f, j = 40, 16, 4, 2, 4
    args = _subround_case(b, c, s, f, 11)
    want = SubroundOuts(*subround_ref(
        *args, queue_size=s, max_frags=f, max_serves=j))
    for be in ("ref", "interpret"):
        kernels.set_kernel_backend(be)
        try:
            got = kernels.subround(*args, s, f, j)
            for name, g, w in zip(SubroundOuts._fields, got, want):
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(w),
                    err_msg=f"{name} (backend={be})")
        finally:
            kernels.set_kernel_backend(None)


def test_subround_ref_matches_composed_oracles():
    """The fused subround oracle == the free-standing core oracles composed
    (enqueue/apply_winners + apply_batch + install_lines_meta + orbit_pass
    over a hand-built PipelineCarry)."""
    from repro.core import orbit as ob
    from repro.core import request_table as rt
    from repro.core import state_table as stt
    from repro.core.types import (OrbitMeta, RequestTable, StateTable)
    from repro.kernels.subround.ops import SubroundOuts
    from repro.kernels.subround.ref import subround_ref

    b, c, s, f, j = 48, 8, 4, 2, 4
    args = _subround_case(b, c, s, f, 13)
    (hq, want, wreq, inst, frag, nfr, kidx, vlen, client, seq, port, ts,
     thk, occ, stv, stver, rtc, rtseq, rtp, rtts, rta, rtk, qlen, front,
     rear, olive, okidx, over, ovlen, ofr, budget) = args
    got = SubroundOuts(*subround_ref(*args, queue_size=s, max_frags=f,
                                     max_serves=j))

    # compose the oracles
    from repro.kernels.orbit_match.ref import orbit_match_ref
    cidx, hit, vhit, pop = orbit_match_ref(hq, thk, occ, stv, want)
    np.testing.assert_array_equal(np.asarray(got.pop), np.asarray(pop))
    hitb = hit > 0
    safe = jnp.where(hitb, cidx, 0)
    tbl = RequestTable(client=rtc, seq=rtseq, port=rtp, ts=rtts, acked=rta,
                       kidx=rtk, qlen=qlen, front=front, rear=rear)
    enq = rt.enqueue(tbl, safe, (want > 0) & hitb & (vhit > 0),
                     client, seq, port, ts, kidx=kidx)
    st2 = stt.apply_batch(StateTable(valid=stv > 0, version=stver), safe,
                          (wreq > 0) & hitb, (inst > 0) & hitb)
    meta, writer, written = ob.install_lines_meta(
        OrbitMeta(live=olive > 0, kidx=okidx, version=over, vlen=ovlen,
                  frags=ofr),
        safe, (inst > 0) & hitb, kidx, st2.version[safe], vlen,
        frag=frag, n_frags=jnp.maximum(nfr, 1))
    np.testing.assert_array_equal(np.asarray(got.accepted),
                                  np.asarray(enq.accepted).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(got.val_writer),
                                  np.asarray(writer))
    np.testing.assert_array_equal(np.asarray(got.val_written),
                                  np.asarray(written).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(got.st_valid),
                                  np.asarray(st2.valid).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(got.st_version),
                                  np.asarray(st2.version))

    # serving round on the updated tables
    from repro.core.types import SwitchState, LookupTable, Counters, OrbitBuffer
    swst = SwitchState(
        lookup=LookupTable(hkeys=thk, occupied=occ > 0,
                           kidx=jnp.full((c,), -1, jnp.int32)),
        state=st2,
        reqtab=enq.table,
        orbit=OrbitBuffer(live=meta.live, kidx=meta.kidx,
                          version=meta.version, vlen=meta.vlen,
                          val=jnp.zeros((c * f, 8), jnp.uint8),
                          frags=meta.frags),
        counters=Counters(popularity=jnp.zeros((c,), jnp.uint32),
                          hits=jnp.zeros((), jnp.uint32),
                          overflow=jnp.zeros((), jnp.uint32),
                          cached_reqs=jnp.zeros((), jnp.uint32)),
    )
    sw2, grid = ob.orbit_pass(swst, budget, j)
    np.testing.assert_array_equal(np.asarray(got.served),
                                  np.asarray(grid.served).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(got.g_client),
                                  np.asarray(grid.client))
    np.testing.assert_array_equal(np.asarray(got.g_ts), np.asarray(grid.ts))
    np.testing.assert_array_equal(np.asarray(got.line_vlen),
                                  np.asarray(grid.vlen))
    np.testing.assert_array_equal(np.asarray(got.line_version),
                                  np.asarray(grid.version))
    np.testing.assert_array_equal(np.asarray(got.qlen),
                                  np.asarray(sw2.reqtab.qlen))
    np.testing.assert_array_equal(np.asarray(got.front),
                                  np.asarray(sw2.reqtab.front))
    np.testing.assert_array_equal(np.asarray(got.ob_live),
                                  np.asarray(sw2.orbit.live).astype(np.int32))
