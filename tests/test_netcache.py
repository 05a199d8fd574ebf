"""NetCache's data plane on hand-built batches: write-through coherence
with several writes and replies to one slot in one batch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines.netcache import (
    init_netcache,
    last_install,
    netcache_install,
    netcache_step,
)
from repro.core.hashing import hash128_u32
from repro.core.types import (
    OP_R_REQ,
    OP_W_REP,
    OP_W_REQ,
    ROUTE_CLIENT,
    ROUTE_SERVER,
    empty_batch,
)

TABLE, LIMIT, PAD = 256, 64, 96
KEYS = np.array([7, 11, 13], np.int32)


def _cached():
    st, n = netcache_install(init_netcache(TABLE, LIMIT), KEYS,
                             np.full(len(KEYS), LIMIT), key_size=16,
                             value_limit=LIMIT)
    assert n == len(KEYS)
    return st


def _slot_of(st, key):
    kidx = np.asarray(st.kidx).reshape(-1)
    return int(np.flatnonzero(kidx == key)[0])


def _batch(lanes):
    """``lanes``: (op, key, flag, vlen, fill byte) per lane."""
    b = empty_batch(len(lanes), PAD)
    op, key, flag, vlen, fill = (np.array(c) for c in zip(*lanes))
    val = np.where(np.arange(PAD) < vlen[:, None], fill[:, None], 0).astype(np.uint8)
    return b._replace(op=jnp.asarray(op, jnp.int32),
                      hkey=hash128_u32(jnp.asarray(key, jnp.int32)),
                      kidx=jnp.asarray(key, jnp.int32),
                      flag=jnp.asarray(flag, jnp.int32),
                      vlen=jnp.asarray(vlen, jnp.int32),
                      valid=jnp.ones(len(lanes), bool),
                      val=jnp.asarray(val))


def test_last_reply_to_a_slot_wins_and_versions_count_every_write():
    st = _cached()
    lanes = [(OP_W_REQ, 7, 0, 0, 0),
             (OP_W_REP, 7, 1, 40, 0xA1),
             (OP_W_REQ, 7, 0, 0, 0),
             (OP_W_REP, 11, 1, 64, 0xB2),
             (OP_W_REP, 7, 1, 24, 0xC3),
             (OP_R_REQ, 13, 0, 0, 0),
             (OP_W_REP, 7, 1, 9, 0xD4)]
    st2, route, flag, answered, n_hit = jax.jit(netcache_step)(st, _batch(lanes))

    val = np.asarray(st2.val).reshape(TABLE, LIMIT)
    vlen = np.asarray(st2.vlen).reshape(-1)
    version = np.asarray(st2.version).reshape(-1)
    valid = np.asarray(st2.valid).reshape(-1)
    a, b = _slot_of(st, 7), _slot_of(st, 11)
    # key 7: three replies in one batch; the last lane's 9 bytes stand
    assert vlen[a] == 9
    np.testing.assert_array_equal(val[a], np.where(np.arange(LIMIT) < 9, 0xD4, 0))
    assert version[a] == 2 and valid[a]
    # key 11: one reply, no write
    assert vlen[b] == 64 and np.all(val[b] == 0xB2) and version[b] == 0
    assert int(version.sum()) == 2
    np.testing.assert_array_equal(np.asarray(flag)[[0, 2]], [1, 1])
    assert np.asarray(route)[5] == ROUTE_CLIENT and bool(np.asarray(answered)[5])
    assert np.asarray(route)[0] == ROUTE_SERVER and int(n_hit) == 1


def test_last_install_keeps_one_lane_per_slot():
    slot = jnp.array([5, 3, 5, 5, 3, 9, 5], jnp.int32)
    install = jnp.array([True, True, True, False, False, True, False])
    np.testing.assert_array_equal(
        np.asarray(last_install(slot, install)),
        [False, True, True, False, False, True, False])


def test_write_without_reply_invalidates():
    st = _cached()
    st2, route, *_ = netcache_step(
        st, _batch([(OP_W_REQ, 13, 0, 0, 0), (OP_R_REQ, 13, 0, 0, 0)]))
    c = _slot_of(st, 13)
    assert not np.asarray(st2.valid).reshape(-1)[c]
    assert np.asarray(st2.version).reshape(-1)[c] == 1
    # the read in the same batch saw the slot as it was: answered
    assert np.asarray(route)[1] == ROUTE_CLIENT


@pytest.mark.parametrize("table,limit", [(1000, 64), (256, 48)])
def test_table_must_tile(table, limit):
    """Slots come in whole 128-lane rows and a row holds whole values."""
    with pytest.raises(ValueError, match="netcache table"):
        init_netcache(table, limit)
