"""The servers' store versions: bumped by served writes, read back into
replies, and seen whole through ``ServerState.key_version``.

The versions live in a tile-aligned main block plus a flat tail
(``kvstore/server.py``); these tests hold ``server_step``'s bump and read
and the ``key_version`` view to a NumPy bincount of the writes the
servers served, at key counts where the main block is empty, where the
tail is empty, and where both hold keys, unbatched and under ``vmap``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import OP_R_REQ, OP_W_REP, OP_W_REQ, empty_batch
from repro.kvstore.server import ServerConfig, init_servers, server_step
from repro.kvstore.simulator import tree_stack
from repro.kvstore.store import synth_value_np

PAD = 8
CFG = ServerConfig(num_servers=2, queue_depth=16, cap_per_window=6,
                   value_pad=PAD, max_frags=1)
LANES, WINDOWS = 24, 6
# main block empty, tail empty, both, both with a tail off a 128 boundary
KEY_COUNTS = [1000, 4096, 5000, 2100]


def _split(num_keys):
    return 1024 * (num_keys // 1024)


def _edge_keys(num_keys):
    """Key 0, the last key and both sides of the main/tail boundary."""
    split = _split(num_keys)
    keys = {0, num_keys - 1}
    if 0 < split < num_keys:
        keys |= {split - 1, split}
    return sorted(keys)


def _windows(num_keys, seed):
    """``WINDOWS`` packet batches of mixed reads and writes.  The first
    window opens with writes to the edge keys, the i-th of them i + 1
    times so that neighbours differ, and the second with a read of each;
    one key (``ghost``) is written only by lanes that do not reach a
    server."""
    rng = np.random.default_rng(seed)
    ghost = num_keys // 2 + 1
    edges = _edge_keys(num_keys)
    out = []
    for w in range(WINDOWS):
        kidx = rng.integers(0, num_keys, LANES)
        kidx[kidx == ghost] = 0
        op = np.where(rng.random(LANES) < 0.5, OP_W_REQ, OP_R_REQ)
        srv = rng.integers(0, CFG.num_servers, LANES)
        to_server = rng.random(LANES) < 0.8
        if w < 2:
            first = np.repeat(edges, np.arange(1, len(edges) + 1)) if w == 0 else edges
            e = len(first)
            kidx[:e], to_server[:e] = first, True
            op[:e] = OP_W_REQ if w == 0 else OP_R_REQ
            srv[:e] = np.arange(e) % CFG.num_servers
        masked = np.flatnonzero(~to_server)
        kidx[masked[:2]], op[masked[:2]] = ghost, OP_W_REQ
        pk = empty_batch(LANES, PAD)._replace(
            op=jnp.asarray(op, jnp.int32), kidx=jnp.asarray(kidx, jnp.int32),
            vlen=jnp.full((LANES,), PAD, jnp.int32),
            server=jnp.asarray(srv, jnp.int32), valid=jnp.ones((LANES,), bool))
        out.append((pk, jnp.asarray(to_server)))
    return out, ghost


def _check_window(versions, replies, counts, num_keys):
    """Add the window's served writes to ``counts``, then hold the stored
    versions and every reply's value bytes to them."""
    valid = np.asarray(replies.valid)
    kidx = np.asarray(replies.kidx)[valid]
    writes = kidx[np.asarray(replies.op)[valid] == OP_W_REP]
    counts += np.bincount(writes, minlength=num_keys).astype(np.int32)
    assert versions.shape == (num_keys,) and versions.dtype == np.int32
    np.testing.assert_array_equal(versions, counts)
    val = np.asarray(replies.val)[valid]
    for k, v in zip(kidx, val):
        np.testing.assert_array_equal(v, synth_value_np(k, counts[k], PAD))


def _check_layout(st, num_keys, lead=()):
    split = _split(num_keys)
    assert st.kv_main.shape == lead + (split // 128, 128)
    assert st.kv_tail.shape == lead + (num_keys - split,)
    for view in (st.key_version, jax.device_get(st).key_version):
        assert view.shape == lead + (num_keys,)
        assert view.dtype == np.int32
    assert isinstance(jax.device_get(st).key_version, np.ndarray)


def _check_exercised(counts, num_keys, ghost):
    assert all(counts[k] >= 1 for k in _edge_keys(num_keys))
    assert counts[ghost] == 0


@pytest.mark.parametrize("num_keys", KEY_COUNTS)
def test_versions_count_served_writes(num_keys):
    st = init_servers(CFG, num_keys)
    _check_layout(st, num_keys)
    counts = np.zeros(num_keys, np.int32)
    windows, ghost = _windows(num_keys, seed=num_keys)
    flag = jnp.ones((LANES,), jnp.int32)   # write replies carry values too
    for w, (pk, to_server) in enumerate(windows):
        st, out = server_step(st, CFG, pk, to_server, flag, jnp.float32(100.0 * w))
        _check_window(np.asarray(st.key_version), out.replies, counts, num_keys)
    _check_layout(st, num_keys)
    _check_exercised(counts, num_keys, ghost)


@pytest.mark.parametrize("num_keys", KEY_COUNTS)
def test_versions_count_served_writes_under_vmap(num_keys):
    points = 3
    st = tree_stack([init_servers(CFG, num_keys)] * points)
    _check_layout(st, num_keys, (points,))
    per_point = [_windows(num_keys, seed=num_keys + 7 * p) for p in range(points)]
    counts = np.zeros((points, num_keys), np.int32)
    flag = jnp.ones((LANES,), jnp.int32)
    for w in range(WINDOWS):
        pk = tree_stack([per_point[p][0][w][0] for p in range(points)])
        to_server = jnp.stack([per_point[p][0][w][1] for p in range(points)])
        st, out = jax.vmap(lambda s, b, t: server_step(
            s, CFG, b, t, flag, jnp.float32(100.0 * w)))(st, pk, to_server)
        versions = np.asarray(st.key_version)
        assert versions.shape == (points, num_keys)
        for p in range(points):
            _check_window(versions[p], jax.tree.map(lambda x: x[p], out.replies),
                          counts[p], num_keys)
    _check_layout(st, num_keys, (points,))
    for p in range(points):
        _check_exercised(counts[p], num_keys, per_point[p][1])
