"""NetCache [21] baseline: hot items stored *in switch memory* (paper §2.1).

Faithful to the reference architecture and its hardware limits:

* the cache lookup table is an exact-match table on the item key — the
  match-key width caps keys at 16 bytes;
* values live across match-action stages — value size is capped at
  ``value_limit`` bytes (the paper's own NetCache prototype served 64 B
  across 8 stages; 128 B is the architectural best case);
* hits are answered directly by the switch at line rate;
* write-through invalidation like OrbitCache (NetCache §Cache coherence).

Items whose key or value exceeds the limits are *uncacheable* — the
controller refuses to install them.  That refusal is the paper's whole
motivation.

The lookup table here is a 2-probe direct-indexed hash table (O(1) per
packet at 10K entries, vs the O(C) associative scan that is fine for
OrbitCache's ~128 entries).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax.numpy as jnp

from repro.core.hashing import fold_hash, hash128_u32_np
from repro.core.types import (
    OP_CRN_REQ,
    OP_F_REP,
    OP_F_REQ,
    OP_R_REP,
    OP_R_REQ,
    OP_W_REP,
    OP_W_REQ,
    ROUTE_CLIENT,
    ROUTE_DROP,
    ROUTE_SERVER,
    COUNTER_DTYPE,
    HKEY_LANES,
    PacketBatch,
    sat_add,
)

N_PROBES = 2
ROW = 128  # a tile row: per-slot tables are stored [T // ROW, ROW]


class NetCacheState(NamedTuple):
    """The switch-memory table of ``T`` slots.

    Per-slot tables are stored tile-aligned, slot ``s`` at ``(s // ROW,
    s % ROW)``, and the value bytes as one ``[T * limit // ROW, ROW]``
    block, slot ``s``'s bytes from flat offset ``s * limit`` (``limit``
    divides ``ROW``).  Under ``vmap`` over sweep points a subround's
    scatters then update the scan carry in its own layout; a ``[T]`` or
    ``[T, 64]`` table makes XLA copy or reshape the whole table into the
    scatter's layout and back.
    """
    hkeys: jnp.ndarray     # uint32[T, 4]
    occupied: jnp.ndarray  # bool[T // ROW, ROW]
    kidx: jnp.ndarray      # int32[T // ROW, ROW]
    valid: jnp.ndarray     # bool[T // ROW, ROW]
    val: jnp.ndarray       # uint8[T * value_limit // ROW, ROW]
    vlen: jnp.ndarray      # int32[T // ROW, ROW]
    hits: jnp.ndarray      # uint32[] running hit count (sat_add, wrap-safe)
    version: jnp.ndarray   # int32[T // ROW, ROW]


def init_netcache(table_size: int, value_limit: int) -> NetCacheState:
    t = table_size
    if t % ROW or ROW % value_limit:
        raise ValueError(f"netcache table of {t} slots and {value_limit}-B values: "
                         f"{ROW} must divide the slots and be a multiple of the bytes")
    rows = (t // ROW, ROW)
    return NetCacheState(
        hkeys=jnp.zeros((t, HKEY_LANES), jnp.uint32),
        occupied=jnp.zeros(rows, bool),
        kidx=jnp.full(rows, -1, jnp.int32),
        valid=jnp.zeros(rows, bool),
        val=jnp.zeros((t * value_limit // ROW, ROW), jnp.uint8),
        vlen=jnp.zeros(rows, jnp.int32),
        hits=jnp.zeros((), COUNTER_DTYPE),
        version=jnp.zeros(rows, jnp.int32),
    )


def _slots(st: NetCacheState) -> int:
    return st.hkeys.shape[0]


def _limit(st: NetCacheState) -> int:
    """Value bytes a slot holds."""
    return st.val.size // _slots(st)


def _cell(slot: jnp.ndarray):
    """Slot -> (row, lane) of a per-slot table; an out-of-range slot stays
    out of range (a dropped update)."""
    return slot // ROW, slot % ROW


def _put_values(val: jnp.ndarray, slot: jnp.ndarray, win: jnp.ndarray,
                v: jnp.ndarray) -> jnp.ndarray:
    """Write the ``limit`` bytes ``v`` of each winning lane into its slot
    (``slot`` in range on every lane; the winners' slots are distinct).

    A row of the value block holds ``ROW // limit`` slots.  Each winning
    lane adds to its slot's row the difference, modulo 256, between its
    bytes and the slot's bytes as they stand, and zero to the row's other
    slots.  Winners whose slots share a row then add into separate bytes,
    and the whole-row scatter-add works on the block in its own layout
    with no comparison between lanes."""
    b, limit = v.shape
    k = ROW // limit                                  # slots per row
    row, pos = slot // k, slot % k
    old = val[row].reshape(b, k, limit)
    mine = (pos[:, None] == jnp.arange(k))[..., None]
    delta = jnp.where(mine, v[:, None, :] - old, jnp.uint8(0))  # uint8 wraps
    return val.at[jnp.where(win, row, val.shape[0])].add(
        delta.reshape(b, ROW), mode='drop')


def _probe_slots(hkey: jnp.ndarray, table_size: int) -> jnp.ndarray:
    """[B, N_PROBES] candidate slots."""
    return jnp.stack(
        [fold_hash(hkey, table_size, salt=100 + p) for p in range(N_PROBES)],
        axis=-1,
    )


def _match(st: NetCacheState, hkey: jnp.ndarray) -> jnp.ndarray:
    """int32[B] slot or -1."""
    slots = _probe_slots(hkey, _slots(st))                # [B, P]
    eq = (jnp.all(st.hkeys[slots] == hkey[:, None, :], axis=-1)
          & st.occupied[_cell(slots)])
    hit = jnp.any(eq, axis=-1)
    which = jnp.argmax(eq, axis=-1)
    slot = jnp.take_along_axis(slots, which[:, None], axis=1)[:, 0]
    return jnp.where(hit, slot, -1)


def last_install(slot: jnp.ndarray, install: jnp.ndarray) -> jnp.ndarray:
    """bool[B]: the installing lanes no later installing lane of the same
    slot overrides -- the last masked lane per slot in lane order, the
    order scatter updates apply in (``core.scatter_free.last_writer``).
    Lanes are compared with each other ([B, B]), not with the table."""
    lanes = jnp.arange(slot.shape[0])
    later = ((slot[None, :] == slot[:, None]) & install[None, :]
             & (lanes[None, :] > lanes[:, None]))
    return install & ~jnp.any(later, axis=1)


def netcache_step(st: NetCacheState, pkts: PacketBatch):
    """One batch through the NetCache data plane.

    Returns (state, route, flag, switch_reply_mask, hit_count):
    ``switch_reply_mask`` marks R-REQ lanes answered by the switch.
    """
    op, valid = pkts.op, pkts.valid
    slot = _match(st, pkts.hkey)
    hit = (slot >= 0) & valid
    safe = jnp.where(hit, slot, 0)

    r_req = valid & (op == OP_R_REQ)
    w_req = valid & (op == OP_W_REQ)
    r_rep = valid & (op == OP_R_REP)
    w_rep = valid & (op == OP_W_REP)
    f_rep = valid & (op == OP_F_REP)
    passthru = valid & ((op == OP_CRN_REQ) | (op == OP_F_REQ))

    entry_valid = st.valid[_cell(safe)] & hit
    switch_reply = r_req & hit & entry_valid
    n_hit = jnp.sum(switch_reply.astype(jnp.int32))

    # writes invalidate, then write-through to the server (FLAG=1 if cached)
    w_cached = w_req & hit
    t = _slots(st)
    widx = _cell(jnp.where(w_cached, slot, t))
    valid_arr = st.valid.at[widx].set(False, mode='drop')
    version = st.version.at[widx].add(1, mode='drop')
    flag = jnp.where(w_cached, jnp.int32(1), pkts.flag)

    # write/fetch replies refresh the stored value; of several replies to
    # one slot in a batch the last lane's bytes and length stand
    install = (w_rep | f_rep) & hit & (pkts.flag >= 1)
    win = last_install(slot, install)
    islot = jnp.where(win, slot, t)
    limit = _limit(st)
    valid_arr = valid_arr.at[_cell(islot)].set(True, mode='drop')
    vlen = st.vlen.at[_cell(islot)].set(jnp.minimum(pkts.vlen, limit),
                                         mode='drop')
    val = _put_values(st.val, safe, win, pkts.val[:, :limit])

    route = jnp.full(pkts.width, ROUTE_DROP, jnp.int32)
    to_server = (r_req & ~switch_reply) | w_req | passthru
    to_client = r_rep | w_rep | switch_reply
    route = jnp.where(to_server, ROUTE_SERVER, route)
    route = jnp.where(to_client, ROUTE_CLIENT, route)

    st2 = st._replace(
        valid=valid_arr, version=version, val=val, vlen=vlen,
        hits=sat_add(st.hits, n_hit),
    )
    return st2, route, flag, switch_reply, n_hit


def netcache_install(
    st: NetCacheState,
    keys: np.ndarray,
    vlens: np.ndarray,
    key_size: int,
    value_limit: int,
    key_limit: int = 16,
) -> tuple[NetCacheState, int]:
    """Controller-side preload: install the cacheable subset of ``keys``.

    Enforces the hardware limits: keys longer than ``key_limit`` bytes or
    values longer than ``value_limit`` bytes are refused (the paper's
    motivation: most Twitter/Facebook items exceed these).  Returns the
    number actually installed.  Values are marked invalid until fetched
    (simulated fetch: installed valid with version-0 synthetic bytes, as the
    paper's evaluation preloads the cache before measuring).
    """
    from repro.kvstore.store import synth_value_np

    t, width = _slots(st), _limit(st)
    flat = lambda a, *shape: np.array(a).reshape(t, *shape)  # a host copy
    hkeys, occupied, kidx = flat(st.hkeys, HKEY_LANES), flat(st.occupied), flat(st.kidx)
    valid, val, vlen_arr = flat(st.valid), flat(st.val, width), flat(st.vlen)

    installed = 0
    for k, vl in zip(np.asarray(keys), np.asarray(vlens)):
        if key_size > key_limit or vl > value_limit:
            continue  # uncacheable under NetCache's hardware limits
        hk = hash128_u32_np(np.int32(k))
        placed = False
        for p in range(N_PROBES):
            # host-side twin of fold_hash
            s = int(_fold_np(hk, t, salt=100 + p))
            if not occupied[s] or kidx[s] == k:
                hkeys[s] = hk
                occupied[s] = True
                kidx[s] = k
                valid[s] = True
                v = synth_value_np(int(k), 0, width)
                val[s] = np.where(np.arange(width) < vl, v, 0)
                vlen_arr[s] = vl
                placed = True
                break
        installed += int(placed)
    back = lambda a, like: jnp.asarray(a.reshape(np.shape(like)))
    return st._replace(
        hkeys=jnp.asarray(hkeys), occupied=back(occupied, st.occupied),
        kidx=back(kidx, st.kidx), valid=back(valid, st.valid),
        val=back(val, st.val), vlen=back(vlen_arr, st.vlen),
    ), installed


def _fold_np(hkey: np.ndarray, width: int, salt: int) -> np.int32:
    def sm(x: int) -> int:
        x &= 0xFFFFFFFF
        x ^= x >> 16
        x = (x * 0x7FEB352D) & 0xFFFFFFFF
        x ^= x >> 15
        x = (x * 0x846CA68B) & 0xFFFFFFFF
        x ^= x >> 16
        return x
    h = sm(int(hkey[0]) ^ ((salt * 0x9E3779B9 + 0x85EBCA6B) & 0xFFFFFFFF))
    h = h ^ int(hkey[1]) ^ (int(hkey[2]) >> 7) ^ ((int(hkey[3]) << 3) & 0xFFFFFFFF)
    return np.int32(sm(h) % width)
