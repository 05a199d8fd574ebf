"""Distributed orbit ring: OrbitCache's recirculation, TPU-native.

A TPU pod has no centralized line-rate switch, so the "switch data plane"
is distributed across devices and the recirculation port becomes the ICI
ring: cache lines — self-contained (key, version, value) records, the
moral equivalent of the paper's cache packets — hop device → device via
``jax.lax.ppermute`` every step.  Each device keeps

  * a replica of the (small) lookup + state tables — match-action state,
  * its *local* circular-queue request table — requests submitted by work
    local to that device wait there,
  * the slice of orbit lines currently visiting it.

One revolution visits every device's request table, so any queued request
is served within ≤ D hops; as in the paper, requests are never forwarded
around the ring — only the small, constant set of cache lines moves.
Cloning (PRE) becomes "serve up to ``clones_per_visit`` queued requests
per visiting line without consuming it".

This module is pure per-device dataplane logic designed to run under
``shard_map``; ``make_ring_step`` binds it to a mesh.  The key-value
*storage* behind it is sharded separately (see
``repro.serving.orbit_service``).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import lookup as lk
from . import request_table as rt
from .types import (
    COUNTER_DTYPE,
    OP_R_REQ,
    OP_W_REQ,
    LookupTable,
    PacketBatch,
    RequestTable,
    StateTable,
    sat_add,
)


class OrbitSlice(NamedTuple):
    """Orbit lines currently resident on this device (local view)."""

    live: jnp.ndarray     # bool[L]
    cidx: jnp.ndarray     # int32[L] cache entry carried (-1 dead)
    kidx: jnp.ndarray     # int32[L]
    version: jnp.ndarray  # int32[L]
    vlen: jnp.ndarray     # int32[L]
    val: jnp.ndarray      # uint8[L, value_pad]


class RingState(NamedTuple):
    lookup: LookupTable   # replicated match-action tables
    state: StateTable
    reqtab: RequestTable  # local request queues
    slice: OrbitSlice     # resident orbit lines
    popularity: jnp.ndarray  # uint32[C] local popularity counters
    overflow: jnp.ndarray    # uint32[] local overflow count (sat_add)
    hits: jnp.ndarray        # uint32[] (sat_add)


def init_ring_state(
    num_entries: int,
    queue_size: int,
    slice_len: int,
    value_pad: int,
) -> RingState:
    c, s, l = num_entries, queue_size, slice_len
    return RingState(
        lookup=LookupTable(
            hkeys=jnp.zeros((c, 4), jnp.uint32),
            occupied=jnp.zeros((c,), bool),
            kidx=jnp.full((c,), -1, jnp.int32),
        ),
        state=StateTable(valid=jnp.zeros((c,), bool),
                         version=jnp.zeros((c,), jnp.int32)),
        reqtab=RequestTable(
            client=jnp.full((c * s,), -1, jnp.int32),
            seq=jnp.zeros((c * s,), jnp.int32),
            port=jnp.zeros((c * s,), jnp.int32),
            ts=jnp.zeros((c * s,), jnp.float32),
            acked=jnp.zeros((c * s,), jnp.int32),
            kidx=jnp.full((c * s,), -1, jnp.int32),
            qlen=jnp.zeros((c,), jnp.int32),
            front=jnp.zeros((c,), jnp.int32),
            rear=jnp.zeros((c,), jnp.int32),
        ),
        slice=OrbitSlice(
            live=jnp.zeros((l,), bool),
            cidx=jnp.full((l,), -1, jnp.int32),
            kidx=jnp.full((l,), -1, jnp.int32),
            version=jnp.zeros((l,), jnp.int32),
            vlen=jnp.zeros((l,), jnp.int32),
            val=jnp.zeros((l, value_pad), jnp.uint8),
        ),
        # running counters: wrap-safe dtype, accumulated via sat_add (same
        # rationale as SwitchState's Counters — see types.sat_add)
        popularity=jnp.zeros((c,), COUNTER_DTYPE),
        overflow=jnp.zeros((), COUNTER_DTYPE),
        hits=jnp.zeros((), COUNTER_DTYPE),
    )


class RingServe(NamedTuple):
    """Replies produced on this device this step."""

    served: jnp.ndarray   # bool[C, J]
    client: jnp.ndarray   # int32[C, J]
    seq: jnp.ndarray      # int32[C, J]
    ts: jnp.ndarray       # float32[C, J]
    kidx: jnp.ndarray     # int32[C] carried key per entry
    vlen: jnp.ndarray     # int32[C]
    val: jnp.ndarray      # uint8[C, value_pad] value of the visiting line
    miss: jnp.ndarray     # bool[B] request missed the cache (route to shard)


def _slice_liveness(st: RingState) -> OrbitSlice:
    """Drop-stale rule, local: entry evicted / invalid / version behind."""
    sl = st.slice
    c = st.lookup.occupied.shape[0]
    safe = jnp.clip(sl.cidx, 0, c - 1)
    ok = (
        sl.live
        & (sl.cidx >= 0)
        & st.lookup.occupied[safe]
        & st.state.valid[safe]
        & (sl.version == st.state.version[safe])
    )
    return sl._replace(live=ok)


def ring_step(
    st: RingState,
    pkts: PacketBatch,
    clones_per_visit: int,
    axis_name,
) -> tuple[RingState, RingServe]:
    """One device-local dataplane step + ring rotation (call under shard_map).

    1. match local requests; enqueue hits, count misses/overflow;
    2. visiting lines serve up to ``clones_per_visit`` queued requests each;
    3. rotate the slice to the next ring position.
    """
    c = st.lookup.occupied.shape[0]
    valid = pkts.valid
    cidx = lk.lookup(st.lookup, pkts.hkey)
    r_req = valid & (pkts.op == OP_R_REQ)
    hit = r_req & (cidx >= 0)
    safe_cidx = jnp.where(hit, cidx, 0)
    entry_valid = st.state.valid[safe_cidx] & hit

    enq = rt.enqueue(st.reqtab, cidx, hit & entry_valid,
                     pkts.client, pkts.seq, pkts.port, pkts.ts)
    miss = (r_req & ~hit) | (hit & ~entry_valid) | enq.overflow | \
           (valid & (pkts.op == OP_W_REQ))

    pop = st.popularity.at[jnp.where(hit, cidx, c)].add(1, mode='drop')
    n_hit = jnp.sum(hit.astype(jnp.int32))
    n_ovf = jnp.sum(enq.overflow.astype(jnp.int32))

    # ---- serve with resident lines -----------------------------------------
    sl = _slice_liveness(st._replace(reqtab=enq.table))
    # per-entry serve budget: clones_per_visit per live resident line
    budget = jnp.zeros((c,), jnp.int32).at[
        jnp.where(sl.live, sl.cidx, c)
    ].add(clones_per_visit, mode='drop')
    deq = rt.peek_front(enq.table, budget, clones_per_visit)
    n_served = jnp.sum(deq.served.astype(jnp.int32), axis=1)
    reqtab = rt.pop(enq.table, n_served)

    # entry -> resident line (for value payload); dead entries serve nothing
    line_of = jnp.full((c,), -1, jnp.int32).at[
        jnp.where(sl.live, sl.cidx, c)
    ].set(jnp.arange(sl.live.shape[0], dtype=jnp.int32), mode='drop')
    safe_line = jnp.clip(line_of, 0, sl.live.shape[0] - 1)
    serve = RingServe(
        served=deq.served,
        client=deq.client,
        seq=deq.seq,
        ts=deq.ts,
        kidx=jnp.where(line_of >= 0, sl.kidx[safe_line], -1),
        vlen=jnp.where(line_of >= 0, sl.vlen[safe_line], 0),
        val=jnp.where((line_of >= 0)[:, None], sl.val[safe_line], 0),
        miss=miss,
    )

    # ---- rotate the slice to the next ring position -------------------------
    ax = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    d = 1
    for a in ax:
        d *= jax.lax.axis_size(a)
    perm = [(i, (i + 1) % d) for i in range(d)]
    rotated = jax.tree.map(
        lambda x: jax.lax.ppermute(x, ax if len(ax) > 1 else ax[0], perm), sl
    )

    st2 = st._replace(
        reqtab=reqtab,
        slice=rotated,
        popularity=pop,
        overflow=sat_add(st.overflow, n_ovf),
        hits=sat_add(st.hits, n_hit),
    )
    return st2, serve


def install_into_slice(
    sl: OrbitSlice,
    cidx: jnp.ndarray,    # int32[B]
    mask: jnp.ndarray,    # bool[B]
    kidx: jnp.ndarray,
    version: jnp.ndarray,
    vlen: jnp.ndarray,
    val: jnp.ndarray,
) -> OrbitSlice:
    """Install fresh lines into locally free slots (F-REP arrival device).

    Packets claim dead slots in order; packets beyond the free-slot count
    are dropped (callers size ``slice_len`` with headroom).
    """
    l = sl.live.shape[0]
    dead_rank = jnp.cumsum((~sl.live).astype(jnp.int32)) - (~sl.live).astype(jnp.int32)
    # slot index of the k-th dead slot
    order = jnp.argsort(sl.live.astype(jnp.int32), stable=True)  # dead first
    want_rank = jnp.cumsum(mask.astype(jnp.int32)) - mask.astype(jnp.int32)
    n_dead = jnp.sum((~sl.live).astype(jnp.int32))
    ok = mask & (want_rank < n_dead)
    dest = jnp.where(ok, order[jnp.clip(want_rank, 0, l - 1)], l)
    del dead_rank
    return OrbitSlice(
        live=sl.live.at[dest].set(True, mode='drop'),
        cidx=sl.cidx.at[dest].set(cidx, mode='drop'),
        kidx=sl.kidx.at[dest].set(kidx, mode='drop'),
        version=sl.version.at[dest].set(version, mode='drop'),
        vlen=sl.vlen.at[dest].set(vlen, mode='drop'),
        val=sl.val.at[dest].set(val, mode='drop'),
    )


def make_ring_step(mesh, axis_names, clones_per_visit: int = 4):
    """Bind ``ring_step`` to a mesh with shard_map.

    The ring spans ``axis_names`` (e.g. ``('data',)`` single-pod or
    ``('pod', 'data')`` across pods); lookup/state tables are replicated,
    request tables and packet batches are per-ring-position.
    """
    ax = axis_names if isinstance(axis_names, tuple) else (axis_names,)
    ring_spec = P(ax)

    state_specs = RingState(
        lookup=LookupTable(hkeys=P(), occupied=P(), kidx=P()),
        state=StateTable(valid=P(), version=P()),
        reqtab=RequestTable(*([ring_spec] * len(RequestTable._fields))),
        slice=OrbitSlice(*([ring_spec] * len(OrbitSlice._fields))),
        popularity=ring_spec,
        overflow=ring_spec,
        hits=ring_spec,
    )
    pkt_spec = PacketBatch(*([ring_spec] * len(PacketBatch._fields)))
    serve_specs = RingServe(*([ring_spec] * 8))

    # shard_map hands each device its *block* with the sharded (ring) axis
    # still present as a leading dim of size 1; squeeze/unsqueeze around the
    # per-device core step.
    # The replication check is off: it cannot see through the manual
    # squeeze/unsqueeze of the ring axis.
    @partial(jax.shard_map, mesh=mesh,
             in_specs=(state_specs, pkt_spec),
             out_specs=(state_specs, serve_specs), check_vma=False)
    def step2(st: RingState, pkts: PacketBatch):
        def squeeze(spec, x):
            return x.reshape(x.shape[1:]) if spec == ring_spec else x
        def unsqueeze(spec, x):
            return x.reshape((1,) + x.shape) if spec == ring_spec else x
        st_l = jax.tree.map(squeeze, state_specs, st)
        pk_l = jax.tree.map(squeeze, pkt_spec, pkts)
        st2, serve = ring_step(st_l, pk_l, clones_per_visit, ax)
        st2 = jax.tree.map(unsqueeze, state_specs, st2)
        serve = jax.tree.map(unsqueeze, serve_specs, serve)
        return st2, serve

    return step2
