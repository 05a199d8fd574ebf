"""Clients (paper §4: open-loop VMA application; §3.6 collision resolution).

Open-loop request generation: the number of requests per window is Poisson
(exponential inter-arrival gaps, as in the paper's client app).  Each
client keeps a list of not-yet-answered requests indexed by SEQ; on a read
reply it compares the *returned key* with the *requested key* — if they
differ (hash collision, or CacheIdx inheritance after a cache update,
paper §3.8) it issues a CRN-REQ so the storage server supplies the correct
value.

Latency is tracked in quarter-octave log histograms, separately for
switch-served and server-served requests (the paper's prototype adds
Cached/Latency header fields for exactly this measurement).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.hashing import hash128_u32, server_of_key
from repro.core.scatter_free import unique_writer
from repro.core.types import (
    COUNTER_DTYPE,
    OP_CRN_REQ,
    OP_R_REP,
    OP_R_REQ,
    OP_W_REP,
    OP_W_REQ,
    PacketBatch,
    empty_batch,
    sat_add,
)

from .workload import WorkloadArrays

LAT_BUCKETS = 80
_LAT_BASE_US = 0.25  # bucket 0 lower edge


def lat_bucket(lat_us: jnp.ndarray) -> jnp.ndarray:
    """Quarter-octave log bucket index."""
    x = jnp.maximum(lat_us, _LAT_BASE_US) / _LAT_BASE_US
    return jnp.clip((4.0 * jnp.log2(x)).astype(jnp.int32), 0, LAT_BUCKETS - 1)


def bucket_edges_us() -> jnp.ndarray:
    import numpy as np
    return _LAT_BASE_US * (2.0 ** (np.arange(LAT_BUCKETS + 1) / 4.0))


def _bucket_counts(bucket: jnp.ndarray) -> jnp.ndarray:
    """int32[LAT_BUCKETS] histogram increments (scatter-free one-hot sum;
    lanes with ``bucket == LAT_BUCKETS`` are dropped)."""
    oh = bucket[:, None] == jnp.arange(LAT_BUCKETS)[None, :]
    return jnp.sum(oh.astype(jnp.int32), axis=0)


class ClientConfig(NamedTuple):
    batch: int = 512            # request lanes per window
    num_clients: int = 4        # paper testbed: 4 client nodes
    crn_width: int = 64         # correction-request lanes per window
    base_rtt_us: float = 2.0    # wire+NIC baseline
    value_pad: int = 1438
    subrounds: int = 1          # pipeline subrounds per window (batch layout)


class ClientState(NamedTuple):
    """Per-fleet client bookkeeping.

    The lifetime accumulators (``hist_*``, ``rx_*``, ``tx``,
    ``mismatches``) run for the whole simulation and therefore live in
    :data:`~repro.core.types.COUNTER_DTYPE` updated via
    :func:`~repro.core.types.sat_add` — same wrap-safety rule as the
    switch's ``Counters``.  ``next_seq``/``crn_*`` are transient window
    state and stay int32.
    """

    next_seq: jnp.ndarray     # int32[]
    crn_kidx: jnp.ndarray     # int32[crn_width] pending corrections
    crn_n: jnp.ndarray        # int32[]
    hist_switch: jnp.ndarray  # uint32[LAT_BUCKETS]
    hist_server: jnp.ndarray  # uint32[LAT_BUCKETS]
    rx_switch: jnp.ndarray    # uint32[] replies served by the switch cache
    rx_server: jnp.ndarray    # uint32[] replies served by storage servers
    tx: jnp.ndarray           # uint32[] requests issued
    mismatches: jnp.ndarray   # uint32[] wrong-key replies detected (-> CRN)


def init_clients(cfg: ClientConfig) -> ClientState:
    return ClientState(
        next_seq=jnp.zeros((), jnp.int32),
        crn_kidx=jnp.full((cfg.crn_width,), -1, jnp.int32),
        crn_n=jnp.zeros((), jnp.int32),
        hist_switch=jnp.zeros((LAT_BUCKETS,), COUNTER_DTYPE),
        hist_server=jnp.zeros((LAT_BUCKETS,), COUNTER_DTYPE),
        rx_switch=jnp.zeros((), COUNTER_DTYPE),
        rx_server=jnp.zeros((), COUNTER_DTYPE),
        tx=jnp.zeros((), COUNTER_DTYPE),
        mismatches=jnp.zeros((), COUNTER_DTYPE),
    )


def generate(
    st: ClientState,
    cfg: ClientConfig,
    rng: jax.Array,
    wl: WorkloadArrays,        # Zipf sampler, rank -> kidx, kidx -> bytes
    offered_per_window: jnp.ndarray,  # float: lambda
    write_ratio: jnp.ndarray,
    num_servers: int,
    now: jnp.ndarray,          # float32 us
) -> tuple[ClientState, PacketBatch]:
    """One window of open-loop request generation (+ pending CRN drain).

    The batch is emitted **subround-major**: shape ``[R, L]`` where row ``r``
    holds the lanes the switch pipeline sees in subround ``r`` (logical lane
    ``j * R + r`` — arrivals spread over the window like real packet
    interleaving; a contiguous split would slam the whole window's burst
    into one pipeline pass and overflow the 8-deep request queues).  With
    ``subrounds == 1`` this degenerates to the flat ``[1, B]`` batch.
    """
    b = cfg.batch
    r_sub = cfg.subrounds
    if b % r_sub or cfg.crn_width % r_sub:
        raise ValueError(
            f"client batch ({b}) and crn_width ({cfg.crn_width}) must be "
            f"multiples of subrounds ({r_sub})")
    lc = b // r_sub
    r1, r2, r3 = jax.random.split(rng, 3)
    n = jnp.minimum(jax.random.poisson(r1, offered_per_window), b).astype(jnp.int32)
    # lane[r, j] = j * R + r: the logical (arrival-order) lane id
    lane = (jnp.arange(lc, dtype=jnp.int32)[None, :] * r_sub
            + jnp.arange(r_sub, dtype=jnp.int32)[:, None])
    valid = lane < n

    def ilv(x):  # flat [W, ...] -> [R, W // R, ...] in lane order
        return x.reshape((x.shape[0] // r_sub, r_sub) + x.shape[1:]).swapaxes(0, 1)

    u = ilv(jax.random.uniform(r2, (b,), jnp.float32))
    kidx = wl.perm[jnp.clip(wl.ranks(u), 0, wl.perm.shape[0] - 1)]
    is_write = ilv(jax.random.uniform(r3, (b,), jnp.float32)) < write_ratio
    seq = st.next_seq + lane
    op = jnp.where(is_write, OP_W_REQ, OP_R_REQ)

    pk = PacketBatch(
        op=jnp.where(valid, op, 7),
        seq=seq,
        hkey=hash128_u32(kidx),
        flag=jnp.zeros((r_sub, lc), jnp.int32),
        kidx=kidx,
        vlen=wl.vlen[kidx],
        client=seq % cfg.num_clients,
        port=jnp.zeros((r_sub, lc), jnp.int32),
        server=server_of_key(kidx, num_servers),
        ts=jnp.full((r_sub, lc), now, jnp.float32),
        valid=valid,
        val=jnp.zeros((r_sub, lc, cfg.value_pad), jnp.uint8),
    )

    # pending correction requests ride along in dedicated lanes
    lcrn = cfg.crn_width // r_sub
    crn_lane = (jnp.arange(lcrn, dtype=jnp.int32)[None, :] * r_sub
                + jnp.arange(r_sub, dtype=jnp.int32)[:, None])
    crn_valid = crn_lane < st.crn_n
    crn_kidx = jnp.where(crn_valid, ilv(st.crn_kidx), 0)
    crn_seq = st.next_seq + b + crn_lane
    crn = PacketBatch(
        op=jnp.where(crn_valid, OP_CRN_REQ, 7),
        seq=crn_seq,
        hkey=hash128_u32(crn_kidx),
        flag=jnp.zeros((r_sub, lcrn), jnp.int32),
        kidx=crn_kidx,
        vlen=wl.vlen[crn_kidx],
        client=crn_seq % cfg.num_clients,
        port=jnp.zeros((r_sub, lcrn), jnp.int32),
        server=server_of_key(crn_kidx, num_servers),
        ts=jnp.full((r_sub, lcrn), now, jnp.float32),
        valid=crn_valid,
        val=jnp.zeros((r_sub, lcrn, cfg.value_pad), jnp.uint8),
    )
    st = st._replace(
        next_seq=st.next_seq + b + cfg.crn_width,
        crn_kidx=jnp.full((cfg.crn_width,), -1, jnp.int32),
        crn_n=jnp.zeros((), jnp.int32),
        tx=sat_add(st.tx, n),
    )
    batch = jax.tree.map(lambda a, c: jnp.concatenate([a, c], axis=1), pk, crn)
    return st, batch


def account_switch_served(
    st: ClientState,
    cfg: ClientConfig,
    served: jnp.ndarray,     # bool[C, J]
    req_kidx: jnp.ndarray,   # int32[C, J] key each served request asked for
    ts: jnp.ndarray,         # float32[C, J]
    line_kidx: jnp.ndarray,  # int32[C] key carried by the serving orbit line
    serve_time: jnp.ndarray, # float32[C, J] absolute time of service
) -> ClientState:
    """Account orbit-served replies; detect wrong-key serves -> CRN queue.

    The requested-vs-returned comparison is the paper's client-side
    collision check; ``req_kidx`` (recorded with the queued request
    metadata) is the simulator's stand-in for the client's own record of
    what each SEQ asked for.
    """
    lat = jnp.maximum(serve_time - ts, 0.05) + cfg.base_rtt_us
    bucket = jnp.where(served, lat_bucket(lat), LAT_BUCKETS)
    hist = sat_add(st.hist_switch, _bucket_counts(bucket.reshape(-1)))
    n_served = jnp.sum(served.astype(jnp.int32))

    expected = req_kidx
    mism = served & (expected != line_kidx[:, None])
    n_mism = jnp.sum(mism.astype(jnp.int32))
    # append mismatched (expected) keys to the CRN buffer, scatter-free:
    # mismatches claim consecutive (distinct) buffer slots.
    flat_m = mism.reshape(-1)
    order = jnp.cumsum(flat_m.astype(jnp.int32)) - flat_m.astype(jnp.int32)
    dest = jnp.where(flat_m, st.crn_n + order, cfg.crn_width)
    writer, written = unique_writer(dest, flat_m, cfg.crn_width)
    exp_flat = jnp.broadcast_to(expected, mism.shape).reshape(-1)
    crn_kidx = jnp.where(written, exp_flat[writer], st.crn_kidx)
    crn_n = jnp.minimum(st.crn_n + n_mism, cfg.crn_width)
    return st._replace(
        hist_switch=hist,
        rx_switch=sat_add(st.rx_switch, n_served),
        mismatches=sat_add(st.mismatches, n_mism),
        crn_kidx=crn_kidx,
        crn_n=crn_n,
    )


def account_server_replies(
    st: ClientState,
    cfg: ClientConfig,
    pkts: PacketBatch,
    to_client: jnp.ndarray,  # bool[B]
    now: jnp.ndarray,
) -> ClientState:
    """Account replies forwarded from storage servers (R-REP / W-REP).

    Multi-fragment replies count once (fragment 0 — ``port`` carries the
    fragment index on reply lanes).
    """
    is_rep = to_client & ((pkts.op == OP_R_REP) | (pkts.op == OP_W_REP)) & (pkts.port == 0)
    lat = jnp.maximum(now - pkts.ts, 0.05) + cfg.base_rtt_us
    bucket = jnp.where(is_rep, lat_bucket(lat), LAT_BUCKETS)
    hist = sat_add(st.hist_server, _bucket_counts(bucket))
    return st._replace(
        hist_server=hist,
        rx_server=sat_add(st.rx_server, jnp.sum(is_rep.astype(jnp.int32))),
    )
