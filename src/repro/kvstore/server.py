"""Emulated storage servers (paper §4, §5.1).

The paper emulates 32 storage servers as partitioned, core-pinned threads
and rate-limits each server's Rx to 100K RPS so the *servers* are the
bottleneck.  Here each server is a FIFO ring buffer drained at
``cap_per_window`` requests per window; arrivals beyond the queue depth are
dropped (open-loop UDP).  Served requests produce replies:

  R-REQ  -> R-REP  (value bytes attached)
  W-REQ  -> W-REP  (paper §3.1: if FLAG says the key is cached, the reply
                    carries the *new value* so the switch can refresh it)
  F-REQ  -> F-REP  (cache-packet fetch; FLAG = fragment count)
  CRN-REQ-> R-REP  (correction: plain read, bypasses the cache)

Each served request emits ``max_frags`` reply lanes; lane f is valid iff
``f < ceil(vlen / value_pad)`` (multi-packet items, paper §3.10).

Servers also run the popularity tracker (count-min sketch + candidates)
over arriving read keys for the periodic top-k report (§3.8).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hashing import hash128_u32
from repro.core.scatter_free import unique_writer
from repro.core.sketch import PopularityTracker, init_tracker, track_fused
from repro.core.types import (
    COUNTER_DTYPE,
    OP_CRN_REQ,
    OP_F_REQ,
    OP_F_REP,
    OP_R_REP,
    OP_R_REQ,
    OP_W_REP,
    OP_W_REQ,
    PacketBatch,
    sat_add,
)
from repro.obs import stage

from .store import synth_value


class ServerConfig(NamedTuple):
    num_servers: int = 32
    queue_depth: int = 64        # per-server FIFO depth (drops beyond)
    cap_per_window: int = 10     # served per window = rate * window
    value_pad: int = 1438
    max_frags: int = 1
    cms_width: int = 2048
    k_candidates: int = 128
    track_popularity: bool = False  # only needed when the controller runs


# Store versions are held tile-aligned: key k < 1024 * (num_keys // 1024)
# at (k // 128, k % 128) of an int32[8 * (num_keys // 1024), 128] block,
# whose (8, 128) tiles are the flat order, so the scan carry and the
# scatter share one layout (a flat or unaligned carry makes XLA copy all
# versions into the scatter's layout and back every window); the last
# num_keys % 1024 keys sit in a flat tail.
_LANES = 128
_TILE = 8 * _LANES


class ServerState(NamedTuple):
    # per-server FIFO ring buffers [n_srv, Q]
    op: jnp.ndarray
    kidx: jnp.ndarray
    seq: jnp.ndarray
    client: jnp.ndarray
    port: jnp.ndarray
    flag: jnp.ndarray
    vlen: jnp.ndarray
    ts: jnp.ndarray
    qlen: jnp.ndarray     # int32[n_srv]
    front: jnp.ndarray    # int32[n_srv]
    rear: jnp.ndarray     # int32[n_srv]
    kv_main: jnp.ndarray  # int32[8 * (num_keys // 1024), 128] store versions
    kv_tail: jnp.ndarray  # int32[num_keys % 1024] versions of the last keys
    tracker: PopularityTracker  # batched: leading dim n_srv
    # lifetime accumulators: COUNTER_DTYPE via sat_add (wrap-safe, like
    # the switch's Counters)
    served: jnp.ndarray   # uint32[n_srv] cumulative
    dropped: jnp.ndarray  # uint32[n_srv] cumulative

    @property
    def key_version(self):
        """int32[..., num_keys] store versions of every key, read-only; a
        host copy (``jax.device_get``) gives a NumPy array."""
        main, tail = self.kv_main, self.kv_tail
        flat = main.reshape(main.shape[:-2] + (-1,))
        xp = np if isinstance(flat, np.ndarray) else jnp
        return xp.concatenate([flat, tail], axis=-1)


def init_servers(cfg: ServerConfig, num_keys: int) -> ServerState:
    n, q = cfg.num_servers, cfg.queue_depth
    zi = lambda: jnp.zeros((n, q), jnp.int32)
    base = init_tracker(cfg.cms_width, cfg.k_candidates)
    tracker = jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), base)
    return ServerState(
        op=zi(), kidx=zi(), seq=zi(), client=zi(), port=zi(), flag=zi(),
        vlen=zi(), ts=jnp.zeros((n, q), jnp.float32),
        qlen=jnp.zeros(n, jnp.int32), front=jnp.zeros(n, jnp.int32),
        rear=jnp.zeros(n, jnp.int32),
        kv_main=jnp.zeros((8 * (num_keys // _TILE), _LANES), jnp.int32),
        kv_tail=jnp.zeros(num_keys % _TILE, jnp.int32),
        tracker=tracker,
        served=jnp.zeros(n, COUNTER_DTYPE),
        dropped=jnp.zeros(n, COUNTER_DTYPE),
    )


class ServerStepOut(NamedTuple):
    replies: PacketBatch          # [n_srv * cap * F]
    served_now: jnp.ndarray       # int32[n_srv]
    dropped_now: jnp.ndarray      # int32[n_srv]
    backlog: jnp.ndarray          # int32[n_srv] queue length after step


@partial(jax.jit, static_argnames=("cfg",))
def server_step(
    st: ServerState,
    cfg: ServerConfig,
    pkts: PacketBatch,
    to_server: jnp.ndarray,   # bool[B] (route == ROUTE_SERVER)
    flag_in: jnp.ndarray,     # int32[B] switch-updated FLAG
    now: jnp.ndarray,         # float32 current time (us)
) -> tuple[ServerState, ServerStepOut]:
    n, q, cap, f = cfg.num_servers, cfg.queue_depth, cfg.cap_per_window, cfg.max_frags
    pad = cfg.value_pad

    # ---- enqueue arrivals (per-server one-hot running offset) -------------
    srv = jnp.where(to_server, pkts.server, 0)
    onehot = (srv[:, None] == jnp.arange(n)[None, :]) & to_server[:, None]
    prior = jnp.cumsum(onehot, axis=0) - onehot
    offset = jnp.take_along_axis(prior, srv[:, None], axis=1)[:, 0]
    free = (q - st.qlen)[srv]
    accepted = to_server & (offset < free)
    dropped_now = jnp.sum((to_server & ~accepted)[:, None] & onehot, axis=0).astype(jnp.int32)

    slot = (st.rear[srv] + offset) % q
    # Scatter-free enqueue: accepted packets land in distinct (server, slot)
    # cells, so each cell's writer is unique.
    writer, written = unique_writer(srv * q + slot, accepted, n * q)
    put = lambda arr, val: jnp.where(written, val[writer],
                                     arr.reshape(-1)).reshape(n, q)
    new_counts = jnp.sum(onehot & accepted[:, None], axis=0).astype(jnp.int32)
    st = st._replace(
        op=put(st.op, pkts.op), kidx=put(st.kidx, pkts.kidx),
        seq=put(st.seq, pkts.seq), client=put(st.client, pkts.client),
        port=put(st.port, pkts.port), flag=put(st.flag, flag_in),
        vlen=put(st.vlen, pkts.vlen), ts=put(st.ts, pkts.ts),
        qlen=st.qlen + new_counts, rear=(st.rear + new_counts) % q,
        dropped=sat_add(st.dropped, dropped_now),
    )

    # ---- popularity tracking on arriving reads (CMS + candidates) ---------
    # Routed through the fused cms_update_query kernel so the server sketch
    # shares the switch's kernel path (backend-dispatched like orbit_match).
    if cfg.track_popularity:
        is_read = accepted & (pkts.op == OP_R_REQ)
        per_srv_mask = onehot & is_read[:, None]          # [B, n]
        def _track(tr, mask_col):
            return track_fused(tr, pkts.kidx, mask_col)
        with stage("repro.sketch"):
            st = st._replace(tracker=jax.vmap(_track)(st.tracker,
                                                      per_srv_mask.T))

    # ---- serve up to cap per server ----------------------------------------
    j = jnp.arange(cap)[None, :]                       # [1, cap]
    n_serve = jnp.minimum(st.qlen, cap)                # [n]
    live = j < n_serve[:, None]                        # [n, cap]
    slot_s = (st.front[:, None] + j) % q               # [n, cap]
    g = lambda arr: jnp.take_along_axis(arr, slot_s, axis=1)
    s_op, s_kidx, s_seq = g(st.op), g(st.kidx), g(st.seq)
    s_client, s_port, s_flag = g(st.client), g(st.port), g(st.flag)
    s_vlen, s_ts = g(st.vlen), g(st.ts)

    # write versions bump before value generation
    w_mask = live & (s_op == OP_W_REQ)
    with stage("repro.key_version"):
        # Each part is bumped in place, the main block by (row, lane)
        # indices (a scatter into its flat view is relaid out again); a
        # lane that writes no key of a part is sent past that part's end
        # and dropped.
        main, tail = st.kv_main, st.kv_tail
        split, k, wm = main.size, s_kidx.reshape(-1), w_mask.reshape(-1)
        if split:
            r = jnp.where(wm & (k < split), k // _LANES, main.shape[0])
            main = main.at[r, k % _LANES].add(1, mode="drop")
            kc = jnp.minimum(s_kidx, split - 1)
            version = main[kc // _LANES, kc % _LANES]
        if tail.size:
            tail = tail.at[jnp.where(wm & (k >= split), k - split, tail.size)
                           ].add(1, mode="drop")
            from_tail = tail[jnp.clip(s_kidx - split, 0, tail.size - 1)]
            version = (jnp.where(s_kidx < split, version, from_tail)
                       if split else from_tail)         # [n, cap]

    # reply op + FLAG (fragment count where a value is attached)
    true_vlen = s_vlen                                  # set by client from workload
    n_frags = jnp.clip((true_vlen + pad - 1) // pad, 1, f)
    rep_op = jnp.select(
        [s_op == OP_R_REQ, s_op == OP_W_REQ, s_op == OP_F_REQ, s_op == OP_CRN_REQ],
        [OP_R_REP, OP_W_REP, OP_F_REP, OP_R_REP],
        OP_R_REP,
    ).astype(jnp.int32)  # strong dtype: a weak one retraces the next chunk
    carries_val = (s_op == OP_R_REQ) | (s_op == OP_CRN_REQ) | (s_op == OP_F_REQ) | \
                  ((s_op == OP_W_REQ) & (s_flag >= 1))
    rep_flag = jnp.where(
        (s_op == OP_F_REQ) | ((s_op == OP_W_REQ) & (s_flag >= 1)), n_frags, 0
    )

    # ---- emit [n, cap, F] reply lanes --------------------------------------
    frag = jnp.arange(f)[None, None, :]                        # [1,1,F]
    lane_valid = live[:, :, None] & (frag < jnp.where(carries_val, n_frags, 1)[:, :, None])
    frag_off = frag * pad
    frag_vlen = jnp.clip(true_vlen[:, :, None] - frag_off, 0, pad)
    val = synth_value(
        jnp.broadcast_to(s_kidx[:, :, None], (n, cap, f)),
        jnp.broadcast_to(version[:, :, None], (n, cap, f)),
        pad,
        offset=jnp.broadcast_to(frag_off, (n, cap, f)),
    )
    val = jnp.where(
        (jnp.arange(pad)[None, None, None, :] < frag_vlen[..., None]) & carries_val[:, :, None, None],
        val, 0,
    )

    def fl(x):  # flatten [n, cap, F] -> [n*cap*F]
        return jnp.broadcast_to(x, (n, cap, f)).reshape(-1)

    flat_kidx = fl(s_kidx[:, :, None])
    replies = PacketBatch(
        op=fl(rep_op[:, :, None]),
        seq=jnp.where(fl(rep_op[:, :, None]) == OP_F_REP, fl(frag), fl(s_seq[:, :, None])),
        hkey=hash128_u32(flat_kidx),
        flag=fl(rep_flag[:, :, None]),
        kidx=flat_kidx,
        vlen=jnp.where(fl(carries_val[:, :, None]), fl(frag_vlen), 0),
        client=fl(s_client[:, :, None]),
        port=fl(frag),  # reply lanes carry the fragment index in ``port``

        server=fl(jnp.broadcast_to(jnp.arange(n)[:, None, None], (n, cap, f))),
        ts=fl(s_ts[:, :, None].astype(jnp.float32)),
        valid=fl(lane_valid),
        val=val.reshape(n * cap * f, pad),
    )

    served_now = n_serve
    st = st._replace(
        qlen=st.qlen - n_serve,
        front=(st.front + n_serve) % q,
        kv_main=main, kv_tail=tail,
        served=sat_add(st.served, served_now),
    )
    return st, ServerStepOut(
        replies=replies, served_now=served_now, dropped_now=dropped_now,
        backlog=st.qlen,
    )


def server_reports_traced(st: ServerState, k: int,
                          ) -> tuple[ServerState, jnp.ndarray, jnp.ndarray]:
    """Per-server top-k report + tracker reset (paper §3.8), fully traced.

    Returns ``(st', top_kidx int32[n_srv, k], top_est int32[n_srv, k])`` —
    the jit/vmap form the in-scan controller consumes; the host-side
    :func:`server_reports` is a thin wrapper over it, so both paths share
    one ranking."""
    from repro.core.sketch import report_and_reset
    def _rep(tr):
        return report_and_reset(tr, k)
    fresh, top_k, top_e = jax.vmap(_rep)(st.tracker)
    return st._replace(tracker=fresh), top_k, top_e


def server_reports(st: ServerState, k: int):
    """Host-side: per-server top-k report + tracker reset (paper §3.8)."""
    st2, top_k, top_e = server_reports_traced(st, k)
    import numpy as np
    reports = [
        (np.asarray(top_k[s]), np.asarray(top_e[s]))
        for s in range(top_k.shape[0])
    ]
    return st2, reports
