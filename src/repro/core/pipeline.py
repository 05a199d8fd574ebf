"""The unified fused switch pipeline: ONE kernel call per subround.

OrbitCache's core claim is that the *entire* per-packet decision — orbit
match, request-table admission, state update, orbit install, the orbit
serving round, egress selection — happens in one switch data-plane pass
(paper §3.3, Fig. 4).  This module is that pass: :func:`subround_pipeline`
runs one ingress batch through the fused ``kernels.subround`` op — a single
``pallas_call`` on the kernel backends covering match, admission + metadata
apply, the state-table invalidate/validate pass, the orbit-line metadata
install, and the serving round (liveness refresh, recirculation-budget
split, front-slot gathers, dequeue).  Everything left outside the kernel is
a pure element-wise reduction over its outputs (routing masks, StepStats
sums, counter accumulation); :func:`window_pipeline` scans the pass over a
window's subrounds.

Value-byte hoisting
-------------------
The serve path reads only ``vlen``/``kidx``/``version`` of an orbit line —
the value payload is never touched between installs.  The per-subround scan
therefore carries :class:`PipelineCarry` (a :class:`SwitchState` whose orbit
buffer is the slim :class:`~repro.core.types.OrbitMeta`), and each subround
emits only its install *winners* (``val_writer``/``val_written`` per line).
:func:`install_window_values` replays the winners once per window — the last
installing subround's last lane wins, exactly the order scatter updates
would have applied in — so the end-of-window ``OrbitBuffer`` is bit-identical
to installing eagerly, while the scan carry shrinks by the whole
``[C*F, value_pad]`` byte buffer.

The free-standing step functions (``switch.switch_step``, ``rt.enqueue``,
``stt.apply_batch``, ``orbit.install_lines_meta``, ``orbit.orbit_pass``)
remain as thin wrappers/oracles for unit tests and kernel parity;
production callers (`kvstore.simulator`, `kvstore.fleet`) go through
:func:`window_pipeline`.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import kernels as kn
from repro.obs import stage

from . import orbit as ob
from .types import (
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
    Counters,
    LookupTable,
    OrbitBuffer,
    OrbitMeta,
    PacketBatch,
    RequestTable,
    StateTable,
    SwitchState,
    sat_add,
)

# ethernet+ip+udp+orbitcache header overhead per cache packet (paper §3.2);
# used for the recirculation-port budget model.
HDR_BYTES = 62


class StepStats(NamedTuple):
    n_r_req: jnp.ndarray       # read requests seen
    n_hit: jnp.ndarray         # cache lookup hits (R-REQ)
    n_enq: jnp.ndarray         # requests buffered in the request table
    n_overflow: jnp.ndarray    # hit but queue full -> server
    n_invalid_fwd: jnp.ndarray # hit but value invalid -> server
    n_w_req: jnp.ndarray       # write requests
    n_w_cached: jnp.ndarray    # writes to cached keys (invalidations)
    n_install: jnp.ndarray     # orbit lines installed (W-REP/F-REP)
    n_served: jnp.ndarray      # requests served by orbit lines
    bytes_served: jnp.ndarray  # value bytes served from orbit this subround
                               # (uint32: doubles the wrap horizon vs int32
                               # and never goes negative; per-subround values
                               # are bounded by C*J*value_pad, but callers
                               # summing long traces must still widen —
                               # e.g. np.sum(..., dtype=np.uint64))
    n_crn: jnp.ndarray         # correction requests (collision resolution)
    n_fwd: jnp.ndarray         # valid packets egressed toward the next tier
                               # down (ROUTE_SERVER): at a ToR that is the
                               # rack's storage servers, at the spine switch
                               # it is the owning rack — the per-tier
                               # forward counter of the fabric topology


class StepOutput(NamedTuple):
    route: jnp.ndarray     # int32[B] ROUTE_* per ingress packet
    flag: jnp.ndarray      # int32[B] possibly updated FLAG field
    grid: ob.ServeGrid     # orbit-served replies this round
    stats: StepStats


class PipelineCarry(NamedTuple):
    """SwitchState minus the orbit value bytes — the per-subround scan carry.

    Field names mirror :class:`SwitchState` so the orbit-pass machinery
    (``refresh_liveness`` / ``orbit_pass``) runs on either unchanged.
    """

    lookup: LookupTable
    state: StateTable
    reqtab: RequestTable
    orbit: OrbitMeta
    counters: Counters


class SubroundOut(NamedTuple):
    """Per-subround egress + the deferred value-install winners."""

    route: jnp.ndarray
    flag: jnp.ndarray
    grid: ob.ServeGrid
    stats: StepStats
    val_writer: jnp.ndarray   # int32[C*F] winning ingress lane per line
    val_written: jnp.ndarray  # bool[C*F]  line installed this subround


def strip_val(sw: SwitchState) -> tuple[PipelineCarry, jnp.ndarray]:
    """Split a SwitchState into the scan carry and the static val buffer."""
    o = sw.orbit
    meta = OrbitMeta(live=o.live, kidx=o.kidx, version=o.version,
                     vlen=o.vlen, frags=o.frags)
    return PipelineCarry(lookup=sw.lookup, state=sw.state, reqtab=sw.reqtab,
                         orbit=meta, counters=sw.counters), o.val


def with_val(carry: PipelineCarry, val: jnp.ndarray) -> SwitchState:
    """Reattach the value buffer after a window's deferred install."""
    m = carry.orbit
    orbit = OrbitBuffer(live=m.live, kidx=m.kidx, version=m.version,
                        vlen=m.vlen, val=val, frags=m.frags)
    return SwitchState(lookup=carry.lookup, state=carry.state,
                       reqtab=carry.reqtab, orbit=orbit,
                       counters=carry.counters)


def subround_pipeline(
    carry: PipelineCarry,
    pkts: PacketBatch,
    recirc_packets: jnp.ndarray,
    max_serves: int,
) -> tuple[PipelineCarry, SubroundOut]:
    """One fused ingress pass + orbit serving round (paper Fig. 4).

    The WHOLE subround is one ``kernels.subround`` call — a single
    ``pallas_call`` on the kernel backends — covering match, request-table
    admission + metadata apply, the state-table pass, the orbit-line
    metadata install and the serving round.  Everything below the kernel
    call is a pure element-wise reduction over its outputs (routing masks,
    StepStats sums, saturating counter accumulation).  Bit-identical to the
    composed seed sequence (``lookup`` + ``enqueue`` + state table +
    ``install_lines`` + ``orbit_pass``) except that value bytes are *not*
    applied — the install winners come back in the output for the
    once-per-window apply.
    """
    op, valid = pkts.op, pkts.valid
    i32 = jnp.int32

    r_req = valid & (op == OP_R_REQ)
    w_req = valid & (op == OP_W_REQ)
    r_rep = valid & (op == OP_R_REP)
    w_rep = valid & (op == OP_W_REP)
    f_rep = valid & (op == OP_F_REP)
    f_req = valid & (op == OP_F_REQ)
    crn = valid & (op == OP_CRN_REQ)

    lk, st, rt_, orb = carry.lookup, carry.state, carry.reqtab, carry.orbit
    k = kn.subround(
        pkts.hkey,
        r_req.astype(i32),                                   # want gate
        w_req.astype(i32),                                   # invalidate gate
        ((w_rep | f_rep) & (pkts.flag >= 1)).astype(i32),    # install gate
        jnp.where(f_rep, pkts.seq, 0),   # F-REP: seq carries fragment number
        jnp.maximum(pkts.flag, 1),       # FLAG carries total fragment count
        pkts.kidx, pkts.vlen, pkts.client, pkts.seq, pkts.port, pkts.ts,
        lk.hkeys, lk.occupied.astype(i32), st.valid.astype(i32), st.version,
        rt_.client, rt_.seq, rt_.port, rt_.ts, rt_.acked, rt_.kidx,
        rt_.qlen, rt_.front, rt_.rear,
        orb.live.astype(i32), orb.kidx, orb.version, orb.vlen, orb.frags,
        recirc_packets,
        queue_size=rt_.queue_size, max_frags=orb.max_frags,
        max_serves=max_serves,
    )

    # ---- pure reductions over the kernel outputs ---------------------------
    hit = (k.hit > 0) & valid
    entry_valid = (k.vhit > 0) & valid
    accepted = k.accepted > 0
    overflow = k.overflow > 0
    r_hit = r_req & hit
    invalid_fwd = r_hit & ~entry_valid
    w_cached = w_req & hit
    install = (w_rep | f_rep) & hit & (pkts.flag >= 1)
    flag_out = jnp.where(w_cached, jnp.int32(1), pkts.flag)

    n_hit = jnp.sum(r_hit.astype(i32))
    n_overflow = jnp.sum(overflow.astype(i32))
    n_invalid_fwd = jnp.sum(invalid_fwd.astype(i32))

    counters = Counters(
        popularity=sat_add(carry.counters.popularity, k.pop),
        hits=sat_add(carry.counters.hits, n_hit),
        overflow=sat_add(carry.counters.overflow, n_overflow + n_invalid_fwd),
        cached_reqs=sat_add(carry.counters.cached_reqs, n_hit),
    )
    carry3 = PipelineCarry(
        lookup=lk,
        state=StateTable(valid=k.st_valid.astype(bool), version=k.st_version),
        reqtab=RequestTable(
            client=k.rt_client, seq=k.rt_seq, port=k.rt_port, ts=k.rt_ts,
            acked=k.rt_acked, kidx=k.rt_kidx,
            qlen=k.qlen, front=k.front, rear=k.rear,
        ),
        orbit=OrbitMeta(live=k.ob_live.astype(bool), kidx=k.ob_kidx,
                        version=k.ob_version, vlen=k.ob_vlen,
                        frags=k.ob_frags),
        counters=counters,
    )

    served = k.served > 0
    grid = ob.ServeGrid(
        served=served,
        client=k.g_client,
        seq=k.g_seq,
        port=k.g_port,
        ts=k.g_ts,
        order=jnp.broadcast_to(jnp.arange(max_serves, dtype=i32)[None, :],
                               served.shape),
        req_kidx=k.g_kidx,
        kidx=k.line_kidx,
        vlen=k.line_vlen,
        version=k.line_version,
    )
    n_served = jnp.sum(served.astype(i32))
    bytes_served = jnp.sum(
        jnp.where(served, grid.vlen[:, None], 0)).astype(jnp.uint32)
    val_writer, val_written = k.val_writer, k.val_written > 0

    # ---- routing ----------------------------------------------------------
    route = jnp.full(pkts.width, ROUTE_DROP, jnp.int32)
    to_server = (
        (r_req & ~hit) | overflow | invalid_fwd | w_req | crn | f_req
    )
    to_client = r_rep | (w_rep & ~install) | (w_rep & install)
    route = jnp.where(to_server & valid, ROUTE_SERVER, route)
    route = jnp.where(to_client & valid, ROUTE_CLIENT, route)
    # accepted R-REQs and F-REPs are absorbed by the switch (ROUTE_DROP)

    stats = StepStats(
        n_r_req=jnp.sum(r_req.astype(jnp.int32)),
        n_hit=n_hit,
        n_enq=jnp.sum(accepted.astype(jnp.int32)),
        n_overflow=n_overflow,
        n_invalid_fwd=n_invalid_fwd,
        n_w_req=jnp.sum(w_req.astype(jnp.int32)),
        n_w_cached=jnp.sum(w_cached.astype(jnp.int32)),
        n_install=jnp.sum(install.astype(jnp.int32)),
        n_served=n_served,
        bytes_served=bytes_served,
        n_crn=jnp.sum(crn.astype(jnp.int32)),
        n_fwd=jnp.sum((to_server & valid).astype(jnp.int32)),
    )
    out = SubroundOut(route=route, flag=flag_out, grid=grid, stats=stats,
                      val_writer=val_writer, val_written=val_written)
    return carry3, out


def install_window_values(
    val: jnp.ndarray,          # uint8[C*F, pad] start-of-window bytes
    batch_val: jnp.ndarray,    # uint8[R, L, pad] ingress values, subround-major
    val_writer: jnp.ndarray,   # int32[R, C*F] per-subround winners
    val_written: jnp.ndarray,  # bool[R, C*F]
) -> jnp.ndarray:
    """Apply a window's orbit value installs in one pass.

    Per line, the winner is the LAST subround that installed it (within a
    subround, the kernel's install reduction already picked the last lane)
    — the order eager scatters would have applied in, so the result is
    bit-identical to installing every subround.

    The apply is a row *scatter* (``.at[].set`` with unwritten lines
    dropped), not a full-buffer ``where`` select: winner lines are distinct
    by construction, so the two are bit-identical, but the scatter lets XLA
    update the donated ``val`` buffer in place inside the window scan —
    untouched ``val`` rows are never rewritten, where the ``where`` form
    read AND wrote the whole ``[C*F, value_pad]`` buffer every window.
    (The gathered update operand ``batch_val[r_star, lane]`` is still a
    dense ``[C*F, value_pad]`` temporary — the win is on the ``val``
    copy/write side, not the gather.)
    """
    with stage("repro.install"):
        r, cf = val_written.shape
        # last subround with an install, per line
        rev = val_written[::-1]
        r_star = (r - 1 - jnp.argmax(rev, axis=0)).astype(jnp.int32)  # [C*F]
        any_w = jnp.any(val_written, axis=0)
        lane = jnp.take_along_axis(val_writer, r_star[None, :], axis=0)[0]
        lines = jnp.where(any_w, jnp.arange(cf, dtype=jnp.int32), cf)
        return val.at[lines].set(batch_val[r_star, lane], mode='drop')


def switch_pipeline(
    sw: SwitchState,
    pkts: PacketBatch,
    recirc_packets: jnp.ndarray,
    max_serves: int,
) -> tuple[SwitchState, StepOutput]:
    """One ingress batch + one orbit serving round, egress included.

    The single-batch entry point (R = 1): fused subround pass, then the
    deferred value install.  ``switch.switch_step`` is a thin alias kept
    for unit tests and examples.
    """
    carry, val = strip_val(sw)
    carry, out = subround_pipeline(carry, pkts, recirc_packets, max_serves)
    val = install_window_values(
        val, pkts.val[None], out.val_writer[None], out.val_written[None])
    return with_val(carry, val), StepOutput(route=out.route, flag=out.flag,
                                            grid=out.grid, stats=out.stats)


def window_pipeline(
    sw: SwitchState,
    sub: PacketBatch,          # subround-major [R, L] ingress
    *,
    recirc_gbps: float,
    window_us: float,
    subrounds: int,
    max_serves: int,
    key_size: int,
) -> tuple[SwitchState, SubroundOut, jnp.ndarray]:
    """One window: scan the fused pass over the subround axis.

    The recirculation budget per subround is the port bandwidth divided by
    the mean live line size (header + key + value fragment), re-evaluated
    from the carry at each subround start — identical to the composed
    path's budget model.  Returns ``(sw', outs, intervals_us)`` with the
    per-subround axis leading in ``outs``/``intervals_us``.
    """
    carry0, val = strip_val(sw)
    window = jnp.float32(window_us)

    def one_subround(pc: PipelineCarry, pk: PacketBatch):
        live = pc.orbit.live
        nlive = jnp.maximum(jnp.sum(live.astype(jnp.int32)), 1)
        mean_line = (
            jnp.sum(jnp.where(live, pc.orbit.vlen, 0)) / nlive
            + HDR_BYTES + key_size
        )
        pps = (recirc_gbps * 1e9 / 8.0) / mean_line
        budget = (pps * window * 1e-6 / subrounds).astype(jnp.int32)
        pc2, out = subround_pipeline(pc, pk, budget, max_serves)
        interval_us = nlive.astype(jnp.float32) / pps * 1e6
        return pc2, (out, interval_us)

    carry, (outs, intervals) = jax.lax.scan(
        one_subround, carry0, sub, unroll=subrounds)
    val = install_window_values(val, sub.val, outs.val_writer,
                                outs.val_written)
    return with_val(carry, val), outs, intervals
