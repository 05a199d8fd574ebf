"""Public wrapper for the subround kernel: packs the public flat layout
into the kernel's 2-D blocks (batch and table padded to tile alignment)
and unpacks the results."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import kernel as k
from .ref import subround_ref  # noqa: F401  (oracle)

class SubroundOuts(NamedTuple):
    """Outputs of the full fused subround op (all call-time-state shapes).

    Per-lane decisions come back for routing/stats (pure reductions in
    ``core.pipeline``); every switch table returns fully updated — admission
    metadata applied, state bits resolved, orbit lines installed and
    liveness-refreshed, served front slots popped; the serve grid carries
    the requests answered by orbit lines this round; ``val_writer`` /
    ``val_written`` are the deferred value-byte install winners.
    """

    hit: jnp.ndarray          # int32[B]
    vhit: jnp.ndarray         # int32[B]
    accepted: jnp.ndarray     # int32[B]
    overflow: jnp.ndarray     # int32[B]
    pop: jnp.ndarray          # int32[C]
    st_valid: jnp.ndarray     # int32[C]
    st_version: jnp.ndarray   # int32[C]
    rt_client: jnp.ndarray    # int32[C*S]
    rt_seq: jnp.ndarray       # int32[C*S]
    rt_port: jnp.ndarray      # int32[C*S]
    rt_ts: jnp.ndarray        # float32[C*S]
    rt_acked: jnp.ndarray     # int32[C*S]
    rt_kidx: jnp.ndarray      # int32[C*S]
    qlen: jnp.ndarray         # int32[C]
    front: jnp.ndarray        # int32[C]
    rear: jnp.ndarray         # int32[C]
    ob_live: jnp.ndarray      # int32[C*F]
    ob_kidx: jnp.ndarray      # int32[C*F]
    ob_version: jnp.ndarray   # int32[C*F]
    ob_vlen: jnp.ndarray      # int32[C*F]
    ob_frags: jnp.ndarray     # int32[C]
    val_writer: jnp.ndarray   # int32[C*F]
    val_written: jnp.ndarray  # int32[C*F]
    served: jnp.ndarray       # int32[C, J]
    g_client: jnp.ndarray     # int32[C, J]
    g_seq: jnp.ndarray        # int32[C, J]
    g_port: jnp.ndarray       # int32[C, J]
    g_ts: jnp.ndarray         # float32[C, J]
    g_kidx: jnp.ndarray       # int32[C, J]
    line_kidx: jnp.ndarray    # int32[C]
    line_vlen: jnp.ndarray    # int32[C]
    line_version: jnp.ndarray # int32[C]


def subround(
    hkey, want, wreq, inst, frag, nfrags, kidx, vlen, client, seq, port, ts,
    table_hkeys, occupied, st_valid, st_version,
    rt_client, rt_seq, rt_port, rt_ts, rt_acked, rt_kidx, qlen, front, rear,
    ob_live, ob_kidx, ob_version, ob_vlen, ob_frags,
    budget,
    queue_size: int, max_frags: int, max_serves: int,
    *, block_b: int = 128, interpret: bool,
) -> SubroundOuts:
    """Padded public wrapper for the full subround kernel.  Any B, any C.

    Pad lanes carry zeroed gate masks (no admission / state / install
    contribution) and pad entries are unoccupied with empty queues and no
    live lines, so neither can perturb the accumulators, the liveness
    count, or the per-entry serve budget; results are sliced back to the
    caller's shapes.
    """
    b = hkey.shape[0]
    c = table_hkeys.shape[0]
    s, f, j = queue_size, max_frags, max_serves
    i32 = jnp.int32
    bits = lambda a: jax.lax.bitcast_convert_type(a, i32)
    block_b = min(block_b, max(8, b))
    pad_b = (-b) % block_b
    pad_c = (-c) % 128

    # per-lane columns -> [B, LANE_COLS]
    cols = [bits(hkey)[:, w] for w in range(4)] + [
        want, wreq, inst, frag, nfrags, kidx, vlen, client, seq, port,
        bits(ts)]
    lanes = jnp.pad(jnp.stack([a.astype(i32) for a in cols], axis=1),
                    ((0, pad_b), (0, 0)))
    # per-entry tables, entry-minor: [rows, C] / [fields, S or F, C]
    pad_e = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad_c)])
    minor = lambda a, w: a.reshape(c, w).T
    thk_t = pad_e(bits(table_hkeys).T)
    ent = pad_e(jnp.stack([a.astype(i32) for a in (
        occupied, st_valid, st_version, qlen, front, rear, ob_frags)]))
    rt = pad_e(jnp.stack([minor(a, s) for a in (
        rt_client, rt_seq, rt_port, bits(rt_ts), rt_acked, rt_kidx)]))
    ob = pad_e(jnp.stack([minor(a.astype(i32), f) for a in (
        ob_live, ob_kidx, ob_version, ob_vlen)]))

    lane_o, ent_o, rt_o, ob_o, grid_o = k.subround(
        lanes, thk_t, ent, rt, ob, jnp.asarray(budget, i32).reshape(1, 1),
        queue_size=s, max_frags=f, max_serves=j,
        block_b=block_b, interpret=interpret)

    f32 = lambda a: jax.lax.bitcast_convert_type(a, jnp.float32)
    lane_out = lambda col: lane_o[:b, col]
    ent_out = lambda row: ent_o[row, :c]
    flat = lambda a: a[:, :c].T.reshape(-1)        # [W, C] -> [C * W]
    grid = lambda fld: grid_o[fld, :, :c].T        # [J, C] -> [C, J]
    return SubroundOuts(
        hit=lane_out(k.O_HIT), vhit=lane_out(k.O_VHIT),
        accepted=lane_out(k.O_ACC), overflow=lane_out(k.O_OVF),
        pop=ent_out(k.E_POP), st_valid=ent_out(k.E_STV),
        st_version=ent_out(k.E_STVER),
        rt_client=flat(rt_o[k.RT_CLIENT]), rt_seq=flat(rt_o[k.RT_SEQ]),
        rt_port=flat(rt_o[k.RT_PORT]), rt_ts=f32(flat(rt_o[k.RT_TS])),
        rt_acked=flat(rt_o[k.RT_ACKED]), rt_kidx=flat(rt_o[k.RT_KIDX]),
        qlen=ent_out(k.E_QLEN), front=ent_out(k.E_FRONT),
        rear=ent_out(k.E_REAR),
        ob_live=flat(ob_o[k.OB_LIVE]), ob_kidx=flat(ob_o[k.OB_KIDX]),
        ob_version=flat(ob_o[k.OB_VER]), ob_vlen=flat(ob_o[k.OB_VLEN]),
        ob_frags=ent_out(k.E_FRAGS),
        val_writer=flat(ob_o[k.OB_VWR]), val_written=flat(ob_o[k.OB_VWN]),
        served=grid(k.G_SERVED), g_client=grid(k.G_CLIENT),
        g_seq=grid(k.G_SEQ), g_port=grid(k.G_PORT),
        g_ts=f32(grid(k.G_TS)), g_kidx=grid(k.G_KIDX),
        line_kidx=ent_out(k.E_LKIDX), line_vlen=ent_out(k.E_LVLEN),
        line_version=ent_out(k.E_LVER),
    )
