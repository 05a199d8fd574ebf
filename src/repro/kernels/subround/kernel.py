"""subround: the FULL per-subround switch pass as one Pallas kernel.

One VMEM-resident pass per request tile fuses the whole per-subround switch
decision of the data plane (paper Fig. 4): 128-bit exact match + validity +
popularity, request-table admission AND metadata apply, the state-table
invalidate/validate one-hots, the orbit-line install last-writer reduction,
and the orbit serving round finalized at the last grid step.

Tiling: the tables (hkeys, flags, queue pointers, orbit metadata) stay
resident in VMEM across the whole grid; the request batch streams through
in ``block_b`` tiles.  Cross-tile sequencing (a packet's slot offset
depends on how many same-entry packets came before it in the batch) is
carried in accumulator blocks mapped to a fixed index — grid steps
execute sequentially on a TPU core, so the running per-entry attempt
counts, the popularity sums, and the winner grids all build up in place,
exactly like the resident sketch accumulator in the cms kernel.

Layout: every array in the body is 2-D.  Request lanes run down the
sublanes (per-lane values are ``[TB, 1]`` columns) and table entries run
along the lanes (per-entry values are ``[1, C]`` rows), so every match and
winner reduction is a ``[TB, C]`` select followed by a sum, min or max over
one axis.  The per-entry tables arrive entry-minor — the request table as
``[S, C]`` per field, orbit lines as ``[F, C]``, the serve grid leaves as
``[J, C]`` — so a slot, fragment or serve index is a static row and no
gather or reshape runs inside the kernel.  The recirculation budget is an
SMEM scalar.  Floats travel as their int32 bit patterns.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# columns of the packed per-lane input ``lanes`` int32[B, LANE_COLS]
(L_HK0, L_HK1, L_HK2, L_HK3, L_WANT, L_WREQ, L_INST, L_FRAG, L_NFRAGS,
 L_KIDX, L_VLEN, L_CLIENT, L_SEQ, L_PORT, L_TS) = range(15)
LANE_COLS = 15
# columns of the packed per-lane output int32[B, 4]
(O_HIT, O_VHIT, O_ACC, O_OVF) = range(4)
# rows of the per-entry input int32[E_IN, C]
(E_OCC, E_STV, E_STVER, E_QLEN, E_FRONT, E_REAR, E_FRAGS) = range(7)
E_IN = 7
# rows of the per-entry output int32[E_OUT, C]
(E_POP, E_LKIDX, E_LVLEN, E_LVER) = (7, 8, 9, 10)
E_OUT = 11
# request-table fields, int32[RT_FIELDS, S, C]
(RT_CLIENT, RT_SEQ, RT_PORT, RT_TS, RT_ACKED, RT_KIDX) = range(6)
RT_FIELDS = 6
# orbit-line fields, int32[OB_IN, F, C] in / int32[OB_OUT, F, C] out
(OB_LIVE, OB_KIDX, OB_VER, OB_VLEN, OB_VWR, OB_VWN) = range(6)
OB_IN, OB_OUT = 4, 6
# serve-grid fields, int32[G_FIELDS, J, C]
(G_SERVED, G_CLIENT, G_SEQ, G_PORT, G_TS, G_KIDX) = range(6)
G_FIELDS = 6
# kernel-internal per-entry accumulators (VMEM scratch rows)
(A_WCNT, A_INV, A_VAL, A_NEWC) = range(4)

# request-table field <- per-lane column, for the admission write and the
# front-slot serve gather (acked is zeroed on admission, never served)
_RT_FROM_LANE = ((RT_CLIENT, L_CLIENT), (RT_SEQ, L_SEQ), (RT_PORT, L_PORT),
                 (RT_TS, L_TS), (RT_KIDX, L_KIDX))
_GRID_FROM_RT = ((G_CLIENT, RT_CLIENT), (G_SEQ, RT_SEQ), (G_PORT, RT_PORT),
                 (G_TS, RT_TS), (G_KIDX, RT_KIDX))


def _subround_kernel(
    lanes_ref, thk_ref, ent_ref, rt_in, ob_in, budget_ref,
    lane_o, ent_o, rt_o, ob_o, grid_o,
    acc,
    *, queue_size: int, max_frags: int, max_serves: int, n_steps: int,
):
    """One VMEM pass per request tile over the WHOLE subround (Fig. 4).

    Stages per tile: 128-bit match + validity + popularity, request-table
    admission AND metadata winner-selects, the state-table
    invalidate/validate one-hots, and the orbit-line install last-writer
    reduction.  At the final grid step — once the whole batch has been
    applied — the resident accumulators are finalized in place: state bits
    resolved, installed lines stamped with the post-batch entry version,
    liveness refreshed, the recirculation budget split over live lines, and
    the request-table front slots selected/popped into the serve grid.
    Value bytes never enter: install winners leave as ``vwr``/``vwn`` for
    the once-per-window byte apply.
    """
    step = pl.program_id(0)
    s, f, j = queue_size, max_frags, max_serves
    tb_n = lanes_ref.shape[0]
    c = thk_ref.shape[1]
    i32 = jnp.int32
    lane = lambda k: lanes_ref[:, k:k + 1]           # [TB, 1]
    ent = lambda k: ent_ref[k:k + 1, :]              # [1, C]
    colsum = lambda m, v: jnp.sum(jnp.where(m, v, 0), axis=0, keepdims=True)
    rowsum = lambda m, v: jnp.sum(jnp.where(m, v, 0), axis=1, keepdims=True)
    colany = lambda m: jnp.max(m.astype(i32), axis=0, keepdims=True) > 0

    col = jax.lax.broadcasted_iota(i32, (tb_n, c), 1)
    lanes_c = jax.lax.broadcasted_iota(i32, (tb_n, c), 0)

    # ---- match slice ------------------------------------------------------
    eq = ent(E_OCC) > 0
    for k in range(4):
        eq = eq & (lane(L_HK0 + k) == thk_ref[k:k + 1, :])
    # first matching entry: a min over the masked entry iota
    cidx = jnp.min(jnp.where(eq, col, c), axis=1, keepdims=True)
    hit = cidx < c
    safe = jnp.where(hit, cidx, 0)
    at = col == safe                                 # [TB, C] one-hot
    entry_valid = (rowsum(at, ent(E_STV)) > 0) & hit
    want = lane(L_WANT) > 0
    pop_delta = colsum(eq & want, 1)

    @pl.when(step == 0)
    def _init():
        # zero the running accumulators, seed the table outputs with the
        # call-time state — later tiles overwrite their winner slots only.
        acc[...] = jnp.zeros_like(acc)
        ent_o[...] = jnp.zeros_like(ent_o)
        ent_o[E_STVER:E_STVER + 1, :] = ent(E_STVER)
        ent_o[E_FRAGS:E_FRAGS + 1, :] = ent(E_FRAGS)
        rt_o[...] = rt_in[...]
        ob_o[...] = jnp.zeros_like(ob_o)
        ob_o[0:OB_IN] = ob_in[...]

    # ---- admission slice (cross-tile sequencing via wcnt) -----------------
    qlen0 = ent(E_QLEN)
    rear0 = ent(E_REAR)
    want_enq = want & hit & entry_valid
    onehot = at & want_enq
    # exclusive prefix count down the tile: a strictly lower-triangular
    # 0/1 matmul, exact in f32 accumulation for any realistic tile
    tri = (jax.lax.broadcasted_iota(i32, (tb_n, tb_n), 1)
           < jax.lax.broadcasted_iota(i32, (tb_n, tb_n), 0))
    tile_prior = jnp.dot(tri.astype(jnp.bfloat16),
                         onehot.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32).astype(i32)
    running = acc[A_WCNT:A_WCNT + 1, :]
    offset = rowsum(onehot, tile_prior + running)
    free_i = rowsum(onehot, s - qlen0)
    rear_i = rowsum(onehot, rear0)
    accepted = want_enq & (offset < free_i)
    overflow = want_enq & ~accepted
    for k, v in ((O_HIT, hit), (O_VHIT, entry_valid), (O_ACC, accepted),
                 (O_OVF, overflow)):
        lane_o[:, k:k + 1] = v.astype(i32)

    slot = (rear_i + offset) % s
    acc_at = onehot & accepted
    for sp in range(s):
        woh = acc_at & (slot == sp)                  # unique writer per slot
        writ = colany(woh)
        for fld, src in _RT_FROM_LANE:
            rt_o[fld, sp:sp + 1, :] = jnp.where(
                writ, colsum(woh, lane(src)), rt_o[fld, sp:sp + 1, :])
        rt_o[RT_ACKED, sp:sp + 1, :] = jnp.where(
            writ, 0, rt_o[RT_ACKED, sp:sp + 1, :])

    ent_o[E_POP:E_POP + 1, :] += pop_delta
    acc[A_NEWC:A_NEWC + 1, :] += colsum(acc_at, 1)
    acc[A_WCNT:A_WCNT + 1, :] = running + colsum(onehot, 1)

    # ---- state-table one-hots (whole-batch apply, finalized at the end) ---
    install = (lane(L_INST) > 0) & hit
    oh_inv = at & (lane(L_WREQ) > 0) & hit
    oh_val = at & install
    acc[A_INV:A_INV + 1, :] |= colany(oh_inv).astype(i32)
    acc[A_VAL:A_VAL + 1, :] |= colany(oh_val).astype(i32)
    ent_o[E_STVER:E_STVER + 1, :] += colsum(oh_inv, 1)

    # ---- orbit-line install (last writer wins; later tiles override) ------
    frag = lane(L_FRAG)
    fr = jnp.clip(frag, 0, f - 1)
    for fp in range(f):
        lh = oh_val & (fr == fp)
        win_rel = jnp.max(jnp.where(lh, lanes_c, -1), axis=0, keepdims=True)
        written = win_rel >= 0
        sel = lh & (lanes_c == win_rel)
        for fld, src in ((OB_KIDX, L_KIDX), (OB_VLEN, L_VLEN)):
            ob_o[fld, fp:fp + 1, :] = jnp.where(
                written, colsum(sel, lane(src)), ob_o[fld, fp:fp + 1, :])
        ob_o[OB_VWR, fp:fp + 1, :] = jnp.where(
            written, win_rel + step * tb_n, ob_o[OB_VWR, fp:fp + 1, :])
        ob_o[OB_VWN, fp:fp + 1, :] |= written.astype(i32)
        ob_o[OB_LIVE, fp:fp + 1, :] |= written.astype(i32)

    eh = oh_val & (frag == 0)
    win_e = jnp.max(jnp.where(eh, lanes_c, -1), axis=0, keepdims=True)
    sel_e = eh & (lanes_c == win_e)
    nf_g = colsum(sel_e, jnp.maximum(lane(L_NFRAGS), 1))
    ent_o[E_FRAGS:E_FRAGS + 1, :] = jnp.where(
        win_e >= 0, nf_g, ent_o[E_FRAGS:E_FRAGS + 1, :])

    # ---- serving round: finalize once the whole batch is in ---------------
    @pl.when(step == n_steps - 1)
    def _serve():
        stv_f = (((ent(E_STV) > 0) & (acc[A_INV:A_INV + 1, :] == 0))
                 | (acc[A_VAL:A_VAL + 1, :] > 0))
        ent_o[E_STV:E_STV + 1, :] = stv_f.astype(i32)
        stver_f = ent_o[E_STVER:E_STVER + 1, :]

        # installed lines carry the post-batch entry version; drop-stale
        # liveness refresh and the per-entry recirculation budget
        n_live_c = jnp.zeros((1, c), i32)
        for fp in range(f):
            over = jnp.where(ob_o[OB_VWN, fp:fp + 1, :] > 0, stver_f,
                             ob_in[OB_VER, fp:fp + 1, :])
            ob_o[OB_VER, fp:fp + 1, :] = over
            ok = ((ent(E_OCC) > 0) & stv_f & (over == stver_f)
                  & (ob_o[OB_LIVE, fp:fp + 1, :] > 0))
            ob_o[OB_LIVE, fp:fp + 1, :] = ok.astype(i32)
            n_live_c = n_live_c + ok.astype(i32)
        n_live = jnp.maximum(jnp.sum(n_live_c, axis=1, keepdims=True), 1)
        per_line = budget_ref[0, 0] // n_live
        complete = n_live_c >= ent_o[E_FRAGS:E_FRAGS + 1, :]
        budget_c = jnp.where(complete, per_line, 0)

        newc = acc[A_NEWC:A_NEWC + 1, :]
        qlen2 = qlen0 + newc
        ent_o[E_REAR:E_REAR + 1, :] = (rear0 + newc) % s

        front0 = ent(E_FRONT)
        n_serve = jnp.minimum(qlen2, budget_c)
        n_pop = jnp.zeros((1, c), i32)
        for jj in range(j):
            served = jj < n_serve
            grid_o[G_SERVED, jj:jj + 1, :] = served.astype(i32)
            n_pop = n_pop + served.astype(i32)
            slot_g = (front0 + jj) % s
            for gf, fld in _GRID_FROM_RT:
                g = jnp.zeros((1, c), i32)
                for sp in range(s):
                    g = jnp.where(slot_g == sp, rt_o[fld, sp:sp + 1, :], g)
                grid_o[gf, jj:jj + 1, :] = g
        ent_o[E_QLEN:E_QLEN + 1, :] = qlen2 - n_pop
        ent_o[E_FRONT:E_FRONT + 1, :] = (front0 + n_pop) % s

        ent_o[E_LKIDX:E_LKIDX + 1, :] = ob_o[OB_KIDX, 0:1, :]
        ent_o[E_LVLEN:E_LVLEN + 1, :] = jnp.sum(ob_o[OB_VLEN], axis=0,
                                                keepdims=True)
        ent_o[E_LVER:E_LVER + 1, :] = ob_o[OB_VER, 0:1, :]


@partial(jax.jit, static_argnames=("queue_size", "max_frags", "max_serves",
                                   "block_b", "interpret"))
def subround(lanes, table_hkeys_t, ent, rt, ob, budget, *,
             queue_size: int, max_frags: int, max_serves: int,
             block_b: int, interpret: bool):
    """Full fused subround (see ``_subround_kernel``).  B % block_b == 0.

    Args (all int32; ``ops.subround`` packs them from the public layout):
      lanes: [B, LANE_COLS] per-lane columns (``L_*``).
      table_hkeys_t: [4, C] installed key hashes, one row per hash word.
      ent: [E_IN, C] per-entry rows (``E_*``).
      rt: [RT_FIELDS, S, C] request-table fields (``RT_*``).
      ob: [OB_IN, F, C] orbit-line metadata (``OB_*``).
      budget: [1, 1] recirculation budget of this subround (SMEM).

    Returns ``(lane_out [B, 4], ent_out [E_OUT, C], rt [RT_FIELDS, S, C],
    ob_out [OB_OUT, F, C], grid [G_FIELDS, J, C])``.
    """
    b = lanes.shape[0]
    c = table_hkeys_t.shape[1]
    s, f, j = queue_size, max_frags, max_serves
    n_steps = b // block_b
    fixed = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    out_shape = [
        jax.ShapeDtypeStruct((b, 4), jnp.int32),
        jax.ShapeDtypeStruct((E_OUT, c), jnp.int32),
        jax.ShapeDtypeStruct((RT_FIELDS, s, c), jnp.int32),
        jax.ShapeDtypeStruct((OB_OUT, f, c), jnp.int32),
        jax.ShapeDtypeStruct((G_FIELDS, j, c), jnp.int32),
    ]
    return pl.pallas_call(
        partial(_subround_kernel, queue_size=s, max_frags=f, max_serves=j,
                n_steps=n_steps),
        grid=(n_steps,),
        in_specs=[
            pl.BlockSpec((block_b, LANE_COLS), lambda i: (i, 0)),
            fixed(4, c),
            fixed(E_IN, c),
            fixed(RT_FIELDS, s, c),
            fixed(OB_IN, f, c),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 4), lambda i: (i, 0)),
            fixed(E_OUT, c),
            fixed(RT_FIELDS, s, c),
            fixed(OB_OUT, f, c),
            fixed(G_FIELDS, j, c),
        ],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((4, c), jnp.int32)],
        interpret=interpret,
    )(lanes, table_hkeys_t, ent, rt, ob, budget)
