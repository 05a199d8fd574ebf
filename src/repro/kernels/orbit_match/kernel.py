"""orbit_match: the switch's match-action lookup as a Pallas TPU kernel.

Fuses, for a batch of requests:
  * 128-bit exact-match of request hashes against the C installed entries
    (the TCAM of the paper's lookup table -> vectorized equality in VMEM),
  * validity filter (state table),
  * per-entry popularity increments (key popularity counter), accumulated
    across the batch grid in the output block.

Tiling: the table (C <= 1024 entries x 4 hash lanes) and its flag vectors
stay resident in VMEM across the whole grid; the request batch streams
through in ``block_b`` tiles.  All comparisons are 2-D (block_b x C) so
the VPU lanes stay full; C is padded to a multiple of 128 by the wrapper
so the one-hot reductions are MXU/VREG aligned.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _match_kernel(hkey_ref, table_ref, occ_ref, valid_ref, mask_ref,
                  cidx_ref, hit_ref, vhit_ref, pop_ref):
    step = pl.program_id(0)
    hk = hkey_ref[...]                       # [TB, 4] uint32
    tb = table_ref[...]                      # [C, 4] uint32
    occ = occ_ref[...]                       # [C] int32
    val = valid_ref[...]                     # [C] int32
    msk = mask_ref[...]                      # [TB] int32 popularity gate

    # [TB, C]: full 128-bit equality (four 32-bit lanes)
    eq = jnp.ones(hk.shape[:1] + tb.shape[:1], dtype=jnp.bool_)
    for lane in range(4):
        eq = eq & (hk[:, lane][:, None] == tb[:, lane][None, :])
    eq = eq & (occ[None, :] > 0)

    hit = jnp.any(eq, axis=1)
    cidx = jnp.argmax(eq, axis=1).astype(jnp.int32)
    safe = jnp.where(hit, cidx, 0)
    entry_valid = (val[safe] > 0) & hit

    cidx_ref[...] = jnp.where(hit, cidx, -1)
    hit_ref[...] = hit.astype(jnp.int32)
    vhit_ref[...] = entry_valid.astype(jnp.int32)

    # popularity accumulation across grid steps (same output block),
    # gated per request (the switch counts only valid R-REQ lanes)
    delta = jnp.sum((eq & (msk[:, None] > 0)).astype(jnp.int32), axis=0)
    @pl.when(step == 0)
    def _init():
        pop_ref[...] = delta

    @pl.when(step > 0)
    def _acc():
        pop_ref[...] = pop_ref[...] + delta


@partial(jax.jit, static_argnames=("block_b", "interpret"))
def orbit_match(hkey, table_hkeys, occupied, valid, pop_mask, *,
                block_b: int, interpret: bool):
    """Batched lookup: returns (cidx [B], hit [B], valid_hit [B], pop [C]).

    Args:
      hkey: uint32[B, 4] request key hashes (B % block_b == 0; wrapper pads).
      table_hkeys: uint32[C, 4]; occupied/valid: int32[C] flags.
      pop_mask: int32[B]; only masked lanes contribute to ``pop``.
    """
    b = hkey.shape[0]
    c = table_hkeys.shape[0]
    grid = (b // block_b,)
    return pl.pallas_call(
        _match_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, 4), lambda i: (i, 0)),
            pl.BlockSpec((c, 4), lambda i: (0, 0)),      # table resident
            pl.BlockSpec((c,), lambda i: (0,)),
            pl.BlockSpec((c,), lambda i: (0,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((c,), lambda i: (0,)),          # accumulated
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((c,), jnp.int32),
        ],
        interpret=interpret,
    )(hkey, table_hkeys, occupied, valid, pop_mask)
