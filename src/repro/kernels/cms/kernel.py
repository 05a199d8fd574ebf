"""cms: count-min sketch update + query as a Pallas TPU kernel.

The sketch is a [DEPTH, W] counter matrix.  A GPU/CPU implementation
scatters; scatters serialize on TPU, so the kernel uses the MXU-native
formulation: per depth, the batch's row indices become a one-hot matrix
[TB, W] and

  * update: counts[d] += ones[1, TB] @ onehot        (column sums)
  * query:  est[b, d]  = (onehot * counts[d]) row-sum (masked gather)

One fused pass returns both the updated sketch and the pre-update
estimates (the paper's servers query-then-report).  The sketch stays
resident in VMEM ([5, 2048] i32 = 40 KiB at the server tracker's width);
the batch streams in tiles.  Per-lane values are ``[TB, 1]`` columns and
sketch rows ``[1, W]`` rows, so the one-hot is a 2-D ``[TB, W]`` select.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEPTH = 5


def _cms_kernel(idx_ref, mask_ref, counts_ref, new_counts_ref, est_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        new_counts_ref[...] = counts_ref[...]

    msk = mask_ref[...] > 0                            # [TB, 1]
    w = counts_ref.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (idx_ref.shape[0], w), 1)
    est = None
    for d in range(DEPTH):
        row = new_counts_ref[d:d + 1, :]               # [1, W] tile start
        onehot = (col == idx_ref[:, d:d + 1]) & msk    # [TB, W]
        q = jnp.sum(jnp.where(onehot, row, 0), axis=1, keepdims=True)
        est = q if est is None else jnp.minimum(est, q)
        new_counts_ref[d:d + 1, :] = row + jnp.sum(
            onehot.astype(jnp.int32), axis=0, keepdims=True)
    est_ref[...] = jnp.where(msk, est, 0)


@partial(jax.jit, static_argnames=("block_b", "interpret"))
def cms_update_query(idx, mask, counts, *, block_b: int, interpret: bool):
    """idx: int32[B, DEPTH] row indices; mask: int32[B]; counts: int32[D, W].

    Returns (new_counts [D, W], est [B]) where est is the pre-update
    count-min estimate of each masked key.
    """
    b = idx.shape[0]
    d, w = counts.shape
    grid = (b // block_b,)
    new_counts, est = pl.pallas_call(
        _cms_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, DEPTH), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((d, w), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d, w), lambda i: (0, 0)),   # resident accumulator
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, w), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
    )(idx, mask.reshape(b, 1), counts)
    return new_counts, est[:, 0]
