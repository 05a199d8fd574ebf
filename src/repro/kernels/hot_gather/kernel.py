"""hot_gather: exact id-match row sums as a Pallas TPU kernel.

Given ids and a hot-id set, produce per id the sum of the int32 rows whose
hot id matches it, and a hit mask.  The id-vs-hot-set equality matrix
[TB, C] is contracted against the hot table [C, D] on the MXU — a gather
with zero scalar loops, and, where an id matches several hot ids, their
sum (the controller merges server reports this way).

Exactness: the MXU multiplies bf16 and accumulates f32, and has no int32
mode.  So each int32 row is split into four 8-bit limbs of its bit
pattern; a limb (0..255) and a 0/1 match are exact in bf16, and a column
sum of at most C * 255 is exact in f32 for C < 65,793.  The limb sums are
shifted back into place with wrapping int32 adds, which is bit-identical
to the int32 sum the oracle computes.  Ids and the hot set are ``[TB, 1]``
columns and ``[1, C]`` rows.

Tiling: grid (B tiles x D tiles); the hot-id row stays resident; the hot
table streams its D tile per grid column.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hot_gather_kernel(ids_ref, hot_ids_ref, rows_ref, out_ref, hit_ref):
    eq = ids_ref[...] == hot_ids_ref[...]                    # [TB, C]
    oh = eq.astype(jnp.bfloat16)
    rows = rows_ref[...]                                      # [C, TD] int32
    out = None
    for k in range(4):
        limb = jax.lax.shift_right_logical(rows, 8 * k) & 0xFF
        part = jnp.dot(oh, limb.astype(jnp.float32).astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(jnp.int32)
        part = jax.lax.shift_left(part, 8 * k)
        out = part if out is None else out + part
    out_ref[...] = out
    hit_ref[...] = jnp.max(eq.astype(jnp.int32), axis=1, keepdims=True)


@partial(jax.jit, static_argnames=("block_b", "block_d", "interpret"))
def hot_gather(ids, hot_ids, rows, *, block_b: int, block_d: int,
               interpret: bool):
    """ids int32[B]; hot_ids int32[C] (pad = -1); rows int32[C, D].

    Returns (out int32[B, D], hit int32[B]).
    """
    b = ids.shape[0]
    c, d = rows.shape
    grid = (b // block_b, d // block_d)
    out, hit = pl.pallas_call(
        _hot_gather_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, c), lambda i, j: (0, 0)),
            pl.BlockSpec((c, block_d), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, d), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
    )(ids.reshape(b, 1), hot_ids.reshape(1, c), rows)
    return out, hit[:, 0]
