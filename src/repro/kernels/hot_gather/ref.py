"""Pure-jnp oracle for hot_gather: an integer select-and-sum, exact on
every backend (no matmul, so no reduced-precision pass)."""
from __future__ import annotations

import jax.numpy as jnp


def hot_gather_ref(ids, hot_ids, rows):
    eq = ids[:, None] == hot_ids[None, :]
    out = jnp.sum(jnp.where(eq[:, :, None], rows[None, :, :], 0), axis=1,
                  dtype=rows.dtype)
    hit = jnp.any(eq, axis=1).astype(jnp.int32)
    return out, hit
