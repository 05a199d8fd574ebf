"""Public wrapper for hot_gather: pads B/C/D to tile alignment."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import hot_gather as _kernel
from .ref import hot_gather_ref  # noqa: F401


def hot_gather(ids, hot_ids, rows, block_b: int = 256, block_d: int = 512,
               *, interpret: bool):
    """Exact id-match sums of int32 ``rows`` (see ``kernel.py``)."""
    b = ids.shape[0]
    c, d = rows.shape
    block_b = min(block_b, max(8, b))
    block_d = min(block_d, max(128, d))
    pad_b = (-b) % block_b
    pad_c = (-c) % 128
    pad_d = (-d) % block_d
    if pad_b:
        ids = jnp.pad(ids, (0, pad_b), constant_values=-2)
    if pad_c:
        hot_ids = jnp.pad(hot_ids, (0, pad_c), constant_values=-1)
        rows = jnp.pad(rows, ((0, pad_c), (0, 0)))
    if pad_d:
        rows = jnp.pad(rows, ((0, 0), (0, pad_d)))
    out, hit = _kernel(ids, hot_ids, rows, block_b=block_b, block_d=block_d,
                       interpret=interpret)
    return out[:b, :d], hit[:b]
