"""Public wrapper for the cms kernel: computes the five fold-hash row
indices from 128-bit key hashes, pads, dispatches."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.hashing import fold_hash

from .kernel import DEPTH, cms_update_query as _kernel
from .ref import cms_update_query_ref  # noqa: F401


def rows_for(hkey: jnp.ndarray, width: int) -> jnp.ndarray:
    """int32[B, DEPTH] sketch row indices for a batch of key hashes."""
    return jnp.stack([fold_hash(hkey, width, salt=d) for d in range(DEPTH)],
                     axis=-1)


def cms_update_query(hkey, mask, counts, block_b: int = 256, *,
                     interpret: bool):
    """Fused CMS update+query.  hkey uint32[B,4]; counts int32[DEPTH, W]."""
    b = hkey.shape[0]
    idx = rows_for(hkey, counts.shape[1])
    block_b = min(block_b, max(8, b))
    pad = (-b) % block_b
    if pad:
        idx = jnp.pad(idx, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, (0, pad))
    new_counts, est = _kernel(idx, mask.astype(jnp.int32), counts,
                              block_b=block_b, interpret=interpret)
    return new_counts, est[:b]
