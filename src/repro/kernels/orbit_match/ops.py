"""Public wrapper for the orbit_match kernel: pads batch/table to hardware
alignment, unpads results."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import orbit_match as _kernel
from .ref import orbit_match_ref  # noqa: F401  (re-exported oracle)


def orbit_match(hkey, table_hkeys, occupied, valid, pop_mask=None,
                block_b: int = 256, *, interpret: bool):
    """Batched match-action lookup (see kernel.py).  Any B, any C."""
    b = hkey.shape[0]
    c = table_hkeys.shape[0]
    if pop_mask is None:
        pop_mask = jnp.ones((b,), jnp.int32)
    block_b = min(block_b, max(8, b))
    pad_b = (-b) % block_b
    pad_c = (-c) % 128 if c % 128 else 0
    if pad_b:
        hkey = jnp.pad(hkey, ((0, pad_b), (0, 0)))
        pop_mask = jnp.pad(pop_mask, (0, pad_b))
    if pad_c:
        table_hkeys = jnp.pad(table_hkeys, ((0, pad_c), (0, 0)))
        occupied = jnp.pad(occupied, (0, pad_c))
        valid = jnp.pad(valid, (0, pad_c))
    cidx, hit, vhit, pop = _kernel(
        hkey, table_hkeys, occupied, valid, pop_mask, block_b=block_b,
        interpret=interpret)
    return cidx[:b], hit[:b], vhit[:b], pop[:c]
