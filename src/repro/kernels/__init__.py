"""Pallas TPU kernels for the OrbitCache dataplane hot spots.

Each kernel directory holds:
  kernel.py  pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py     public wrapper: pads to tile alignment, packs the kernel's
             2-D blocks; ``interpret`` is a required keyword
  ref.py     pure-jnp oracle (tests assert bit-identity across shape sweeps)

Hardware adaptation: the switch's TCAM match and register scatters have no
TPU analogue, so `subround` and `cms` work on 2-D one-hot selects —
request lanes down the sublanes, table entries along the lanes — reduced
with sums, mins and maxes over one axis, and `hot_gather` contracts the
id-match matrix on the MXU, exactly, through 8-bit limbs.  `orbit_match`
has no production caller and does not lower through Mosaic.

Backend dispatch
----------------
The simulator hot path calls the dispatchers below (``subround`` — the
whole per-subround switch pass as ONE kernel, ``orbit_match``,
``cms_update_query``, ``hot_gather``) instead of picking a kernel variant
by hand.  The backend is resolved once per trace:

  * ``pallas``     compiled Pallas kernels (the TPU hot path),
  * ``interpret``  Pallas kernels under the interpreter (debugging,
                   kernel-vs-oracle parity off-TPU),
  * ``ref``        the pure-jnp oracles (fast XLA path on CPU/GPU).

Resolution order: ``set_kernel_backend()`` > the ``REPRO_KERNEL_BACKEND``
environment variable > autodetect (``pallas`` on TPU, ``ref`` elsewhere).
Backend choice is baked into jitted callers at trace time, so flip it
before building simulators.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

# Initialize the kernel subpackages BEFORE the same-named dispatchers below:
# Python binds a submodule as a parent-package attribute at first import, so
# importing them eagerly here guarantees the dispatcher functions (defined
# afterwards) permanently shadow the subpackage attributes.
from . import cms as _cms_pkg                      # noqa: F401, E402
from . import hot_gather as _hot_gather_pkg        # noqa: F401, E402
from . import orbit_match as _orbit_match_pkg      # noqa: F401, E402
from . import subround as _subround_pkg            # noqa: F401, E402

KERNEL_BACKENDS = ("pallas", "interpret", "ref")
_ENV_VAR = "REPRO_KERNEL_BACKEND"
_forced: str | None = None


def set_kernel_backend(name: str | None) -> None:
    """Force a kernel backend for this process (``None`` restores auto)."""
    global _forced
    if name is not None and name not in KERNEL_BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; "
                         f"expected one of {KERNEL_BACKENDS}")
    _forced = name


def kernel_backend() -> str:
    """Resolve the active backend: forced > env > autodetect."""
    if _forced is not None:
        return _forced
    env = os.environ.get(_ENV_VAR, "").strip().lower()
    if env:
        if env not in KERNEL_BACKENDS:
            raise ValueError(f"{_ENV_VAR}={env!r}; "
                             f"expected one of {KERNEL_BACKENDS}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# ---------------------------------------------------------------------------
# dispatchers
# ---------------------------------------------------------------------------
def orbit_match(hkey, table_hkeys, occupied, valid, pop_mask=None,
                block_b: int = 256):
    """Fused match-action lookup: (cidx [B], hit [B], valid_hit [B], pop [C]).

    128-bit exact-match of ``hkey`` against the installed table entries,
    validity filter, and per-entry popularity accumulation over the lanes
    selected by ``pop_mask`` — one fused pass on the active backend.
    """
    be = kernel_backend()
    if be == "ref":
        from .orbit_match.ref import orbit_match_ref
        return orbit_match_ref(hkey, table_hkeys, occupied, valid, pop_mask)
    from .orbit_match.ops import orbit_match as _om
    return _om(hkey, table_hkeys, occupied, valid, pop_mask,
               block_b=block_b, interpret=(be == "interpret"))


def subround(
    hkey, want, wreq, inst, frag, nfrags, kidx, vlen, client, seq, port, ts,
    table_hkeys, occupied, st_valid, st_version,
    rt_client, rt_seq, rt_port, rt_ts, rt_acked, rt_kidx, qlen, front, rear,
    ob_live, ob_kidx, ob_version, ob_vlen, ob_frags,
    budget,
    queue_size: int, max_frags: int, max_serves: int, block_b: int = 128,
):
    """The FULL per-subround switch pass as one fused op (paper Fig. 4).

    Superset of ``orbit_match``: 128-bit match, validity filter,
    popularity, request-table admission AND metadata apply, the state-table
    invalidate/validate pass, the orbit-line metadata install (value bytes
    deferred to the per-window apply), and the orbit serving round
    (liveness refresh, recirculation-budget split, front-slot gathers,
    served-entry dequeue).  On the kernel backends this is a single
    ``pallas_call``; ``ref`` runs the pure-jnp oracle.  All gate masks must
    already include lane validity.  Returns an ``ops.SubroundOuts``.
    """
    be = kernel_backend()
    if be == "ref":
        from .subround.ops import SubroundOuts
        from .subround.ref import subround_ref
        out = subround_ref(
            hkey, want, wreq, inst, frag, nfrags, kidx, vlen, client, seq,
            port, ts, table_hkeys, occupied, st_valid, st_version,
            rt_client, rt_seq, rt_port, rt_ts, rt_acked, rt_kidx, qlen,
            front, rear, ob_live, ob_kidx, ob_version, ob_vlen, ob_frags,
            jnp.asarray(budget, jnp.int32),
            queue_size=queue_size, max_frags=max_frags,
            max_serves=max_serves)
        return SubroundOuts(*out)
    from .subround.ops import subround as _sr
    return _sr(hkey, want, wreq, inst, frag, nfrags, kidx, vlen, client,
               seq, port, ts, table_hkeys, occupied, st_valid, st_version,
               rt_client, rt_seq, rt_port, rt_ts, rt_acked, rt_kidx, qlen,
               front, rear, ob_live, ob_kidx, ob_version, ob_vlen, ob_frags,
               budget, queue_size, max_frags, max_serves,
               block_b=block_b, interpret=(be == "interpret"))


def cms_update_query(hkey, mask, counts, block_b: int = 256):
    """Fused count-min sketch update+query on the active backend."""
    be = kernel_backend()
    if be == "ref":
        # replay the kernel's tile order exactly (estimates are taken
        # against the sketch state at the start of each batch tile), in the
        # O(B * DEPTH) scatter/gather form — bit-identical to the one-hot
        # oracle, cheap enough for the per-window server tracker.
        from .cms.ops import rows_for
        from .cms.ref import cms_update_query_fast
        b = hkey.shape[0]
        idx = rows_for(hkey, counts.shape[1])
        msk = jnp.asarray(mask, jnp.int32)
        tile = min(block_b, max(8, b))
        pad = (-b) % tile
        if pad:
            idx = jnp.pad(idx, ((0, pad), (0, 0)))
            msk = jnp.pad(msk, (0, pad))
        new_counts, est = cms_update_query_fast(idx, msk, counts, block_b=tile)
        return new_counts, est[:b]
    from .cms.ops import cms_update_query as _cms
    return _cms(hkey, mask, counts, block_b=block_b,
                interpret=(be == "interpret"))


def hot_gather(ids, hot_ids, rows, block_b: int = 256, block_d: int = 512):
    """Exact id-match sums of int32 ``rows`` on the active backend."""
    be = kernel_backend()
    if be == "ref":
        from .hot_gather.ref import hot_gather_ref
        return hot_gather_ref(ids, hot_ids, rows)
    from .hot_gather.ops import hot_gather as _hg
    return _hg(ids, hot_ids, rows, block_b=block_b, block_d=block_d,
               interpret=(be == "interpret"))
