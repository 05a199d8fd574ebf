"""Key-value workload generation (paper §5.1).

The paper's default: 10M key-value pairs, Zipf-0.99 popularity, 16-byte
keys, bimodal values (82% 64 B / 18% 1024 B — the cacheable-item ratio of
NetCache on Twitter Cluster018), read-mostly.  Production workloads A–E
model Twitter clusters 045/016/044/017/020 by their cacheable-item ratio
and write ratio (paper Fig. 14).

Keys are identified by rank-order ids (0 = hottest); a permutation maps
rank -> kidx so popularity can change over time (hot-in churn, Fig. 18).
Value sizes are assigned per *key* (deterministic hash) so a key's size is
stable, matching how the paper assigns its 64 B/1024 B split.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.hashing import hash128_u32_np
from repro.obs import span


# float32 ``jax.random.uniform`` draws carry 23 random mantissa bits: every
# draw is exactly ``k * 2**-23`` for an integer k in [0, 2**23).
UNIFORM_BITS = 23


def zipf_probs(num_keys: int, alpha: float) -> np.ndarray:
    """float64[num_keys] Zipf popularity of each rank (0 = hottest)."""
    w = np.arange(1, num_keys + 1, dtype=np.float64) ** (-alpha)
    return w / w.sum()


def zipf_inverse_table(cdf: np.ndarray) -> tuple[np.ndarray, int]:
    """Inverse of ``cdf`` on a grid of uniform draws: ``(table, fine_steps)``.

    ``table`` is ``int32[2**m]`` with ``m = min(23, floor(log2 N))`` (never
    larger than the CDF) and ``table[j] = searchsorted(cdf, j * 2**-m)``:
    the rank of the first draw in bucket ``j``.  ``fine_steps`` is the
    number of binary-search steps in the CDF that the widest bucket needs
    to reach the rank of every draw ``jax.random.uniform`` can make in it;
    at ``m = 23`` each bucket holds one draw and it is 0.  Built in
    O(N + 2**m): rank ``i`` counts in bucket ``j`` onwards iff
    ``cdf[i] < j * 2**-m``, i.e. ``j >= floor(cdf[i] * 2**m) + 1``
    (exact in float64).
    """
    m = min(UNIFORM_BITS, cdf.shape[0].bit_length() - 1)
    first = np.floor(cdf.astype(np.float64) * 2.0**m).astype(np.int64) + 1
    counts = np.bincount(np.minimum(first, 2**m), minlength=2**m + 1)
    table = np.cumsum(counts[:2**m]).astype(np.int32)
    if m == UNIFORM_BITS:
        return table, 0
    # the last draw of bucket j is ((j + 1) * 2**(23 - m) - 1) * 2**-23
    last = ((np.arange(1, 2**m + 1, dtype=np.int64) << (UNIFORM_BITS - m)) - 1)
    hi = np.searchsorted(cdf, last.astype(np.float32) * np.float32(2.0**-UNIFORM_BITS))
    return table, int((hi - table).max()).bit_length()


@partial(jax.tree_util.register_dataclass,
         data_fields=["table", "cdf", "perm", "vlen"],
         meta_fields=["fine_steps"])
@dataclass(frozen=True)
class WorkloadArrays:
    """The device-side workload state the jitted window step consumes.

    Kept separate from :class:`Workload` so it can be (a) passed as an
    explicit jit argument — host-side churn (``hot_in_swap``) is picked up
    without retracing — and (b) stacked/vmapped over a leading rack axis
    for batched multi-rack sweeps (``repro.kvstore.fleet``).  A vmap
    in_axes tree is a WorkloadArrays of 0/None with the same
    ``fine_steps``, which is static (part of the tree structure).
    """

    table: jnp.ndarray        # int32[2**m] Zipf inverse table (zipf_inverse_table)
    cdf: jnp.ndarray | None   # float32[num_keys] Zipf CDF; None where m == 23
    perm: jnp.ndarray         # int32[num_keys] rank -> key identity
    vlen: jnp.ndarray         # int32[num_keys] per-key value bytes
    fine_steps: int = 0       # binary-search steps in cdf after the table

    def ranks(self, u: jnp.ndarray) -> jnp.ndarray:
        """Popularity ranks of float32 ``jax.random.uniform`` draws ``u``:
        exactly ``jnp.searchsorted(cdf, u)`` (side left), as one table
        gather plus ``fine_steps`` bounded search steps in the CDF."""
        bits = self.table.shape[-1].bit_length() - 1
        rank = self.table[(u * 2.0**bits).astype(jnp.int32)]
        if self.fine_steps:
            # the largest r in [rank, rank + 2**fine_steps) with r == rank
            # or cdf[r - 1] < u: the answer lies there, and the predicate
            # holds up to it and not after
            n = self.cdf.shape[-1]
            for k in reversed(range(self.fine_steps)):
                cand = rank + (1 << k)
                below = self.cdf[jnp.minimum(cand, n) - 1] < u
                rank = jnp.where((cand <= n) & below, cand, rank)
        return rank


@dataclass(frozen=True)
class WorkloadConfig:
    num_keys: int = 1_000_000
    zipf_alpha: float = 0.99
    key_size: int = 16                  # bytes (paper default)
    # (size_bytes, fraction) pairs; fractions sum to 1.
    value_sizes: tuple[tuple[int, float], ...] = ((64, 0.82), (1024, 0.18))
    write_ratio: float = 0.0
    offered_rps: float = 4.0e6          # open-loop Tx rate (Poisson)
    seed: int = 0
    # Which random sample of the per-key size assignment to draw.  The
    # benchmark default (5) puts the hottest NetCache-uncacheable item at
    # popularity rank 2 — consistent with the paper's measured NetCache
    # saturation (~0.5x OrbitCache); an 18% large-value share makes a
    # top-3 uncacheable item the expected case.
    value_seed: int = 5


# Paper Fig. 14: Twitter-derived workloads A–E = Cluster045/016/044/017/020,
# characterized by (fraction of small 64-B values = NetCache-cacheable ratio,
# write ratio).  Values per the paper's description (A: 95% cacheable &
# relatively high write ratio; E: 1% cacheable).
PRODUCTION_WORKLOADS: dict[str, dict] = {
    "A": dict(small_frac=0.95, write_ratio=0.20),   # Cluster045
    "B": dict(small_frac=0.70, write_ratio=0.05),   # Cluster016
    "C": dict(small_frac=0.50, write_ratio=0.10),   # Cluster044
    "D": dict(small_frac=0.25, write_ratio=0.02),   # Cluster017
    "E": dict(small_frac=0.01, write_ratio=0.01),   # Cluster020
}


def production_workload(name: str, base: WorkloadConfig | None = None) -> WorkloadConfig:
    base = base or WorkloadConfig()
    p = PRODUCTION_WORKLOADS[name]
    sf = p["small_frac"]
    return replace(
        base,
        value_sizes=((64, sf), (1024, 1.0 - sf)),
        write_ratio=p["write_ratio"],
    )


class Workload:
    """Materialized workload: Zipf sampler + per-key value sizes + rank perm.

    The Zipf CDF reaches the device only where a table bucket holds more
    than one draw (``zipf_bits < 23``, fewer than 2**23 keys), for the
    fine search steps."""

    def __init__(self, cfg: WorkloadConfig):
        self.cfg = cfg
        n = cfg.num_keys
        self.probs = zipf_probs(n, cfg.zipf_alpha)
        cdf = np.cumsum(self.probs).astype(np.float32)
        table, self.zipf_fine_steps = zipf_inverse_table(cdf)
        self.zipf_bits = table.shape[0].bit_length() - 1
        self.zipf_table = jnp.asarray(table)
        self.cdf = jnp.asarray(cdf) if self.zipf_bits < UNIFORM_BITS else None
        # rank -> key identity; starts as identity, mutated by churn.
        self._perm_np = np.arange(n, dtype=np.int32)
        self.perm = jnp.asarray(self._perm_np)
        # per-key value size: deterministic hash -> size class
        h = hash128_u32_np(
            ((np.arange(n, dtype=np.int64) + cfg.value_seed * 1_000_003)
             .astype(np.int32)))[:, 0]
        u = (h.astype(np.float64) / 2**32)
        sizes = np.zeros(n, np.int32)
        lo = 0.0
        for size, frac in cfg.value_sizes:
            hi = lo + frac
            sizes[(u >= lo) & (u < hi)] = size
            lo = hi
        sizes[sizes == 0] = cfg.value_sizes[-1][0]
        self.vlen_np = sizes
        self.vlen = jnp.asarray(sizes)

    @property
    def arrays(self) -> WorkloadArrays:
        """Current device arrays (fresh after any churn)."""
        return WorkloadArrays(table=self.zipf_table, cdf=self.cdf,
                              perm=self.perm, vlen=self.vlen,
                              fine_steps=self.zipf_fine_steps)

    # -- sampling (jit-friendly) ---------------------------------------------
    def sample_ranks(self, rng: jax.Array, batch: int) -> jnp.ndarray:
        return self.arrays.ranks(jax.random.uniform(rng, (batch,), jnp.float32))

    def sample_keys(self, rng: jax.Array, batch: int) -> jnp.ndarray:
        return self.perm[self.sample_ranks(rng, batch)]

    # -- churn (host-side, Fig. 18) -------------------------------------------
    def hot_in_swap(self, n_hot: int = 128) -> None:
        """Swap the n_hot hottest ranks with the n_hot coldest (paper §5.3:
        'every 10 seconds, the popularity of the 128 coldest items and the
        128 hottest items is swapped')."""
        with span("repro.churn"):
            p = self._perm_np
            hot = p[:n_hot].copy()
            p[:n_hot] = p[-n_hot:]
            p[-n_hot:] = hot
            self.perm = jnp.asarray(p)

    def hottest_keys(self, k: int) -> np.ndarray:
        return self._perm_np[:k].copy()

    def head_coverage(self, k: int) -> float:
        """Fraction of requests served by the k hottest keys."""
        return float(self.probs[:k].sum())
