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
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.hashing import hash128_u32_np
from repro.obs import span


class WorkloadArrays(NamedTuple):
    """The device-side workload state the jitted window step consumes.

    Kept separate from :class:`Workload` so it can be (a) passed as an
    explicit jit argument — host-side churn (``hot_in_swap``) is picked up
    without retracing — and (b) stacked/vmapped over a leading rack axis
    for batched multi-rack sweeps (``repro.kvstore.fleet``).
    """

    cdf: jnp.ndarray   # float32[num_keys] Zipf CDF over popularity ranks
    perm: jnp.ndarray  # int32[num_keys] rank -> key identity
    vlen: jnp.ndarray  # int32[num_keys] per-key value bytes


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
    """Materialized workload: Zipf CDF + per-key value sizes + rank perm."""

    def __init__(self, cfg: WorkloadConfig):
        self.cfg = cfg
        n = cfg.num_keys
        ranks = np.arange(1, n + 1, dtype=np.float64)
        w = ranks ** (-cfg.zipf_alpha)
        self.probs = w / w.sum()
        self.cdf = jnp.asarray(np.cumsum(self.probs), jnp.float32)
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
        return WorkloadArrays(cdf=self.cdf, perm=self.perm, vlen=self.vlen)

    # -- sampling (jit-friendly) ---------------------------------------------
    def sample_ranks(self, rng: jax.Array, batch: int) -> jnp.ndarray:
        u = jax.random.uniform(rng, (batch,), jnp.float32)
        return jnp.searchsorted(self.cdf, u).astype(jnp.int32)

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
