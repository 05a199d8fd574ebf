"""The Zipf inverse table draws exactly the ranks the binary search drew.

Client generation maps each float32 ``jax.random.uniform`` draw to a
popularity rank through ``WorkloadArrays.ranks``: one gather from
``zipf_inverse_table``'s ``int32[2**m]`` table, then ``fine_steps``
bounded search steps in the CDF where a table bucket holds more than one
draw.  It has to give ``jnp.searchsorted(cdf, u)`` (side left) on every
draw the sampler can make, which is what the benchmark's plain reference
computes.  That rests on the draws lying on the ``k * 2**-23`` grid,
which the last test pins.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kvstore.workload import (UNIFORM_BITS, Workload, WorkloadArrays,
                                    WorkloadConfig, zipf_inverse_table,
                                    zipf_probs)

PAPER_KEYS = 10_000_000


def _searchsorted(cdf, u):
    return jax.jit(jnp.searchsorted)(cdf, u)


def _ranks(arrays: WorkloadArrays, u):
    return jax.jit(WorkloadArrays.ranks)(arrays, u)


def test_paper_table_equals_searchsorted_on_every_draw():
    """Zipf 0.99 over 10M keys: a 2**23-entry table, no fine steps, and
    ``table[k] == searchsorted(cdf, k * 2**-23)`` for every k; the device
    sampler then needs no CDF and agrees with ``jnp.searchsorted``."""
    probs = zipf_probs(PAPER_KEYS, 0.99)
    cdf = np.cumsum(probs).astype(np.float32)
    # the program's CDF is the one the reference builds
    np.testing.assert_array_equal(
        cdf, np.asarray(jnp.asarray(np.cumsum(probs), jnp.float32)))
    table, fine = zipf_inverse_table(cdf)
    assert table.dtype == np.int32 and table.shape == (2**23,) and fine == 0
    grid = np.arange(2**23, dtype=np.float32) * np.float32(2.0**-23)
    np.testing.assert_array_equal(table, np.searchsorted(cdf, grid, side="left"))

    u = jax.random.uniform(jax.random.PRNGKey(7), (2**16,), jnp.float32)
    arrays = WorkloadArrays(table=jnp.asarray(table), cdf=None, perm=None,
                            vlen=None)
    np.testing.assert_array_equal(_ranks(arrays, u),
                                  _searchsorted(jnp.asarray(cdf), u))


def _edge_draws(bits: int) -> np.ndarray:
    """0, the first and last draw of every table bucket, and 1 - 2**-23."""
    j = np.arange(2**bits, dtype=np.int64) << (UNIFORM_BITS - bits)
    k = np.concatenate([j, j + (1 << (UNIFORM_BITS - bits)) - 1,
                        [0, 2**UNIFORM_BITS - 1]])
    return k.astype(np.float32) * np.float32(2.0**-UNIFORM_BITS)


@pytest.mark.parametrize("alpha", [0.9, 0.99, 1.2])
@pytest.mark.parametrize("keys", [1_000, 20_000])
def test_small_workload_sampler_equals_searchsorted(keys, alpha):
    """Fewer than 2**23 keys: a table no bigger than the CDF, a few fine
    steps in the CDF (and no fewer than the widest bucket needs), and the
    same rank as ``jnp.searchsorted`` on random and edge draws, through
    ``Workload.sample_ranks`` as well."""
    wl = Workload(WorkloadConfig(num_keys=keys, zipf_alpha=alpha))
    assert wl.zipf_bits == keys.bit_length() - 1
    assert wl.zipf_table.shape == (2**wl.zipf_bits,)
    assert 2**wl.zipf_bits <= keys and wl.zipf_fine_steps > 0
    assert wl.cdf is not None and wl.cdf.shape == (keys,)
    arrays = wl.arrays
    assert arrays.fine_steps == wl.zipf_fine_steps

    key = jax.random.PRNGKey(keys + int(alpha * 100))
    u = jnp.concatenate([
        jax.random.uniform(key, (2**16,), jnp.float32),
        jnp.asarray(_edge_draws(wl.zipf_bits))])
    want = _searchsorted(wl.cdf, u)
    np.testing.assert_array_equal(_ranks(arrays, u), want)
    short = dataclasses.replace(arrays, fine_steps=arrays.fine_steps - 1)
    assert not np.array_equal(_ranks(short, u), want)

    k2 = jax.random.fold_in(key, 1)
    np.testing.assert_array_equal(
        jax.jit(wl.sample_ranks, static_argnums=1)(k2, 4096),
        _searchsorted(wl.cdf, jax.random.uniform(k2, (4096,), jnp.float32)))


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_uniform_float32_draws_lie_on_the_table_grid(seed):
    """The table's premise: float32 ``jax.random.uniform`` draws are
    exactly ``k * 2**-23`` with k an integer in [0, 2**23)."""
    u = np.asarray(jax.random.uniform(jax.random.PRNGKey(seed), (2**18,),
                                      jnp.float32), np.float64)
    k = u * 2.0**UNIFORM_BITS
    off_grid = k[(k != np.floor(k)) | (k < 0) | (k >= 2**UNIFORM_BITS)]
    assert off_grid.size == 0, (
        "jax.random.uniform no longer draws float32 on the k * 2**-23 grid, "
        "so the Zipf inverse table (zipf_inverse_table) is no longer exact; "
        f"off-grid draws * 2**23: {off_grid[:5]}")
