"""The main-path Pallas kernels compile through Mosaic for a TPU v5e.

Each test lowers and compiles one kernel for a described (not attached)
v5e chip, at the widths the paper rack hands it: a subround of the
rack's 1,344-lane window (336 lanes) against 128 entries with 8-slot
queues, the server tracker's 5 x 2,048 sketch vmapped over 32 servers,
and the controller's 2,048 report lanes (32 servers x top-64) with int32
rows.  One more compiles the servers' window under a 12-point sweep at
10M keys and reads how XLA lays out their store versions.  Nothing runs:
this guards Mosaic lowering and that layout at no chip time.

The topology is described inside a module-scoped fixture — never at
import — so every test worker collects the same tests and only the worker
that runs this file loads the TPU compiler.  The persistent compilation
cache is off around these compiles: an entry written for a described chip
cannot be read back without one.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels

LANES, ENTRIES, QUEUE, SERVES = 336, 128, 8, 8
SKETCH_W, SERVERS, BATCH = 2048, 32, 1344
REPORT_LANES = 32 * 64
FLEET = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def pallas(no_persistent_cache):
    kernels.set_kernel_backend("pallas")
    yield
    kernels.set_kernel_backend(None)


def _spec(sharding, shape, dtype=jnp.int32, fleet=None):
    shape = shape if fleet is None else (fleet,) + shape
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _subround_args(sharding, f, fleet=None):
    b, c, s = LANES, ENTRIES, QUEUE
    lane = lambda dt=jnp.int32: _spec(sharding, (b,), dt, fleet)
    ent = lambda w=1, dt=jnp.int32: _spec(sharding, (c * w,), dt, fleet)
    return [
        _spec(sharding, (b, 4), jnp.uint32, fleet),
        *[lane() for _ in range(10)], lane(jnp.float32),
        _spec(sharding, (c, 4), jnp.uint32, fleet), ent(), ent(), ent(),
        ent(s), ent(s), ent(s), ent(s, jnp.float32), ent(s), ent(s),
        ent(), ent(), ent(),
        ent(f), ent(f), ent(f), ent(f), ent(),
        _spec(sharding, (), jnp.int32, fleet),
    ]


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("f,fleet", [(1, None), (4, None), (1, FLEET),
                                     (4, FLEET)])
def test_subround_compiles(one_chip, pallas, f, fleet):
    fn = lambda *a: kernels.subround(*a, QUEUE, f, SERVES)
    if fleet is not None:
        fn = jax.vmap(fn)
    compiled = _compile(fn, *_subround_args(one_chip, f, fleet))
    assert "tpu_custom_call" in compiled.as_text()


def test_cms_compiles_at_tracker_widths(one_chip, pallas):
    fn = jax.vmap(lambda m, c, h: kernels.cms_update_query(h, m, c),
                  in_axes=(0, 0, None))
    compiled = _compile(
        fn, _spec(one_chip, (SERVERS, BATCH)),
        _spec(one_chip, (SERVERS, 5, SKETCH_W)),
        _spec(one_chip, (BATCH, 4), jnp.uint32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_ids,n_hot", [(ENTRIES, REPORT_LANES),
                                         (REPORT_LANES, REPORT_LANES),
                                         (REPORT_LANES, ENTRIES)])
def test_hot_gather_compiles_with_int32_rows(one_chip, pallas, n_ids, n_hot):
    compiled = _compile(
        kernels.hot_gather, _spec(one_chip, (n_ids,)),
        _spec(one_chip, (n_hot,)), _spec(one_chip, (n_hot, 1)))
    assert "tpu_custom_call" in compiled.as_text()


POINTS, KEYS, WINDOWS = 12, 10_000_000, 4
RACK_LANES = 1344          # a window's ingress: 768 requests, 320 replies, 256 fetches
_DUS = re.compile(r"= s32\[([0-9,]*)\]\S* dynamic-update-slice\(")


def test_server_versions_update_in_place(one_chip, no_persistent_cache):
    """The paper rack's servers (32 x FIFO 64, 10 serves a window) under
    ``vmap`` over a 12-point ladder and ``lax.scan`` over windows, with
    10M keys of versions in the donated carry: the version bump works in
    the carry's own layout.  A carry XLA has to copy into the scatter's
    layout and back every window shows up as temporary buffers of about
    all points' versions and as ``dynamic-update-slice``s of 10M-wide
    ``s32`` rows."""
    from repro.core.types import empty_batch
    from repro.kvstore.server import ServerConfig, init_servers, server_step

    cfg = ServerConfig(num_servers=32, queue_depth=64, cap_per_window=10,
                       value_pad=1438, max_frags=1)

    def chunk(st, pkts, to_server, flag, now):
        def one(st_i):
            def step(s, x):
                s, out = server_step(s, cfg, *x)
                return s, jnp.sum(out.replies.val, dtype=jnp.int32)
            return jax.lax.scan(step, st_i, (pkts, to_server, flag, now))
        return jax.vmap(one)(st)

    spec = lambda lead, s: _spec(one_chip, lead + s.shape, s.dtype)
    st = jax.tree.map(lambda s: spec((POINTS,), s),
                      jax.eval_shape(lambda: init_servers(cfg, KEYS)))
    pkts = jax.tree.map(lambda s: spec((WINDOWS,), s),
                        jax.eval_shape(lambda: empty_batch(RACK_LANES)))
    compiled = jax.jit(chunk, donate_argnums=(0,)).lower(
        st, pkts, _spec(one_chip, (WINDOWS, RACK_LANES), bool),
        _spec(one_chip, (WINDOWS, RACK_LANES)),
        _spec(one_chip, (WINDOWS,), jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < KEYS * 4
    widths = [math.prod(int(d) for d in m.group(1).split(",") if d)
              for m in _DUS.finditer(compiled.as_text())]
    assert not [w for w in widths if w >= KEYS], widths


NC_TABLE, NC_LIMIT = 32768, 64
_WHOLE = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([0-9,]*)\]\S* "
                    r"(copy|reshape|transpose|dynamic-update-slice)\(", re.M)
_WHILE = re.compile(r" while\([^\n]*?op_name=\"([^\"]*)\"")


@functools.cache
def _paper_workload():
    """The paper's workload (10M keys, Zipf 0.99), built once."""
    from repro.kvstore.workload import Workload, WorkloadConfig
    return Workload(WorkloadConfig(num_keys=KEYS, zipf_alpha=0.99))


def _ladder_chunk(sharding, scheme):
    """The paper rack's vmapped ladder chunk (12 points sharing the paper
    workload's arrays, the fleet's own jitted chunk) compiled for a
    described v5e."""
    from dataclasses import replace

    from repro.kvstore.fleet import compiled_batched_chunk
    from repro.kvstore.simulator import (RackConfig, init_carry,
                                         make_client_config,
                                         make_server_config)

    cfg = RackConfig(scheme=scheme, recirc_gbps=150.0, netcache_table=NC_TABLE,
                     netcache_value_limit=NC_LIMIT)
    scfg, ccfg = make_server_config(cfg), make_client_config(cfg)
    carry = jax.eval_shape(lambda: init_carry(cfg, scfg, ccfg, KEYS, 1e6, 0.0, 0))
    carry = jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype, POINTS), carry)
    arrays = _paper_workload().arrays
    wl = jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), arrays)
    chunk = compiled_batched_chunk(replace(cfg, seed=0), scfg, ccfg, 16, WINDOWS,
                                   jax.tree.map(lambda _: None, arrays))
    return chunk.lower(wl, carry).compile()


def test_ladder_chunk_draws_zipf_ranks_in_one_gather(one_chip, pallas):
    """At the paper's 10M keys each Zipf rank is one gather from the
    inverse table: the chunk's workload arguments hold no float32 CDF, the
    compiled program holds no 10M-entry float32 array, and client
    generation (``repro.gen``) runs no loop but ``jax.random.poisson``'s,
    where it once ran a 24-trip binary search of the CDF."""
    wl = _paper_workload()
    assert (wl.zipf_bits, wl.zipf_fine_steps) == (23, 0)
    leaves = jax.tree.leaves(wl.arrays)
    assert [(a.shape, a.dtype) for a in leaves] == [
        ((2**23,), jnp.int32), ((KEYS,), jnp.int32), ((KEYS,), jnp.int32)]
    text = _ladder_chunk(one_chip, "nocache").as_text()
    assert f"f32[{KEYS}]" not in text
    loops = _WHILE.findall(text)
    assert loops, "no while loop found: the chunk's window scan is one"
    gen = [n for n in loops if "/repro.gen/" in n and "_poisson" not in n]
    assert not gen, gen


def test_netcache_tables_update_in_place(one_chip, pallas):
    """NetCache's per-subround scatters (validity twice, version, length and
    value bytes) into the 12-point ladder's donated scan carry work in the
    carry's own layout: no copy, reshape, transpose or
    ``dynamic-update-slice`` of a whole table over all points, and no more
    temporary memory than the no-cache chunk's plus less than one value
    table."""
    compiled = _ladder_chunk(one_chip, "netcache")
    tables = {POINTS * NC_TABLE, POINTS * NC_TABLE * NC_LIMIT}
    whole = [(m.group(2), m.group(1)) for m in _WHOLE.finditer(compiled.as_text())
             if math.prod(int(d) for d in m.group(1).split(",") if d) in tables]
    assert not whole, whole
    base = _ladder_chunk(one_chip, "nocache")
    extra = (compiled.memory_analysis().temp_size_in_bytes
             - base.memory_analysis().temp_size_in_bytes)
    assert extra < POINTS * NC_TABLE * NC_LIMIT, extra
