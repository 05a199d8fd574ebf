"""OrbitCache-backed distributed KV service on a device mesh.

The full paper architecture as a TPU service: a value store hash-partitioned
across the ring devices (the "storage servers"), and the orbit ring
(``repro.core.distributed``) circulating the hot set.  Each service step,
every device submits a local batch of key lookups:

  hot hit   -> request-table enqueue; a visiting orbit line answers within
               <= D hops, no storage access, no all-to-all lane consumed;
  miss      -> routed to the key's owner shard over a fixed-quota
               ``all_to_all`` exchange (the "forward to server" path);
               quota overflow waits in a local spill queue — exactly the
               paper's overflow-to-server semantics, inverted for a
               lossless fabric.

The measurable claim (benchmarked in ``benchmarks/fig13_scalability.py``-
style sweeps and the dry-run): under Zipf-skewed keys the hot set absorbs
the head, so per-shard lookup load and all-to-all lane pressure stay
balanced — small cache, big effect, on ICI instead of a ToR switch.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import distributed as ring
from repro.core.hashing import hash128_u32
from repro.core.types import OP_R_REQ, PacketBatch


class ServiceConfig(NamedTuple):
    num_entries: int = 128       # hot-set size (small cache effect)
    queue_size: int = 8
    slice_len: int = 8           # orbit lines resident per device
    value_pad: int = 256
    local_batch: int = 64        # lookups per device per step
    a2a_quota: int = 16          # cold lanes per (src, dst) pair per step
    clones_per_visit: int = 4


class ServiceState(NamedTuple):
    ring: ring.RingState
    store_vals: jnp.ndarray      # [keys_local, value_pad] per device shard
    store_keys: jnp.ndarray      # [keys_local] global key ids


def init_service(cfg: ServiceConfig, num_keys: int, num_devices: int,
                 key_dtype=jnp.uint8) -> ServiceState:
    keys_local = num_keys // num_devices
    rs = ring.init_ring_state(
        cfg.num_entries, cfg.queue_size, cfg.slice_len, cfg.value_pad)
    # stacked per-device (callers shard dim 0 over the ring axes)
    stack = lambda x: jnp.broadcast_to(x, (num_devices,) + x.shape).copy()
    return ServiceState(
        ring=rs._replace(
            reqtab=jax.tree.map(stack, rs.reqtab),
            slice=jax.tree.map(stack, rs.slice),
            popularity=stack(rs.popularity),
            overflow=stack(rs.overflow),
            hits=stack(rs.hits),
        ),
        store_vals=jnp.zeros((num_devices, keys_local, cfg.value_pad), key_dtype),
        store_keys=(jnp.arange(num_keys, dtype=jnp.int32)
                    .reshape(num_devices, keys_local)),
    )


def owner_of(key: jnp.ndarray, num_devices: int, keys_local: int):
    return key // keys_local, key % keys_local


def service_step_local(st: ServiceState, keys: jnp.ndarray,
                       mask: jnp.ndarray, cfg: ServiceConfig, axis_names):
    """Per-device body (under shard_map).  keys: int32[local_batch];
    mask: bool[local_batch] (idle lanes carry no request).

    Returns (state', values [local_batch, pad], served mask, hot mask).
    """
    ax = axis_names if isinstance(axis_names, tuple) else (axis_names,)
    d = 1
    for a in ax:
        d *= jax.lax.axis_size(a)
    keys_local = st.store_keys.shape[-1]
    b = keys.shape[0]

    # 1) hot path through the orbit ring
    pk = PacketBatch(
        op=jnp.full((b,), OP_R_REQ, jnp.int32),
        seq=jnp.arange(b, dtype=jnp.int32),
        hkey=hash128_u32(keys),
        flag=jnp.zeros((b,), jnp.int32),
        kidx=keys,
        vlen=jnp.zeros((b,), jnp.int32),
        client=jnp.zeros((b,), jnp.int32),
        port=jnp.zeros((b,), jnp.int32),
        server=jnp.zeros((b,), jnp.int32),
        ts=jnp.zeros((b,), jnp.float32),
        valid=mask,
        val=jnp.zeros((b, cfg.value_pad), jnp.uint8),
    )
    rst, serve = ring.ring_step(st.ring, pk, cfg.clones_per_visit, ax)

    # 2) cold path: quota'd all-to-all to owner shards
    owner, local_idx = owner_of(keys, d, keys_local)
    miss = serve.miss & mask
    onehot = (owner[:, None] == jnp.arange(d)[None, :]) & miss[:, None]
    rank = jnp.cumsum(onehot, axis=0) - onehot
    lane = jnp.take_along_axis(rank, owner[:, None], axis=1)[:, 0]
    within_quota = miss & (lane < cfg.a2a_quota)

    q = cfg.a2a_quota
    req_buf = jnp.full((d, q), 0, jnp.int32)
    src_slot = jnp.full((d, q), -1, jnp.int32)
    dest = jnp.where(within_quota, owner * q + lane, d * q)
    req_buf = req_buf.reshape(-1).at[dest].set(local_idx, mode='drop').reshape(d, q)
    src_slot = src_slot.reshape(-1).at[dest].set(
        jnp.arange(b, dtype=jnp.int32), mode='drop').reshape(d, q)
    # exchange requests: [d, q] -> owner receives [d, q] (src-major)
    ax_a2a = ax if len(ax) > 1 else ax[0]
    got_idx = jax.lax.all_to_all(req_buf, ax_a2a, 0, 0, tiled=True)
    got_idx = got_idx.reshape(d, q)
    vals_out = st.store_vals[jnp.clip(got_idx, 0, keys_local - 1)]  # local shard
    # send values back
    back = jax.lax.all_to_all(vals_out.reshape(d * q, cfg.value_pad)
                              .reshape(d, q, cfg.value_pad),
                              ax_a2a, 0, 0, tiled=True)
    back = back.reshape(d, q, cfg.value_pad)

    # scatter cold values into the local result
    res = jnp.zeros((b, cfg.value_pad), jnp.uint8)
    flat_back = back.reshape(d * q, cfg.value_pad)
    flat_slot = src_slot.reshape(d * q)
    res = res.at[jnp.where(flat_slot >= 0, flat_slot, b)].set(
        flat_back, mode='drop')

    # hot values: requests answered by the ring this step get the line value
    # (requests still queued are answered on later steps as lines rotate)
    hot_mask = ~miss & mask
    new_state = ServiceState(ring=rst, store_vals=st.store_vals,
                             store_keys=st.store_keys)
    return new_state, res, within_quota, hot_mask, serve


def make_service_step(mesh, axis_names, cfg: ServiceConfig):
    """shard_map-wrapped service step for the production mesh."""
    ax = axis_names if isinstance(axis_names, tuple) else (axis_names,)
    spec = P(ax)
    rspec = ring.RingState(
        lookup=ring.LookupTable(hkeys=P(), occupied=P(), kidx=P()),
        state=ring.StateTable(valid=P(), version=P()),
        reqtab=ring.RequestTable(*([spec] * len(ring.RequestTable._fields))),
        slice=ring.OrbitSlice(*([spec] * len(ring.OrbitSlice._fields))),
        popularity=spec, overflow=spec, hits=spec,
    )
    sspec = ServiceState(ring=rspec, store_vals=spec, store_keys=spec)
    serve_spec = ring.RingServe(*([spec] * len(ring.RingServe._fields)))

    # The replication check is off: it cannot see through the manual
    # squeeze/unsqueeze of the ring axis.
    @partial(jax.shard_map, mesh=mesh,
             in_specs=(sspec, spec, spec),
             out_specs=(sspec, spec, spec, spec, serve_spec), check_vma=False)
    def step(st: ServiceState, keys, mask):
        sq = lambda t: jax.tree.map(
            lambda s, x: x.reshape(x.shape[1:]) if s == spec else x, t[0], t[1])
        st_l = sq((sspec, st))
        keys_l = keys.reshape(keys.shape[1:])
        mask_l = mask.reshape(mask.shape[1:])
        st2, res, cold, hot, serve = service_step_local(
            st_l, keys_l, mask_l, cfg, ax)
        un = lambda t: jax.tree.map(
            lambda s, x: x.reshape((1,) + x.shape) if s == spec else x, t[0], t[1])
        return (un((sspec, st2)), res[None], cold[None], hot[None],
                un((serve_spec, serve)))

    return step
