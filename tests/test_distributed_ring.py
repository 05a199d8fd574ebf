"""Distributed orbit ring on 8 host devices (separate process: the device-
count flag must be set before jax initializes)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as dist
from repro.core.hashing import hash128_u32, hash128_u32_np
from repro.core.types import OP_R_REQ, OP_NONE, PacketBatch

D, C, S, L, PAD, B = 8, 16, 4, 4, 64, 8
mesh = jax.make_mesh((D,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
st0 = dist.init_ring_state(C, S, L, PAD)
st = st0._replace(
    reqtab=jax.tree.map(lambda x: jnp.broadcast_to(x, (D,)+x.shape).copy(), st0.reqtab),
    slice=jax.tree.map(lambda x: jnp.broadcast_to(x, (D,)+x.shape).copy(), st0.slice),
    popularity=jnp.zeros((D, C), jnp.int32),
    overflow=jnp.zeros((D,), jnp.int32),
    hits=jnp.zeros((D,), jnp.int32),
)
keys = np.arange(4, dtype=np.int32)
hk = hash128_u32_np(keys)
st = st._replace(
    lookup=st0.lookup._replace(
        hkeys=st0.lookup.hkeys.at[:4].set(jnp.asarray(hk)),
        occupied=st0.lookup.occupied.at[:4].set(True),
        kidx=st0.lookup.kidx.at[:4].set(jnp.asarray(keys))),
    state=st0.state._replace(valid=st0.state.valid.at[:4].set(True)),
)
live = np.zeros((D, L), bool); cidx = np.full((D, L), -1, np.int32)
kidx = np.full((D, L), -1, np.int32); vlen = np.zeros((D, L), np.int32)
val = np.zeros((D, L, PAD), np.uint8)
for d in range(4):
    live[d,0]=True; cidx[d,0]=d; kidx[d,0]=d; vlen[d,0]=32; val[d,0,:32]=d+1
st = st._replace(slice=st.slice._replace(
    live=jnp.asarray(live), cidx=jnp.asarray(cidx), kidx=jnp.asarray(kidx),
    vlen=jnp.asarray(vlen), val=jnp.asarray(val)))
op = np.full((D, B), OP_NONE, np.int32); op[:, :4] = OP_R_REQ
kq = np.zeros((D, B), np.int32); kq[:, :4] = np.arange(4)
pk = PacketBatch(
    op=jnp.asarray(op), seq=jnp.arange(D*B, dtype=jnp.int32).reshape(D,B),
    hkey=hash128_u32(jnp.asarray(kq)), flag=jnp.zeros((D,B), jnp.int32),
    kidx=jnp.asarray(kq), vlen=jnp.full((D,B),32,jnp.int32),
    client=jnp.zeros((D,B),jnp.int32), port=jnp.zeros((D,B),jnp.int32),
    server=jnp.zeros((D,B),jnp.int32), ts=jnp.zeros((D,B),jnp.float32),
    valid=jnp.asarray(op==OP_R_REQ), val=jnp.zeros((D,B,PAD),jnp.uint8),
)
step = jax.jit(dist.make_ring_step(mesh, ("data",), clones_per_visit=4))
empty = jax.tree.map(lambda x: jnp.zeros_like(x), pk)
st_, serve = step(st, pk)
total = int(serve.served.sum())
vals_seen = []
for hop in range(D):
    st_, serve = step(st_, empty)
    total += int(serve.served.sum())
    sv = np.asarray(serve.val); sk = np.asarray(serve.served)
    for d in range(D):
        for c in range(4):
            if sk[d, c].any():
                vals_seen.append((c, sv[d, c, 0]))
assert total == D * 4, f"served {total} != {D*4}"
# value payload correctness: entry c serves byte c+1
for c, byte in vals_seen:
    assert byte == c + 1, (c, byte)
# requests never recirculate: overflow==0, queues drained
assert int(st_.reqtab.qlen.sum()) == 0
print("RING_OK")
"""


@pytest.mark.slow
def test_ring_full_revolution_serves_all(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "RING_OK" in p.stdout, p.stderr[-3000:]
