#!/usr/bin/env python3
"""Chip smoke test: the simulator's main path end to end on a TPU.

Run from the root of a checkout:

    python chip_smoke.py              # one chip: rack, controller, fabric
    python chip_smoke.py --chips 4    # four chips: the orbit-ring service

One chip, three phases in one process.  Each phase runs once on the
``pallas`` kernel backend and once on ``ref`` (the pure-jnp oracles) with
the same seeds, and asserts that every trace array and every leaf of the
final carry are bit-identical between the two.

* rack: a ``BatchedRackSimulator`` of the paper rack
  (``configs/orbitcache_paper.py``: 10M keys, Zipf 0.99, 64/1024-B
  values, 128 entries, 32 servers x 100K RPS) with 8 sweep points whose
  offered load spans 0.5x-2x the rack's 3.2M RPS server capacity, under
  each of orbitcache, netcache and nocache.
* controller: the orbitcache fleet with server popularity tracking and
  ``controller_period_s`` set, so ``subround``, ``cms`` and ``hot_gather``
  all run inside the compiled period scan.
* fabric: a ``BatchedFabricSimulator`` of 4 paper racks under an
  orbitcache spine at ``local_frac`` 0.5.

``--chips 4`` runs only the orbit-ring KV service
(``serving/orbit_service.py``) on a 4-chip mesh with a store of 2^20 keys,
and checks it against host-side expectations: byte-exact cold values,
every hot request served exactly once within a revolution, and the store
and ring state split across all four devices.

Each phase prints its device kind, kernel backend, compile and run
seconds, simulated requests, hit ratio (switch-served share of delivered
replies), parity result and ``peak_bytes_in_use``.  The last line of
stdout is one JSON object.  With no TPU the script exits non-zero before
it simulates anything, and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.launch.compile_cache import configure_compile_cache  # noqa: E402

SEED = 0
CHUNK = 16            # windows per compiled chunk (the preload warm-up too)
RUN_CHUNKS = 3        # chunks after the first, timed as run seconds
PERIOD_W = 64         # controller period, windows
N_PERIODS = 3
N_LOADS = 8
FABRIC_RACKS = 4
FABRIC_RPS = (0.5e6, 1.0e6)   # per rack; remote lanes fit the spine ingress
RING_KEYS = 1 << 20


class CompileClock:
    """Sums JAX's tracing, lowering and backend-compile durations."""

    EVENT_PREFIX = "/jax/core/compile/"

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration_secs, **kwargs):
        if event.startswith(self.EVENT_PREFIX):
            self.seconds += duration_secs


def host_leaves(tree):
    """Every leaf of a pytree as a numpy array (PRNG keys as key data)."""
    import jax
    import numpy as np

    out = []
    for x in jax.tree.leaves(tree):
        if jax.dtypes.issubdtype(getattr(x, "dtype", None),
                                 jax.dtypes.prng_key):
            x = jax.random.key_data(x)
        out.append(np.asarray(x))
    return out


def assert_identical(phase, a, b):
    """Bit-identity of two (traces, carry leaves) results."""
    import numpy as np

    traces_a, leaves_a = a
    traces_b, leaves_b = b
    if traces_a.keys() != traces_b.keys() or len(leaves_a) != len(leaves_b):
        raise AssertionError(f"{phase}: pallas and ref return different "
                             "trace names or carry structures")
    for k in traces_a:
        if not np.array_equal(traces_a[k], traces_b[k]):
            raise AssertionError(f"{phase}: trace {k!r} differs "
                                 "between pallas and ref")
    as_bytes = lambda x: np.ascontiguousarray(x).reshape(-1).view(np.uint8)
    for i, (x, y) in enumerate(zip(leaves_a, leaves_b)):
        if x.dtype != y.dtype or x.shape != y.shape or not np.array_equal(
                as_bytes(x), as_bytes(y)):
            raise AssertionError(f"{phase}: carry leaf {i} {x.dtype}{x.shape} "
                                 "differs between pallas and ref")
    return len(traces_a), len(leaves_a)


def concat_traces(chunks, axis):
    import numpy as np
    return {k: np.concatenate([c[k] for c in chunks], axis=axis)
            for k in chunks[0]}


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name, drive, device, clock):
    """``drive()`` on the pallas backend, then on ref; assert bit-identity.

    ``drive`` returns ``(traces, carry, run_s, requests, hit_ratio)``.
    """
    from repro import kernels

    results = {}
    for backend in ("pallas", "ref"):
        kernels.set_kernel_backend(backend)
        try:
            clock.seconds = 0.0
            t0 = time.perf_counter()
            traces, carry, run_s, requests, hit_ratio = drive()
            wall = time.perf_counter() - t0
            results[backend] = (traces, host_leaves(carry))
            del carry
            gc.collect()
        finally:
            kernels.set_kernel_backend(None)
        print(f"[{name}] device={device.device_kind} backend={backend} "
              f"compile_s={clock.seconds!r} run_s={run_s!r} wall_s={wall!r} "
              f"requests={requests} hit_ratio={hit_ratio!r} "
              f"peak_bytes_in_use={peak_bytes(device)}", flush=True)
    n_tr, n_leaves = assert_identical(name, results["pallas"], results["ref"])
    print(f"[{name}] parity=bit-identical traces={n_tr} "
          f"carry_leaves={n_leaves}", flush=True)


def _rack_summary(traces):
    import numpy as np
    sw = int(np.sum(traces["rx_switch"], dtype=np.int64))
    srv = int(np.sum(traces["rx_server"], dtype=np.int64))
    return int(np.sum(traces["tx"], dtype=np.int64)), sw / max(sw + srv, 1)


def rack_drive(cfg, wl, loads, *, controller: bool):
    """A preloaded fleet: one untimed chunk, then timed chunks — or, with
    ``controller``, two runs of whole controller periods, the second
    timed."""
    import jax
    import numpy as np
    from repro.kvstore.fleet import BatchedRackSimulator

    def drive():
        fleet = BatchedRackSimulator(cfg, wl, offered_rps=loads)
        fleet.preload()
        if controller:
            period_s = PERIOD_W * cfg.window_us * 1e-6

            def run():
                res = fleet.run(N_PERIODS * period_s,
                                controller_period_s=period_s)
                return {k: np.stack([r.traces[k] for r in res])
                        for k in res[0].traces}

            chunks = [run()]
            t0 = time.perf_counter()
            chunks.append(run())
            run_s = time.perf_counter() - t0
            carry = (fleet.carry, fleet._last_update,
                     np.asarray([c.active_size for c in fleet.controllers]))
        else:
            chunks = [fleet.run_windows(CHUNK)]
            t0 = time.perf_counter()
            for _ in range(RUN_CHUNKS):
                chunks.append(fleet.run_windows(CHUNK))
            run_s = time.perf_counter() - t0
            carry = fleet.carry
        jax.block_until_ready(fleet.carry)
        traces = concat_traces(chunks, axis=1)
        requests, hit_ratio = _rack_summary(traces)
        return traces, carry, run_s, requests, hit_ratio

    return drive


def fabric_drive(cfg, fcfg, wl):
    """A preloaded batched fabric: one untimed chunk, then timed chunks."""
    import numpy as np
    from repro.kvstore.fleet import BatchedFabricSimulator

    def drive():
        fab = BatchedFabricSimulator(cfg, fcfg, wl,
                                     local_fracs=[fcfg.local_frac] * 2,
                                     offered_rps=list(FABRIC_RPS))
        fab.preload(warm_windows=CHUNK)
        chunks = [fab.run_windows(CHUNK)]
        t0 = time.perf_counter()
        for _ in range(RUN_CHUNKS):
            chunks.append(fab.run_windows(CHUNK))
        run_s = time.perf_counter() - t0
        traces = concat_traces(chunks, axis=1)
        s64 = lambda k: int(np.sum(traces[k], dtype=np.int64))
        switch = s64("rack_rx_switch") + s64("spine_served")
        hit_ratio = switch / max(switch + s64("rack_rx_server"), 1)
        return traces, fab.carry, run_s, s64("rack_tx"), hit_ratio

    return drive


def one_chip(device, clock):
    """The rack, controller and fabric phases, pallas vs ref."""
    import numpy as np
    from repro.configs import orbitcache_paper as paper
    from repro.kvstore.fabric_sim import FabricConfig
    from repro.kvstore.workload import Workload

    t0 = time.perf_counter()
    wl = Workload(replace(paper.WORKLOAD, seed=SEED))
    rack = replace(paper.RACK, seed=SEED)
    capacity = rack.num_servers * rack.server_rps
    loads = list(np.linspace(0.5, 2.0, N_LOADS) * capacity)
    print(f"[setup] workload keys={wl.cfg.num_keys} "
          f"setup_s={time.perf_counter() - t0!r} loads_rps={loads}",
          flush=True)

    for scheme in ("orbitcache", "netcache", "nocache"):
        run_phase(f"rack/{scheme}",
                  rack_drive(replace(rack, scheme=scheme), wl, loads,
                             controller=False), device, clock)
    run_phase("controller",
              rack_drive(replace(rack, scheme="orbitcache",
                                 track_popularity=True), wl, loads,
                         controller=True), device, clock)
    fcfg = FabricConfig(n_racks=FABRIC_RACKS, local_frac=0.5,
                        spine_scheme="orbitcache")
    run_phase("fabric", fabric_drive(replace(rack, scheme="orbitcache"),
                                     fcfg, wl), device, clock)


def ring_service(devices, clock, num_keys: int = RING_KEYS):
    """The orbit-ring KV service on a mesh of ``devices``.

    Hot keys ``0..H-1`` (two orbit lines per device) are requested once by
    every device; the other lanes look up cold keys.  Checks: cold values
    byte-exact, every hot request served exactly once within one
    revolution with the line's exact bytes, and every sharded leaf of the
    store and ring state split over all devices.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.core.hashing import hash128_u32_np
    from repro.serving import orbit_service as svc

    n = len(devices)
    mesh = jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,),
                         devices=devices)
    cfg = svc.ServiceConfig()
    pad, b = cfg.value_pad, cfg.local_batch
    keys_local = num_keys // n
    hot = 2 * n
    sharded = NamedSharding(mesh, P("data"))

    t0 = time.perf_counter()
    st = svc.init_service(cfg, num_keys, n)
    # the store: value bytes of key k are k % 251, built on its shards
    store = jax.jit(
        lambda: jnp.broadcast_to(
            (jnp.arange(num_keys, dtype=jnp.int32) % 251).astype(jnp.uint8)
            .reshape(n, keys_local, 1), (n, keys_local, pad)),
        out_shardings=sharded)()
    keys = np.arange(hot, dtype=np.int32)
    rs = st.ring
    lookup = rs.lookup._replace(
        hkeys=rs.lookup.hkeys.at[:hot].set(jnp.asarray(hash128_u32_np(keys))),
        occupied=rs.lookup.occupied.at[:hot].set(True),
        kidx=rs.lookup.kidx.at[:hot].set(jnp.asarray(keys)))
    state = rs.state._replace(valid=rs.state.valid.at[:hot].set(True))
    live = np.zeros((n, cfg.slice_len), bool)
    cidx = np.full((n, cfg.slice_len), -1, np.int32)
    vlen = np.zeros((n, cfg.slice_len), np.int32)
    sval = np.zeros((n, cfg.slice_len, pad), np.uint8)
    for h in range(hot):
        d, slot = h % n, h // n
        live[d, slot], cidx[d, slot], vlen[d, slot] = True, h, pad
        sval[d, slot] = h % 251
    st = st._replace(store_vals=store, ring=rs._replace(
        lookup=lookup, state=state, slice=rs.slice._replace(
            live=jnp.asarray(live), cidx=jnp.asarray(cidx),
            kidx=jnp.asarray(cidx), vlen=jnp.asarray(vlen),
            val=jnp.asarray(sval))))

    rng = np.random.default_rng(SEED)
    req = np.zeros((n, b), np.int32)
    req[:, :hot] = keys
    req[:, hot:] = rng.integers(hot, num_keys, (n, b - hot))
    step = jax.jit(svc.make_service_step(mesh, ("data",), cfg))
    print(f"[ring] setup_s={time.perf_counter() - t0!r}", flush=True)

    clock.seconds = 0.0
    t0 = time.perf_counter()
    st, res, cold, hot_mask, serve = step(st, jnp.asarray(req),
                                          jnp.ones((n, b), bool))
    jax.block_until_ready(st)
    first_s = time.perf_counter() - t0
    res, cold = np.asarray(res), np.asarray(cold)
    n_cold = int(cold.sum())
    want = (req % 251).astype(np.uint8)[:, :, None]
    bad = cold[:, :, None] & (res != want)
    if bad.any():
        raise AssertionError(f"ring: {int(bad.any(-1).sum())} cold lookups "
                             "returned wrong bytes")
    if not np.asarray(hot_mask)[:, :hot].all():
        raise AssertionError("ring: a hot key missed the cache")

    served = np.zeros((n, cfg.num_entries), np.int64)
    empty = jnp.zeros((n, b), jnp.int32)
    idle = jnp.zeros((n, b), bool)
    t0 = time.perf_counter()
    for i in range(n + 1):
        if i:
            st, _, _, _, serve = step(st, empty, idle)
        got = np.asarray(serve.served)                 # [n, C, J]
        served += got.sum(axis=2)
        line_val = np.asarray(serve.val)               # [n, C, pad]
        line_kidx = np.asarray(serve.kidx)             # [n, C]
        for d, c in zip(*np.nonzero(got.any(axis=2))):
            if line_kidx[d, c] != c or (line_val[d, c] != c % 251).any():
                raise AssertionError(f"ring: device {d} served entry {c} "
                                     "with the wrong line")
    jax.block_until_ready(st)
    run_s = time.perf_counter() - t0
    expect = np.zeros_like(served)
    expect[:, :hot] = 1
    if not np.array_equal(served, expect):
        raise AssertionError(f"ring: hot serves per (device, entry) "
                             f"{served[:, :hot].tolist()}, expected one each")

    placement = {}
    for name, leaf in (("store_vals", st.store_vals),
                       ("store_keys", st.store_keys),
                       ("ring.slice.val", st.ring.slice.val),
                       ("ring.reqtab.client", st.ring.reqtab.client)):
        shards = leaf.addressable_shards
        devs = {s.device.id for s in shards}
        if len(devs) != n or any(s.data.shape[0] != 1 for s in shards):
            raise AssertionError(f"ring: {name} is not split over {n} "
                                 f"devices: {leaf.sharding}")
        placement[name] = [int(s.data.nbytes) for s in shards]
    for d in devices:
        print(f"[ring] device={d.id} kind={d.device_kind} "
              f"peak_bytes_in_use={peak_bytes(d)}", flush=True)
    print(f"[ring] devices={n} keys={num_keys} compile_s={clock.seconds!r} "
          f"first_step_s={first_s!r} run_s={run_s!r} steps={n + 1} "
          f"cold_verified={n_cold} hot_served={int(served.sum())} "
          f"shard_bytes={placement}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: rack/controller/fabric phases; "
                         "4: the orbit-ring service on a 4-chip mesh")
    args = ap.parse_args()

    configure_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    print(f"[device] platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)} "
          f"jax={jax.__version__}", flush=True)
    if args.chips == 4:
        ring_service(devices[:4], clock)
    else:
        one_chip(devices[0], clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
