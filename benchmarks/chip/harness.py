"""Run one cell once: set-up, a timed window, then the check against the
plain reference, and (traced) the per-layer metrics.

The timed path is the entry the figures drive: ``BatchedRackSimulator.run``
called one chunk at a time, with the host-side hot-in swap between chunks
where the traffic mix asks for churn.  The window keeps calling until
``seconds`` have passed; the rate counts every timed chunk, including the
one that ends after the deadline, over the wall time up to that chunk's
return (its traces are on the host by then).
"""
from __future__ import annotations

import contextlib
import gc
import sys
import tempfile
import time
from typing import NamedTuple

import numpy as np

import compare
import layout
import reduce as trace_reduce

SPAN_RUN = "bench.run"
SPAN_CHURN = "bench.churn"


class CompileClock:
    """Counts and sums JAX's tracing, lowering and compile events."""

    EVENT_PREFIX = "/jax/core/compile/"

    def __init__(self):
        self.seconds = 0.0
        self.events = 0

    def __call__(self, event, duration_secs, **kwargs):
        if event.startswith(self.EVENT_PREFIX):
            self.seconds += duration_secs
            self.events += 1


def point_seeds(seed: int, n: int) -> list[int]:
    """Per-point PRNG seeds drawn from the run's seed (any size)."""
    state = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(s) & 0x7FFFFFFF for s in state]


def check_points(traffic: dict, seed: int) -> list[int]:
    """The sweep points the reference replays: ``check_points`` of them
    (all when absent), the most loaded one always, the rest drawn from
    the seed.  More than half of the points, so that a fault which
    simulates only half of them and copies their results to the rest
    always leaves one checked point wrong."""
    loads = traffic["offered_rps"]
    n = len(loads)
    k = min(traffic.get("check_points") or n, n)
    if k <= n // 2:
        raise ValueError(f"check_points {k} of {n} points: the check needs more than half")
    top = int(np.argmax(loads))
    rest = [i for i in range(n) if i != top]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    return sorted([top] + [int(i) for i in rng.choice(rest, k - 1, replace=False)])


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
class Program:
    """The simulator as its users drive it: a fleet of sweep points."""

    def __init__(self, cell: layout.Cell, seeds: list[int]):
        from repro.kvstore.fleet import BatchedRackSimulator
        from repro.kvstore.simulator import RackConfig
        from repro.kvstore.workload import Workload, WorkloadConfig

        w, tr = cell.config["workload"], cell.traffic
        self.traffic = tr
        self.scheme = cell.config["rack"]["scheme"]
        self.wl = Workload(WorkloadConfig(
            num_keys=w["num_keys"], zipf_alpha=w["zipf_alpha"],
            key_size=w["key_size"],
            value_sizes=tuple(tuple(x) for x in w["value_sizes"]),
            write_ratio=tr["write_ratio"], offered_rps=tr["offered_rps"][0],
            value_seed=w["value_seed"]))
        rack = RackConfig(**cell.config["rack"],
                          track_popularity=bool(tr.get("track_popularity")))
        self.fleet = BatchedRackSimulator(
            rack, self.wl, offered_rps=tr["offered_rps"],
            write_ratios=tr["write_ratio"], seeds=seeds)
        period = tr.get("controller_period_windows")
        self.period_s = period * rack.window_us * 1e-6 if period else None
        self.chunk_s = tr["chunk_windows"] * rack.window_us * 1e-6

    def preload(self):
        if self.traffic["preload"]:
            self.fleet.preload()

    def chunk(self, first: bool) -> dict:
        """One chunk; ``{trace name: [points, windows, ...]}``."""
        import jax

        swap = self.traffic.get("churn_swap", 0)
        if swap and not first:
            with jax.profiler.TraceAnnotation(SPAN_CHURN):
                self.wl.hot_in_swap(swap)
                self.fleet.refresh_workloads()
        with jax.profiler.TraceAnnotation(SPAN_RUN):
            res = self.fleet.run(self.chunk_s,
                                 chunk_windows=self.traffic["chunk_windows"],
                                 controller_period_s=self.period_s)
        return {k: np.stack([r.traces[k] for r in res]) for k in res[0].traces}

    def outputs(self, chunks: list[dict], points) -> list[dict]:
        """What the comparison reads of each point in ``points``: the timed
        chunks' traces, the final state (copied to the host once) and the
        controller's updates of the last chunk."""
        import jax
        host = jax.device_get(self.fleet.carry)
        return [dict(traces=[{k: v[i] for k, v in c.items()} for c in chunks],
                     state=self.state(host, i), control=self.control(i))
                for i in points]

    def state(self, c, i: int) -> dict:
        """Point ``i`` of a host copy of the fleet's state, under the
        reference's names."""
        import jax

        take = lambda x: np.asarray(x[i])
        out = dict(layout.scheme(self.scheme).program_state(c.policy))
        sv = c.servers
        out.update({f"servers.{k}": getattr(sv, k) for k in (
            "op", "kidx", "seq", "client", "port", "flag", "vlen", "ts", "qlen",
            "front", "rear", "key_version", "served", "dropped")})
        out.update({"servers.cms": sv.tracker.cms.counts,
                    "servers.cand_kidx": sv.tracker.cand.kidx,
                    "servers.cand_est": sv.tracker.cand.est})
        out.update({f"clients.{k}": v for k, v in c.clients._asdict().items()})
        for q in ("pending", "fetch"):
            out.update({f"{q}.{k}": v for k, v in getattr(c, q)._asdict().items()})
        rng = c.rng
        if jax.dtypes.issubdtype(rng.dtype, jax.dtypes.prng_key):
            rng = jax.random.key_data(rng)
        out.update(rng=rng, now=c.now, offered=c.offered, write_ratio=c.write_ratio)
        return {k: take(v) for k, v in out.items()}

    def control(self, i: int) -> dict:
        """The controller's updates of the last chunk (churn mixes only)."""
        if not self.period_s or not hasattr(self.fleet, "_last_update"):
            return {}
        out = layout.scheme(self.scheme).program_update(self.fleet._last_update, i)
        out["update.active_size"] = np.asarray(self.fleet.controllers[i].active_size)
        return out


# ---------------------------------------------------------------------------
# the plain reference, replayed on the same cadence
# ---------------------------------------------------------------------------
def replay(cell: layout.Cell, seeds, n_timed: int, points, tdt="float32") -> list:
    """Replay each point in ``points`` through the reference: preload, the
    untimed chunk, then ``n_timed`` chunks; churn before every chunk after
    the first.  Returns the timed chunks' traces and the final state."""
    import reference as ref

    w, tr = cell.config["workload"], cell.traffic
    rack = dict(cell.config["rack"], track_popularity=bool(tr.get("track_popularity")))
    g = ref.geometry(rack, w["key_size"], tdt)
    wl = ref.RefWorkload(w["num_keys"], w["zipf_alpha"], w["value_sizes"],
                         w["value_seed"])
    period = tr.get("controller_period_windows")
    periodic = bool(period) and ref.scheme(g).CONTROLLER
    out = []
    for i in points:
        wl.perm_np = np.arange(w["num_keys"], dtype=np.int32)
        rack_i = ref.RefRack(g, wl, tr["offered_rps"][i], tr["write_ratio"], seeds[i])
        if tr["preload"]:
            rack_i.preload(tr["warm_windows"])
        traces = []
        for k in range(n_timed + 1):
            if k and tr.get("churn_swap", 0):
                wl.swap_hot_cold(tr["churn_swap"])
            if periodic:
                t = rack_i.run_periods(tr["chunk_windows"] // period, period)
            else:
                t = rack_i.run_windows(tr["chunk_windows"])
            if k:
                traces.append(t)
        control = {}
        if periodic:
            control = ref.scheme(g).update_lanes(
                rack_i.updates[-(tr["chunk_windows"] // period):], g)
            control["update.active_size"] = np.asarray(rack_i.active)
        state = {k: np.asarray(v) for k, v in compare.flatten(rack_i.st).items()}
        out.append(dict(traces=traces, state=state, control=control))
        del rack_i
    return out


# ---------------------------------------------------------------------------
# per-layer metrics from the trace
# ---------------------------------------------------------------------------
class Context(NamedTuple):
    """What a per-layer metric reader sees."""
    cell: layout.Cell
    trace: trace_reduce.Reduced
    windows: int                 # fleet windows in the traced chunks
    shapes: dict
    peaks: dict

    def kernel_us_per_window(self, kernel: str):
        ns = self.trace.kernel_ns(layout.kernel_counter(kernel).TRACE_NAMES)
        return ns / self.windows / 1e3 if ns > 0 else None

    def roofline(self, kernel: str):
        """Least time the chip could take for the kernel's counted work
        (the larger of operations over peak and bytes over HBM bandwidth)
        over its measured time, in percent."""
        k = layout.kernel_counter(kernel)
        work = k.per_window(self.shapes)
        ns = self.trace.kernel_ns(k.TRACE_NAMES)
        if not work or ns <= 0:
            return None
        t_ops = work["ops"] / self.peaks[work["ops_peak"]] if work["ops_peak"] else 0.0
        t_bytes = work["bytes"] / self.peaks["hbm_bytes_per_s"]
        return 100.0 * max(t_ops, t_bytes) * self.windows / (ns / 1e9)


def per_layer(cell: layout.Cell, trace_dir: str, windows: int, device_kind: str):
    pd = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    red = trace_reduce.reduce(pd, SPAN_RUN)
    del pd
    ctx = Context(cell=cell, trace=red, windows=windows,
                  shapes=layout.shapes(cell), peaks=layout.peaks(device_kind))
    metrics = {}
    for m in cell.per_layer:
        value = layout.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"busy_s": red.busy_ns() / 1e9, "window_s": red.window_ns / 1e9}
    return metrics, device, trace_reduce.breakdown(red)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
class Driven(NamedTuple):
    """What the program side of one run leaves for the check and the result."""
    seeds: list[int]
    chunks: list[dict]           # each timed chunk's traces
    points: list[int]            # the points the reference replays
    outputs: list[dict]          # what the comparison reads of those points
    setup_s: float
    window_s: float
    chunk_s: list[float]         # wall seconds of each timed chunk
    compiles: int                # compile events inside the timed window
    peak: int | None             # peak_bytes_in_use after the window
    phases: str                  # set-up split by phase, for stderr
    setup_compile_s: float


def drive(cell: layout.Cell, seed: int, seconds: float, t_start: float, device,
          clock: CompileClock, trace_dir: str | None = None) -> Driven:
    """The timed path: set-up, then chunks until ``seconds`` have passed
    (under the profiler where ``trace_dir`` is given), the peak, and what
    the comparison reads.  The program's state is freed on return."""
    import jax

    seeds = point_seeds(seed, len(cell.traffic["offered_rps"]))
    marks = [("start", time.perf_counter())]
    prog = Program(cell, seeds)
    marks.append(("build", time.perf_counter()))
    prog.preload()
    marks.append(("preload", time.perf_counter()))
    prog.chunk(first=True)
    jax.block_until_ready(prog.fleet.carry)
    marks.append(("first_chunk", time.perf_counter()))
    phases = " ".join(f"{name}={t - t_prev:.3f}" for (name, t), t_prev
                      in zip(marks, [t_start] + [t for _, t in marks]))
    setup_compile_s = clock.seconds

    compiles0 = clock.events
    chunks, ends = [], []
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    while True:
        chunks.append(prog.chunk(first=False))
        ends.append(time.perf_counter())
        if ends[-1] - t0 >= seconds:
            break
    if trace_dir:
        jax.profiler.stop_trace()
    compiles = clock.events - compiles0
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")

    points = check_points(cell.traffic, seed)
    outputs = prog.outputs(chunks, points)
    del prog
    gc.collect()
    return Driven(seeds=seeds, chunks=chunks, points=points, outputs=outputs,
                  setup_s=t0 - t_start, window_s=ends[-1] - t0,
                  chunk_s=[b - a for a, b in zip([t0] + ends, ends)],
                  compiles=compiles, peak=peak, phases=phases,
                  setup_compile_s=setup_compile_s)


def run(cell: layout.Cell, seed: int, seconds: float, trace: bool, t_start: float,
        device) -> tuple[dict, dict]:
    """One run of ``cell``; returns the result line and the checks."""
    import jax

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
              "device": {"platform": device.platform, "kind": device.device_kind,
                         "count": cell.chips}}
    with tempfile.TemporaryDirectory() if trace else contextlib.nullcontext() as tmp:
        d = drive(cell, seed, seconds, t_start, device, clock, trace_dir=tmp)
        result["device"]["memory_peak_bytes"] = d.peak
        if trace:
            windows = len(d.chunks) * cell.traffic["chunk_windows"]
            metrics, dev, bd = per_layer(cell, tmp, windows, device.device_kind)
            result["metrics"] = metrics
            result["device"].update(dev)
            result["breakdown"] = bd

    t_ref = time.perf_counter()
    ref_out = replay(cell, d.seeds, len(d.chunks), d.points)
    numbers, attempted, failed = compare.compare(d.outputs, ref_out)
    print(f"[harness] {cell.name} setup_s={d.setup_s:.3f} window_s={d.window_s:.3f} "
          f"chunks={len(d.chunks)} points={d.points} "
          f"reference_s={time.perf_counter() - t_ref:.3f} "
          f"compile_s={clock.seconds:.3f} setup_compile_s={d.setup_compile_s:.3f} "
          f"chunk_s={[round(s, 4) for s in d.chunk_s]} "
          f"setup_phases: {d.phases}", file=sys.stderr, flush=True)
    numbers["compiles_in_window"] = d.compiles
    limits = dict(compare.LIMITS, compiles_in_window=0)
    result["correct"] = compare.verdict(numbers, limits)
    result["attempted"], result["failed"] = attempted, failed + (d.compiles > 0)
    if not trace:
        tx = sum(int(np.sum(c["tx"], dtype=np.int64)) for c in d.chunks)
        e2e = {"sim_req_per_s": (tx / d.window_s, "req/s"),
               "peak_hbm_mb": (d.peak / 1e6 if d.peak else None, "MB"),
               "setup_s": (d.setup_s, "s")}
        for m in cell.end_to_end:
            value, unit = e2e[m["name"]]
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": unit}
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    result["checks"] = checks
    return result, checks
