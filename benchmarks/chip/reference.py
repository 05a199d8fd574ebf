"""Plain reference of the simulated rack: the yardstick that decides `correct`.

The same semantics as the simulator under test (paper §3-§5: clients,
OrbitCache or no cache in the ToR switch, rate-limited server FIFOs,
client accounting, and the §3.8 controller), written independently of it:
one sweep point at a time, no vmap, no kernels, plain scatters and host
loops where order matters.  It imports nothing of the program.  The same
seed gives the same random stream (``jax.random`` draws in the same
order), so on the same device its traces and final state equal the
program's exactly; the float32 latency arithmetic is the one place where
rounding could differ, and the comparison in ``compare.py`` holds it to a
limit of its own.

``tdt`` is the dtype of simulated time (``now``, packet timestamps,
serve times, latencies).  It is float32 as the configuration states;
``bfloat16`` gives the control that ``compare.py`` must reject.

What the switch does is the scheme's: ``schemes/<scheme>.py``, found by
the configuration's ``rack.scheme``, defines

* ``PRELOAD`` and ``CONTROLLER``: whether the hottest keys are installed
  before the first window, and whether a controller period updates the
  cache (then also ``cache_update`` and ``update_lanes``);
* ``init_switch(g)``: the switch state, a dict of arrays;
* ``switch_window(g, sw, sub, clients, now)``: one window's subround-major
  ingress through the switch -> ``(sw, route, flag, stats, clients)``;
* ``preload(g, st, perm, vlen_of)`` where ``PRELOAD``;
* ``program_state(policy)`` (and ``program_update(update, i)`` where
  ``CONTROLLER``): where the program keeps the same state, under the
  reference's names, for the harness.

A new scheme is one new file there.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

import layout

# op codes and routes (paper §3.2)
R_REQ, W_REQ, R_REP, W_REP, F_REQ, F_REP, CRN_REQ, NONE = range(8)
DROP, SERVER, CLIENT = 0, 1, 2
HDR_BYTES = 62            # eth + ip + udp + orbitcache header (paper §3.2)
LAT_BUCKETS = 80          # quarter-octave latency histogram
LAT_BASE_US = 0.25
BASE_RTT_US = 2.0
CRN_WIDTH = 64
CMS_DEPTH = 5
CMS_WIDTH = 2048
K_CANDIDATES = 128
K_REPORT = 64
U32_MAX = np.uint32(0xFFFFFFFF)

# ---------------------------------------------------------------------------
# key hashing and value bytes: the data definition of the workload
# ---------------------------------------------------------------------------
_LANE_BASIS = np.array([2166136261, 2166136261 ^ 0x5BD1E995,
                        2166136261 ^ 0x9E3779B9, 2166136261 ^ 0x85EBCA6B],
                       np.uint32)


def _mix(x):
    """SplitMix32 finalizer, on numpy or jax uint32 arrays."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> 16)


def hash128(kidx):
    """128-bit key hash (FNV-1a over the 4 little-endian bytes + mix)."""
    k = kidx.astype(jnp.uint32)
    lanes = jnp.broadcast_to(jnp.asarray(_LANE_BASIS), k.shape + (4,))
    for i in range(4):
        lanes = (lanes ^ ((k >> (8 * i)) & 0xFF)[..., None]) * np.uint32(16777619)
    return _mix(lanes)


def hash128_np(kidx):
    k = np.asarray(kidx).astype(np.uint32)
    lanes = np.broadcast_to(_LANE_BASIS, k.shape + (4,)).copy()
    with np.errstate(over="ignore"):
        for i in range(4):
            lanes = (lanes ^ ((k >> np.uint32(8 * i)) & np.uint32(0xFF))[..., None]
                     ) * np.uint32(16777619)
        return _mix(lanes.astype(np.uint32))


def server_of(kidx, n_servers):
    return (_mix(kidx.astype(jnp.uint32) ^ np.uint32(0xCAFE01))
            % np.uint32(n_servers)).astype(jnp.int32)


def sketch_row(hkey, width, salt):
    s = np.uint32((salt * 0x9E3779B9 + 0x85EBCA6B) & 0xFFFFFFFF)
    h = _mix(hkey[..., 0] ^ s)
    h = h ^ hkey[..., 1] ^ (hkey[..., 2] >> 7) ^ (hkey[..., 3] << 3)
    return (_mix(h) % np.uint32(width)).astype(jnp.int32)


def value_bytes(kidx, version, width):
    """Value bytes of (key, version): byte i = mix(k*P1 ^ v*P2 ^ i) & 0xFF."""
    k = kidx.astype(jnp.uint32)[..., None]
    v = version.astype(jnp.uint32)[..., None]
    i = jnp.arange(width, dtype=jnp.uint32)
    x = k * np.uint32(0x9E3779B9) ^ v * np.uint32(0x85EBCA6B) ^ i
    return (_mix(x) & 0xFF).astype(jnp.uint8)


class RefWorkload:
    """Zipf CDF over popularity ranks, rank -> key permutation, value sizes."""

    def __init__(self, num_keys, zipf_alpha, value_sizes, value_seed):
        ranks = np.arange(1, num_keys + 1, dtype=np.float64)
        w = ranks ** (-zipf_alpha)
        self.cdf = jnp.asarray(np.cumsum(w / w.sum()), jnp.float32)
        self.perm_np = np.arange(num_keys, dtype=np.int32)
        u = hash128_np((np.arange(num_keys, dtype=np.int64)
                        + value_seed * 1_000_003).astype(np.int32))[:, 0]
        u = u.astype(np.float64) / 2**32
        sizes = np.zeros(num_keys, np.int32)
        lo = 0.0
        for size, frac in value_sizes:
            sizes[(u >= lo) & (u < lo + frac)] = size
            lo += frac
        sizes[sizes == 0] = value_sizes[-1][0]
        self.vlen = jnp.asarray(sizes)

    def swap_hot_cold(self, n):
        """Hot-in churn (paper §5.3): swap the n hottest and n coldest ranks."""
        p = self.perm_np
        hot = p[:n].copy()
        p[:n] = p[-n:]
        p[-n:] = hot

    def arrays(self):
        return self.cdf, jnp.asarray(self.perm_np), self.vlen


# ---------------------------------------------------------------------------
# the rack
# ---------------------------------------------------------------------------
class Geometry(NamedTuple):
    """Static shapes of one rack (hashable: part of the jit key)."""
    scheme: str
    window_us: float
    subrounds: int
    max_serves: int
    entries: int
    queue: int
    value_pad: int
    recirc_gbps: float
    n_servers: int
    server_queue: int
    cap: int
    client_batch: int
    n_clients: int
    fetch_lanes: int
    track: bool
    key_size: int
    tdt: str
    rack: tuple           # every (field, value) of the configuration's rack

    def opt(self, name):
        """A rack field that only some scheme reads."""
        return dict(self.rack)[name]


def scheme(g: Geometry):
    """``schemes/<scheme>.py`` of the rack."""
    return layout.scheme(g.scheme)


def geometry(rack: dict, key_size: int, tdt: str = "float32") -> Geometry:
    if rack.get("max_frags", 1) != 1:
        raise ValueError("the reference models single-fragment values only")
    return Geometry(
        scheme=rack["scheme"], window_us=float(rack["window_us"]),
        subrounds=rack["subrounds"], max_serves=rack["max_serves"],
        entries=rack["cache_entries"], queue=rack["queue_size"],
        value_pad=rack["value_pad"], recirc_gbps=float(rack["recirc_gbps"]),
        n_servers=rack["num_servers"], server_queue=rack["server_queue"],
        cap=max(1, int(round(rack["server_rps"] * rack["window_us"] * 1e-6))),
        client_batch=rack["client_batch"], n_clients=rack["num_clients"],
        fetch_lanes=rack["fetch_lanes"],
        track=bool(rack.get("track_popularity", False)),
        key_size=key_size, tdt=tdt, rack=tuple(sorted(rack.items())))


def empty_packets(width, g: Geometry):
    return dict(
        op=jnp.full((width,), NONE, jnp.int32),
        seq=jnp.zeros((width,), jnp.int32),
        hkey=jnp.zeros((width, 4), jnp.uint32),
        flag=jnp.zeros((width,), jnp.int32),
        kidx=jnp.full((width,), -1, jnp.int32),
        vlen=jnp.zeros((width,), jnp.int32),
        client=jnp.full((width,), -1, jnp.int32),
        port=jnp.zeros((width,), jnp.int32),
        server=jnp.full((width,), -1, jnp.int32),
        ts=jnp.zeros((width,), g.tdt),
        valid=jnp.zeros((width,), bool),
        val=jnp.zeros((width, g.value_pad), jnp.uint8))


def by_subround(p, r):
    """Arrival-ordered lanes [W] -> [R, W // R]: lane i goes to row i % R."""
    return {k: v.reshape((v.shape[0] // r, r) + v.shape[1:]).swapaxes(0, 1)
            for k, v in p.items()}


def reply_width(g: Geometry):
    w = g.n_servers * g.cap
    return w + (-w) % g.subrounds


def init_state(g: Geometry, num_keys, offered_rps, write_ratio, seed):
    n, q = g.n_servers, g.server_queue
    zu = lambda *shape: jnp.zeros(shape, jnp.uint32)
    st = dict(
        servers=dict(
            op=jnp.zeros((n, q), jnp.int32), kidx=jnp.zeros((n, q), jnp.int32),
            seq=jnp.zeros((n, q), jnp.int32), client=jnp.zeros((n, q), jnp.int32),
            port=jnp.zeros((n, q), jnp.int32), flag=jnp.zeros((n, q), jnp.int32),
            vlen=jnp.zeros((n, q), jnp.int32), ts=jnp.zeros((n, q), g.tdt),
            qlen=jnp.zeros((n,), jnp.int32), front=jnp.zeros((n,), jnp.int32),
            rear=jnp.zeros((n,), jnp.int32),
            key_version=jnp.zeros((num_keys,), jnp.int32),
            cms=jnp.zeros((n, CMS_DEPTH, CMS_WIDTH), jnp.int32),
            cand_kidx=jnp.full((n, K_CANDIDATES), -1, jnp.int32),
            cand_est=jnp.zeros((n, K_CANDIDATES), jnp.int32),
            served=zu(n), dropped=zu(n)),
        clients=dict(
            next_seq=jnp.zeros((), jnp.int32),
            crn_kidx=jnp.full((CRN_WIDTH,), -1, jnp.int32),
            crn_n=jnp.zeros((), jnp.int32),
            hist_switch=zu(LAT_BUCKETS), hist_server=zu(LAT_BUCKETS),
            rx_switch=zu(), rx_server=zu(), tx=zu(), mismatches=zu()),
        pending=by_subround(empty_packets(reply_width(g), g), g.subrounds),
        fetch=by_subround(empty_packets(g.fetch_lanes, g), g.subrounds),
        rng=jax.random.PRNGKey(seed),
        now=jnp.zeros((), g.tdt),
        offered=jnp.float32(offered_rps * g.window_us * 1e-6),
        write_ratio=jnp.float32(write_ratio))
    st["switch"] = scheme(g).init_switch(g)
    return st


def sat_add(acc, delta):
    delta = jnp.asarray(delta).astype(jnp.uint32)
    return acc + jnp.minimum(delta, U32_MAX - acc)


def lat_hist(lat, mask):
    b = jnp.clip((4.0 * jnp.log2(jnp.maximum(lat, LAT_BASE_US) / LAT_BASE_US)
                  ).astype(jnp.int32), 0, LAT_BUCKETS - 1)
    return jnp.zeros((LAT_BUCKETS,), jnp.int32).at[b].add(mask.astype(jnp.int32))


def prior_same(keys, mask):
    """Per lane: how many earlier masked lanes carry the same key."""
    same = (keys[:, None] == keys[None, :]) & mask[None, :]
    earlier = jnp.arange(keys.shape[0])[None, :] < jnp.arange(keys.shape[0])[:, None]
    return jnp.sum(same & earlier, axis=1).astype(jnp.int32)


def generate(g: Geometry, st, cdf, perm, vlen_of, key):
    """One window of open-loop Poisson requests, plus pending corrections."""
    r1, r2, r3 = jax.random.split(key, 3)
    b, r = g.client_batch, g.subrounds
    cl = st["clients"]
    n = jnp.minimum(jax.random.poisson(r1, st["offered"]), b).astype(jnp.int32)
    lane = jnp.arange(b, dtype=jnp.int32)
    u = jax.random.uniform(r2, (b,), jnp.float32)
    kidx = perm[jnp.clip(jnp.searchsorted(cdf, u).astype(jnp.int32), 0,
                         perm.shape[0] - 1)]
    write = jax.random.uniform(r3, (b,), jnp.float32) < st["write_ratio"]
    seq = cl["next_seq"] + lane
    valid = lane < n
    now = st["now"]
    req = dict(op=jnp.where(valid, jnp.where(write, W_REQ, R_REQ), NONE),
               seq=seq, hkey=hash128(kidx), flag=jnp.zeros((b,), jnp.int32),
               kidx=kidx, vlen=vlen_of[kidx], client=seq % g.n_clients,
               port=jnp.zeros((b,), jnp.int32), server=server_of(kidx, g.n_servers),
               ts=jnp.full((b,), now, g.tdt), valid=valid,
               val=jnp.zeros((b, g.value_pad), jnp.uint8))
    lc = jnp.arange(CRN_WIDTH, dtype=jnp.int32)
    cvalid = lc < cl["crn_n"]
    ck = jnp.where(cvalid, cl["crn_kidx"], 0)
    cseq = cl["next_seq"] + b + lc
    crn = dict(op=jnp.where(cvalid, CRN_REQ, NONE), seq=cseq, hkey=hash128(ck),
               flag=jnp.zeros((CRN_WIDTH,), jnp.int32), kidx=ck, vlen=vlen_of[ck],
               client=cseq % g.n_clients, port=jnp.zeros((CRN_WIDTH,), jnp.int32),
               server=server_of(ck, g.n_servers),
               ts=jnp.full((CRN_WIDTH,), now, g.tdt), valid=cvalid,
               val=jnp.zeros((CRN_WIDTH, g.value_pad), jnp.uint8))
    reqs = {k: jnp.concatenate([a, c], axis=1)
            for (k, a), c in zip(by_subround(req, r).items(),
                                 by_subround(crn, r).values())}
    cl = dict(cl, next_seq=cl["next_seq"] + b + CRN_WIDTH,
              crn_kidx=jnp.full((CRN_WIDTH,), -1, jnp.int32),
              crn_n=jnp.zeros((), jnp.int32), tx=sat_add(cl["tx"], n))
    return cl, reqs


def track_reads(sv, kidx, mask_by_server):
    """Per server: count-min sketch update (estimates read at the start of
    each 256-lane tile, as the servers' tracker does) and hashed
    heavy-hitter candidate slots."""
    hk = hash128(kidx)
    rows = jnp.stack([sketch_row(hk, CMS_WIDTH, d) for d in range(CMS_DEPTH)], -1)
    b = kidx.shape[0]
    tile = min(256, max(8, b))
    cslot = (hk[:, 0] % np.uint32(K_CANDIDATES)).astype(jnp.int32)

    def one(counts, cand_k, cand_e, mask):
        est = jnp.zeros((b,), jnp.int32)
        for t0 in range(0, b, tile):
            sl = slice(t0, t0 + tile)
            m = mask[sl]
            q = jnp.min(jnp.stack([counts[d, rows[sl, d]] for d in range(CMS_DEPTH)], -1), -1)
            est = est.at[sl].set(jnp.where(m, q, 0))
            for d in range(CMS_DEPTH):
                counts = counts.at[d, jnp.where(m, rows[sl, d], CMS_WIDTH)].add(1, mode="drop")
        slot = jnp.where(mask, cslot, K_CANDIDATES)
        best = cand_e.at[slot].max(est, mode="drop")
        won = mask & (est >= best[jnp.clip(slot, 0, K_CANDIDATES - 1)]) & (slot < K_CANDIDATES)
        cand_k = cand_k.at[jnp.where(won, slot, K_CANDIDATES)].set(kidx, mode="drop")
        return counts, cand_k, best

    counts, ck, ce = jax.vmap(one)(sv["cms"], sv["cand_kidx"], sv["cand_est"],
                                   mask_by_server)
    return dict(sv, cms=counts, cand_kidx=ck, cand_est=ce)


def servers_step(g: Geometry, sv, pk, to_server, flag):
    """Server FIFOs: enqueue in arrival order, drop when full, serve ``cap``."""
    n, q, cap, pad = g.n_servers, g.server_queue, g.cap, g.value_pad
    srv = jnp.where(to_server, pk["server"], 0)
    offset = prior_same(jnp.where(to_server, srv, -1), to_server)
    accepted = to_server & (offset < (q - sv["qlen"])[srv])
    dropped = jnp.zeros((n,), jnp.int32).at[srv].add((to_server & ~accepted).astype(jnp.int32))
    cnt = jnp.zeros((n,), jnp.int32).at[srv].add(accepted.astype(jnp.int32))
    cell = jnp.where(accepted, srv * q + (sv["rear"][srv] + offset) % q, n * q)
    put = lambda arr, v: arr.reshape(-1).at[cell].set(v, mode="drop").reshape(n, q)
    sv = dict(sv, op=put(sv["op"], pk["op"]), kidx=put(sv["kidx"], pk["kidx"]),
              seq=put(sv["seq"], pk["seq"]), client=put(sv["client"], pk["client"]),
              port=put(sv["port"], pk["port"]), flag=put(sv["flag"], flag),
              vlen=put(sv["vlen"], pk["vlen"]), ts=put(sv["ts"], pk["ts"]),
              qlen=sv["qlen"] + cnt, rear=(sv["rear"] + cnt) % q,
              dropped=sat_add(sv["dropped"], dropped))
    if g.track:
        reads = accepted & (pk["op"] == R_REQ)
        by_srv = (srv[None, :] == jnp.arange(n)[:, None]) & reads[None, :]
        sv = track_reads(sv, pk["kidx"], by_srv)

    n_serve = jnp.minimum(sv["qlen"], cap)
    live = jnp.arange(cap)[None, :] < n_serve[:, None]
    at = (sv["front"][:, None] + jnp.arange(cap)[None, :]) % q
    get = lambda k: jnp.take_along_axis(sv[k], at, axis=1)
    s_op, s_kidx, s_flag, s_vlen = get("op"), get("kidx"), get("flag"), get("vlen")
    writes = live & (s_op == W_REQ)
    kv = sv["key_version"].at[jnp.where(writes, s_kidx, sv["key_version"].shape[0])
                              ].add(1, mode="drop")
    version = kv[s_kidx]
    rep_op = jnp.select([s_op == R_REQ, s_op == W_REQ, s_op == F_REQ, s_op == CRN_REQ],
                        [R_REP, W_REP, F_REP, R_REP], R_REP).astype(jnp.int32)
    with_val = ((s_op == R_REQ) | (s_op == CRN_REQ) | (s_op == F_REQ)
                | ((s_op == W_REQ) & (s_flag >= 1)))
    frag_vlen = jnp.clip(s_vlen, 0, pad)
    val = value_bytes(s_kidx, version, pad)
    val = jnp.where((jnp.arange(pad) < frag_vlen[..., None]) & with_val[..., None], val, 0)
    flat = lambda x: x.reshape(-1)
    replies = dict(
        op=flat(rep_op),
        seq=jnp.where(flat(rep_op) == F_REP, 0, flat(get("seq"))),
        hkey=hash128(flat(s_kidx)),
        flag=flat(jnp.where((s_op == F_REQ) | ((s_op == W_REQ) & (s_flag >= 1)), 1, 0)),
        kidx=flat(s_kidx), vlen=flat(jnp.where(with_val, frag_vlen, 0)),
        client=flat(get("client")), port=jnp.zeros((n * cap,), jnp.int32),
        server=flat(jnp.broadcast_to(jnp.arange(n)[:, None], (n, cap))),
        ts=flat(get("ts")), valid=flat(live), val=val.reshape(n * cap, pad))
    sv = dict(sv, qlen=sv["qlen"] - n_serve, front=(sv["front"] + n_serve) % q,
              key_version=kv, served=sat_add(sv["served"], n_serve))
    return sv, replies, n_serve, dropped


def window(g: Geometry, cdf, perm, vlen_of, st):
    """One simulated window; returns the next state and the window's trace."""
    key, gen = jax.random.split(st["rng"])
    clients, reqs = generate(g, st, cdf, perm, vlen_of, gen)
    sub = {k: jnp.concatenate([reqs[k], st["pending"][k], st["fetch"][k]], axis=1)
           for k in reqs}
    sw, route, flag, stats, clients = scheme(g).switch_window(
        g, st["switch"], sub, clients, st["now"])
    pk = {k: v.reshape((-1,) + v.shape[2:]) for k, v in sub.items()}
    to_server = (route == SERVER) & pk["valid"]
    servers, replies, served, dropped = servers_step(g, st["servers"], pk, to_server, flag)

    window_t = jnp.asarray(g.window_us, g.tdt)
    is_rep = ((route == CLIENT) & pk["valid"]
              & ((pk["op"] == R_REP) | (pk["op"] == W_REP)) & (pk["port"] == 0))
    lat = jnp.maximum((st["now"] + window_t) - pk["ts"], 0.05) + BASE_RTT_US
    n_rep = jnp.sum(is_rep.astype(jnp.int32))
    clients = dict(clients, hist_server=sat_add(clients["hist_server"], lat_hist(lat, is_rep)),
                   rx_server=sat_add(clients["rx_server"], n_rep))
    pad = reply_width(g) - replies["op"].shape[0]
    if pad:
        e = empty_packets(pad, g)
        replies = {k: jnp.concatenate([v, e[k]]) for k, v in replies.items()}
    trace = dict(
        tx=jnp.sum((reqs["valid"] & (reqs["op"] != NONE)).astype(jnp.int32)),
        rx_switch=stats["rx_switch"],
        rx_server=clients["rx_server"] - st["clients"]["rx_server"],
        served=served, dropped=dropped, backlog=servers["qlen"],
        hits=stats["hits"], overflow=stats["overflow"], installs=stats["installs"],
        crn=stats["crn"], mismatches=clients["mismatches"],
        fwd=jnp.sum(to_server.astype(jnp.int32)))
    out = dict(st, servers=servers, clients=clients,
               pending=by_subround(replies, g.subrounds),
               fetch=by_subround(empty_packets(g.fetch_lanes, g), g.subrounds),
               switch=sw, rng=key, now=st["now"] + window_t)
    return out, trace


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(5,))
def run_windows(g: Geometry, n, cdf, perm, vlen_of, st):
    return jax.lax.scan(lambda s, _: window(g, cdf, perm, vlen_of, s), st, None, length=n)


# ---------------------------------------------------------------------------
# the controller (paper §3.8), on the host between periods
# ---------------------------------------------------------------------------
def server_reports(st):
    """Per-server top-K_REPORT candidates by estimate; trackers reset."""
    ck = np.asarray(st["servers"]["cand_kidx"])
    ce = np.asarray(st["servers"]["cand_est"])
    reports = []
    for k, e in zip(ck, ce):
        top = np.argsort(-e, kind="stable")[:K_REPORT]
        reports.append((k[top], e[top]))
    sv = dict(st["servers"], cms=jnp.zeros_like(st["servers"]["cms"]),
              cand_kidx=jnp.full_like(st["servers"]["cand_kidx"], -1),
              cand_est=jnp.zeros_like(st["servers"]["cand_est"]))
    return dict(st, servers=sv), reports


class RefRack:
    """One sweep point, driven on the same cadence as the program's fleet."""

    def __init__(self, g: Geometry, wl: RefWorkload, offered_rps, write_ratio, seed):
        self.g, self.wl = g, wl
        self.active = g.entries
        self.updates = []
        self.st = init_state(g, wl.perm_np.shape[0], offered_rps, write_ratio, seed)

    def preload(self, warm_windows):
        """The scheme's preload and its warm-up windows, where it has one."""
        if not scheme(self.g).PRELOAD:
            return None
        self.st = scheme(self.g).preload(self.g, self.st, self.wl.perm_np, self.wl.vlen)
        return self.run_windows(warm_windows)

    def run_windows(self, n):
        cdf, perm, vlen = self.wl.arrays()
        self.st, tr = run_windows(self.g, n, cdf, perm, vlen, self.st)
        return {k: np.asarray(v) for k, v in tr.items()}

    def run_periods(self, n_periods, period_w):
        out = []
        for _ in range(n_periods):
            out.append(self.run_windows(period_w))
            self.st, reports = server_reports(self.st)
            self.st, upd = scheme(self.g).cache_update(self.g, self.st, reports,
                                                       self.active, self.wl.vlen)
            self.updates.append(upd)
        return {k: np.concatenate([t[k] for t in out]) for k in out[0]}
