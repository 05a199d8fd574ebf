"""OrbitCache in the ToR switch (paper §3): the lookup and state tables,
the request table, orbit lines served within the recirculation budget,
and the §3.8 controller on the host between periods.

A scheme module gives the reference its switch and tells the harness
where the program keeps the same state; ``reference.py`` documents the
names each scheme module defines.
"""
import numpy as np

import jax.numpy as jnp

import reference as ref

PRELOAD = True        # the hottest keys are installed before the first window
CONTROLLER = True     # a controller period runs the cache update in the scan


def init_switch(g):
    c, s = g.entries, g.queue
    zu = lambda *shape: jnp.zeros(shape, jnp.uint32)
    return dict(
        hkeys=jnp.zeros((c, 4), jnp.uint32), occupied=jnp.zeros((c,), bool),
        kidx=jnp.full((c,), -1, jnp.int32),
        valid=jnp.zeros((c,), bool), version=jnp.zeros((c,), jnp.int32),
        rt_client=jnp.full((c * s,), -1, jnp.int32),
        rt_seq=jnp.zeros((c * s,), jnp.int32),
        rt_port=jnp.zeros((c * s,), jnp.int32),
        rt_ts=jnp.zeros((c * s,), g.tdt),
        rt_acked=jnp.zeros((c * s,), jnp.int32),
        rt_kidx=jnp.full((c * s,), -1, jnp.int32),
        qlen=jnp.zeros((c,), jnp.int32), front=jnp.zeros((c,), jnp.int32),
        rear=jnp.zeros((c,), jnp.int32),
        live=jnp.zeros((c,), bool), line_kidx=jnp.full((c,), -1, jnp.int32),
        line_version=jnp.zeros((c,), jnp.int32),
        line_vlen=jnp.zeros((c,), jnp.int32),
        line_val=jnp.zeros((c, g.value_pad), jnp.uint8),
        frags=jnp.ones((c,), jnp.int32),
        popularity=zu(c), hits=zu(), overflow=zu(), cached_reqs=zu())


def subround(g, sw, pk, r):
    """One subround of the OrbitCache switch (paper Fig. 4) on ingress ``pk``.

    Returns the switch state, per-lane (route, flag), this round's serves
    and its counters."""
    c, s = g.entries, g.queue
    op, valid = pk["op"], pk["valid"]
    is_ = lambda code: valid & (op == code)
    r_req, w_req, r_rep, w_rep, f_rep, f_req, crn = (
        is_(ref.R_REQ), is_(ref.W_REQ), is_(ref.R_REP), is_(ref.W_REP),
        is_(ref.F_REP), is_(ref.F_REQ), is_(ref.CRN_REQ))

    # recirculation budget from the lines live at the start of the subround
    live = sw["live"]
    nlive = jnp.maximum(jnp.sum(live.astype(jnp.int32)), 1)
    mean_line = (jnp.sum(jnp.where(live, sw["line_vlen"], 0)) / nlive
                 + ref.HDR_BYTES + g.key_size)
    pps = (g.recirc_gbps * 1e9 / 8.0) / mean_line
    budget = (pps * jnp.float32(g.window_us) * 1e-6 / g.subrounds).astype(jnp.int32)
    interval = nlive.astype(jnp.float32) / pps * 1e6

    # match against the lookup table; first matching entry wins
    eq = jnp.all(pk["hkey"][:, None, :] == sw["hkeys"][None], axis=-1) & sw["occupied"][None]
    hit = jnp.any(eq, axis=1)
    ent = jnp.where(hit, jnp.argmax(eq, axis=1), 0).astype(jnp.int32)
    entry_valid = sw["valid"][ent] & hit
    pop = jnp.sum((eq & r_req[:, None]).astype(jnp.int32), axis=0)

    # request-table admission, in arrival order
    want = r_req & hit & entry_valid
    offset = ref.prior_same(jnp.where(want, ent, -1), want)
    accepted = want & (offset < (s - sw["qlen"])[ent])
    overflow = want & ~accepted
    new_counts = jnp.zeros((c,), jnp.int32).at[ent].add(accepted.astype(jnp.int32))
    slot = jnp.where(accepted, ent * s + (sw["rear"][ent] + offset) % s, c * s)
    put = lambda arr, v: arr.at[slot].set(v, mode="drop")
    rt = dict(rt_client=put(sw["rt_client"], pk["client"]),
              rt_seq=put(sw["rt_seq"], pk["seq"]),
              rt_port=put(sw["rt_port"], pk["port"]),
              rt_ts=put(sw["rt_ts"], pk["ts"]),
              rt_acked=put(sw["rt_acked"], jnp.zeros_like(pk["seq"])),
              rt_kidx=put(sw["rt_kidx"], pk["kidx"]))
    qlen = sw["qlen"] + new_counts
    rear = (sw["rear"] + new_counts) % s

    # state table: a cached write invalidates, a value reply revalidates
    w_cached = w_req & hit
    install = (w_rep | f_rep) & hit & (pk["flag"] >= 1)
    inv = jnp.zeros((c,), bool).at[ent].max(w_cached)
    ok = jnp.zeros((c,), bool).at[ent].max(install)
    valid_c = (sw["valid"] & ~inv) | ok
    version = sw["version"] + jnp.zeros((c,), jnp.int32).at[ent].add(
        w_cached.astype(jnp.int32))

    # orbit lines: the last installing lane of an entry wins
    lanes = jnp.arange(op.shape[0])
    winner = jnp.full((c,), -1, jnp.int32).at[jnp.where(install, ent, c)].max(
        lanes, mode="drop")
    got = winner >= 0
    w = jnp.maximum(winner, 0)
    pick = lambda old, v: jnp.where(got, v[w], old)
    line_kidx = pick(sw["line_kidx"], pk["kidx"])
    line_version = pick(sw["line_version"], version[ent])
    line_vlen = pick(sw["line_vlen"], pk["vlen"])
    line_val = jnp.where(got[:, None], pk["val"][w], sw["line_val"])
    frags = pick(sw["frags"], jnp.maximum(pk["flag"], 1))
    live = (sw["occupied"] & valid_c & (line_version == version)
            & (sw["live"] | got))

    # serving round: the budget split over live lines, front of each queue
    per_line = budget // jnp.maximum(jnp.sum(live.astype(jnp.int32)), 1)
    n_serve = jnp.minimum(qlen, jnp.where(live.astype(jnp.int32) >= frags,
                                          per_line, 0))
    j = jnp.arange(g.max_serves)[None, :]
    served = j < n_serve[:, None]
    at = jnp.arange(c)[:, None] * s + (sw["front"][:, None] + j) % s
    serve = dict(served=served, ts=rt["rt_ts"][at], req_kidx=rt["rt_kidx"][at],
                 line_kidx=line_kidx, interval=interval, r=r)
    sw = dict(sw, **rt, qlen=qlen - n_serve, front=(sw["front"] + n_serve) % s,
              rear=rear, valid=valid_c, version=version, live=live,
              line_kidx=line_kidx, line_version=line_version,
              line_vlen=line_vlen, line_val=line_val, frags=frags)

    r_hit = r_req & hit
    invalid_fwd = r_hit & ~entry_valid
    n_hit = jnp.sum(r_hit.astype(jnp.int32))
    n_ovf = jnp.sum(overflow.astype(jnp.int32)) + jnp.sum(invalid_fwd.astype(jnp.int32))
    sw["popularity"] = ref.sat_add(sw["popularity"], pop)
    sw["hits"] = ref.sat_add(sw["hits"], n_hit)
    sw["overflow"] = ref.sat_add(sw["overflow"], n_ovf)
    sw["cached_reqs"] = ref.sat_add(sw["cached_reqs"], n_hit)
    to_server = (r_req & ~hit) | overflow | invalid_fwd | w_req | crn | f_req
    route = jnp.where(to_server & valid, ref.SERVER, ref.DROP)
    route = jnp.where((r_rep | w_rep) & valid, ref.CLIENT, route)
    flag = jnp.where(w_cached, 1, pk["flag"])
    stats = dict(hits=n_hit, overflow=n_ovf,
                 installs=jnp.sum(install.astype(jnp.int32)),
                 crn=jnp.sum(crn.astype(jnp.int32)),
                 rx_switch=jnp.sum(served.astype(jnp.int32)))
    return sw, route, flag, serve, stats


def account_switch(g, cl, serves, now):
    """Orbit-served replies: latency, and the client's requested-key check."""
    hist = jnp.zeros((ref.LAT_BUCKETS,), jnp.int32)
    crn_kidx, crn_n = cl["crn_kidx"], cl["crn_n"]
    n_served = n_mism = jnp.zeros((), jnp.int32)
    window = jnp.asarray(g.window_us, g.tdt)
    order = jnp.arange(g.max_serves, dtype=g.tdt)[None, :]
    for sv in serves:                       # subround order, then entry, slot
        t = (now + (jnp.asarray(sv["r"], g.tdt) + 0.5) * window / g.subrounds
             + (order + 1.0) * sv["interval"].astype(g.tdt))
        lat = jnp.maximum(t - sv["ts"], 0.05) + ref.BASE_RTT_US
        hist = hist + ref.lat_hist(lat.reshape(-1), sv["served"].reshape(-1))
        mism = (sv["served"] & (sv["req_kidx"] != sv["line_kidx"][:, None])).reshape(-1)
        dest = crn_n + jnp.cumsum(mism.astype(jnp.int32)) - mism
        crn_kidx = crn_kidx.at[jnp.where(mism, dest, ref.CRN_WIDTH)].set(
            sv["req_kidx"].reshape(-1), mode="drop")
        k = jnp.sum(mism.astype(jnp.int32))
        crn_n = jnp.minimum(crn_n + k, ref.CRN_WIDTH)
        n_mism = n_mism + k
        n_served = n_served + jnp.sum(sv["served"].astype(jnp.int32))
    return dict(cl, hist_switch=ref.sat_add(cl["hist_switch"], hist),
                rx_switch=ref.sat_add(cl["rx_switch"], n_served),
                mismatches=ref.sat_add(cl["mismatches"], n_mism),
                crn_kidx=crn_kidx, crn_n=crn_n)


def switch_window(g, sw, sub, clients, now):
    """The window's subrounds through the switch, in order; then the
    clients account for the orbit-served replies."""
    stats = {k: jnp.zeros((), jnp.int32)
             for k in ("hits", "overflow", "installs", "crn", "rx_switch")}
    routes, flags, serves = [], [], []
    for r in range(g.subrounds):
        pk = {k: v[r] for k, v in sub.items()}
        sw, route, flag, serve, s = subround(g, sw, pk, r)
        routes.append(route)
        flags.append(flag)
        serves.append(serve)
        stats = {k: stats[k] + s[k] for k in stats}
    clients = account_switch(g, clients, serves, now)
    return sw, jnp.concatenate(routes), jnp.concatenate(flags), stats, clients


# ---------------------------------------------------------------------------
# the controller (paper §3.8), on the host between periods
# ---------------------------------------------------------------------------
def fetch_packets(g, keys, vlen_of):
    """F-REQ lanes for newly inserted keys, in insert order."""
    p = {k: np.array(v) for k, v in ref.empty_packets(g.fetch_lanes, g).items()}
    keys = np.asarray(keys[:g.fetch_lanes], np.int32)
    n = len(keys)
    if n:
        kj = jnp.asarray(keys)
        p["op"][:n] = ref.F_REQ
        p["kidx"][:n] = keys
        p["hkey"][:n] = ref.hash128_np(keys)
        p["vlen"][:n] = np.asarray(vlen_of[kj])
        p["server"][:n] = np.asarray(ref.server_of(kj, g.n_servers))
        p["valid"][:n] = True
    return ref.by_subround({k: jnp.asarray(v) for k, v in p.items()}, g.subrounds)


def cache_update(g, st, reports, active, vlen_of):
    """Merge switch popularity with server reports, keep the ``active``
    most popular keys (score desc, key asc), evict the rest; a new key
    takes the slot of an evicted one first, then a free one (§3.8).
    Returns the state with F-REQs queued, and the update."""
    sw = {k: np.array(v) for k, v in st["switch"].items()}
    occ, ck, c = sw["occupied"], sw["kidx"], g.entries
    scores = {int(ck[i]): int(sw["popularity"][i]) for i in range(c) if occ[i]}
    for keys, est in reports:
        for k, e in zip(keys.tolist(), est.tolist()):
            if k >= 0:
                scores[k] = scores.get(k, 0) + e
    desired = sorted(scores, key=lambda k: (-scores[k], k))[:min(active, c)]
    keep = set(desired)
    current = {int(ck[i]): i for i in range(c) if occ[i]}
    evict = [i for k, i in current.items() if k not in keep]
    new = [k for k in desired if k not in current]
    slots = evict + [i for i in range(c) if not occ[i]]
    inserted = []
    for k, i in zip(new, slots):
        sw["hkeys"][i] = ref.hash128_np(np.int32(k))
        occ[i], ck[i], sw["valid"][i] = True, k, False
        sw["version"][i] += 1
        sw["live"][i] = False
        inserted.append((k, i))
    evicted = [int(st["switch"]["kidx"][i]) for i in evict]
    for i in evict[len(inserted):]:
        occ[i], ck[i], sw["valid"][i] = False, -1, False
        sw["version"][i] += 1
        sw["live"][i] = False
    for k in ("popularity", "overflow", "cached_reqs"):
        sw[k] = np.zeros_like(sw[k])
    st = dict(st, switch={k: jnp.asarray(v) for k, v in sw.items()},
              fetch=fetch_packets(g, [k for k, _ in inserted], vlen_of))
    return st, dict(inserted=inserted, evicted=evicted)


def preload(g, st, perm, vlen_of):
    """Install the ``entries`` hottest keys, as the controller's preload
    does: descending estimates, so the hottest takes the first slot."""
    keys = np.asarray(perm[:g.entries], np.int32)
    est = (1 << 20) - np.arange(len(keys), dtype=np.int64)
    st, _ = cache_update(g, st, [(keys, est)], g.entries, vlen_of)
    return st


def update_lanes(updates, g) -> dict:
    """The reference's controller updates in the fixed-width lanes the
    program emits."""
    cap = g.entries

    def lanes(vals, fill):
        a = np.full((cap,), fill, np.int32)
        a[:len(vals)] = vals
        return a
    rows = [dict(
        fetch_kidx=lanes([k for k, _ in u["inserted"]], -1),
        fetch_cidx=lanes([c for _, c in u["inserted"]], -1),
        fetch_valid=np.arange(cap) < len(u["inserted"]),
        evicted_kidx=lanes(u["evicted"], -1),
        evicted_valid=np.arange(cap) < len(u["evicted"]),
        n_insert=np.int32(len(u["inserted"])), n_evict=np.int32(len(u["evicted"])),
    ) for u in updates]
    return {f"update.{k}": np.stack([r[k] for r in rows]) for k in rows[0]}


# ---------------------------------------------------------------------------
# where the program keeps the same state
# ---------------------------------------------------------------------------
def program_state(policy) -> dict:
    """The program's switch state (``carry.policy``) under the reference's
    names."""
    lk, st, rt, ob, ct = policy
    return {
        "switch.hkeys": lk.hkeys, "switch.occupied": lk.occupied,
        "switch.kidx": lk.kidx, "switch.valid": st.valid,
        "switch.version": st.version,
        **{f"switch.rt_{k}": getattr(rt, k)
           for k in ("client", "seq", "port", "ts", "acked", "kidx")},
        "switch.qlen": rt.qlen, "switch.front": rt.front,
        "switch.rear": rt.rear, "switch.live": ob.live,
        "switch.line_kidx": ob.kidx, "switch.line_version": ob.version,
        "switch.line_vlen": ob.vlen, "switch.line_val": ob.val,
        "switch.frags": ob.frags,
        **{f"switch.{k}": v for k, v in ct._asdict().items()}}


def program_update(update, i: int) -> dict:
    """Point ``i`` of the program's controller updates of the last chunk."""
    return {f"update.{k}": np.asarray(getattr(update, k))[i] for k in (
        "fetch_kidx", "fetch_cidx", "fetch_valid", "evicted_kidx",
        "evicted_valid", "n_insert", "n_evict")}
