"""NetCache in the ToR switch (Jin et al., SOSP 2017; the paper's §5.1
baseline): the hottest items' values in switch memory, answered by the
switch itself.

* The table is ``netcache_table`` slots addressed by two hashed probes of
  the 128-bit key hash (salts 100 and 101); a key lives in the first of
  its probes that holds it.
* The preload installs the cacheable subset of the ``netcache_entries``
  hottest keys, hottest first: keys of at most 16 bytes with values of at
  most ``netcache_value_limit`` bytes, each in the first of its probes
  that is free or already its own; a key with neither is refused.
  Installed entries are valid with their version-0 value bytes.
* Write-through coherence: a write to a cached key invalidates its slot,
  counts one more version and goes to the server flagged; the write's
  reply (flag set) makes the slot valid again with the reply's bytes and
  length.  Of several replies to one slot in a subround the last lane's
  bytes stand; an invalidation and a reply in one subround leave the slot
  valid.
* A read of a valid cached key is answered by the switch: its latency is
  1 us of switch pipeline plus the base round trip, and it never reaches
  a server.

A scheme module gives the reference its switch and tells the harness
where the program keeps the same state; ``reference.py`` documents the
names each scheme module defines.
"""
import numpy as np

import jax.numpy as jnp

import reference as ref

PRELOAD = True        # the hottest keys are installed before the first window
CONTROLLER = False    # the table stays as preloaded
PROBES = (100, 101)   # the salts of the two probes
KEY_LIMIT = 16        # bytes of the exact-match key
SWITCH_US = 1.0       # switch-served latency before the base round trip


def probe(hkey, t, salt):
    """Slot of one probe: the 128-bit hash folded into [0, t).  The fold is
    ``reference.sketch_row``'s, with the probe's salt."""
    return ref.sketch_row(hkey, t, salt)


def init_switch(g):
    t, width = g.opt("netcache_table"), g.opt("netcache_value_limit")
    return dict(
        hkeys=jnp.zeros((t, 4), jnp.uint32), occupied=jnp.zeros((t,), bool),
        kidx=jnp.full((t,), -1, jnp.int32), valid=jnp.zeros((t,), bool),
        val=jnp.zeros((t, width), jnp.uint8), vlen=jnp.zeros((t,), jnp.int32),
        version=jnp.zeros((t,), jnp.int32), hits=jnp.zeros((), jnp.uint32))


def subround(g, sw, pk):
    """One subround through the table -> (sw, route, flag, answered, hits)."""
    t, width = sw["valid"].shape[0], sw["val"].shape[1]
    op, valid = pk["op"], pk["valid"]
    is_ = lambda code: valid & (op == code)

    # lookup: the first probe that holds the key
    slot = jnp.full(op.shape, -1, jnp.int32)
    for salt in PROBES:
        s = probe(pk["hkey"], t, salt)
        here = sw["occupied"][s] & jnp.all(sw["hkeys"][s] == pk["hkey"], axis=-1)
        slot = jnp.where((slot < 0) & here, s, slot)
    hit = valid & (slot >= 0)
    at = jnp.where(hit, slot, t)                       # t: no slot, dropped
    entry_valid = hit & sw["valid"][jnp.minimum(at, t - 1)]

    answered = is_(ref.R_REQ) & entry_valid
    w_cached = is_(ref.W_REQ) & hit
    install = (is_(ref.W_REP) | is_(ref.F_REP)) & hit & (pk["flag"] >= 1)

    # invalidations, then the installs of this subround; the last
    # installing lane of a slot wins, and only the winners are written
    lanes = jnp.arange(op.shape[0], dtype=jnp.int32)
    inst = jnp.where(install, at, t)
    last = jnp.full((t + 1,), -1, jnp.int32).at[inst].max(lanes)
    put = jnp.where(install & (last[inst] == lanes), at, t)
    inv = jnp.where(w_cached, at, t)
    sw = dict(
        sw, valid=sw["valid"].at[inv].set(False, mode="drop").at[put].set(True, mode="drop"),
        version=sw["version"].at[inv].add(1, mode="drop"),
        val=sw["val"].at[put].set(pk["val"][:, :width], mode="drop"),
        vlen=sw["vlen"].at[put].set(jnp.minimum(pk["vlen"], width), mode="drop"))

    to_server = ((is_(ref.R_REQ) & ~answered) | is_(ref.W_REQ) | is_(ref.CRN_REQ)
                 | is_(ref.F_REQ))
    route = jnp.where(to_server, ref.SERVER, ref.DROP)
    route = jnp.where(is_(ref.R_REP) | is_(ref.W_REP) | answered, ref.CLIENT, route)
    flag = jnp.where(w_cached, 1, pk["flag"])
    return sw, route, flag, answered, jnp.sum(answered.astype(jnp.int32))


def switch_window(g, sw, sub, clients, now):
    """The window's subrounds through the table, in order; then the
    clients account for the switch's answers."""
    routes, flags, answers = [], [], []
    hits = jnp.zeros((), jnp.int32)
    for r in range(g.subrounds):
        sw, route, flag, answered, n = subround(g, sw, {k: v[r] for k, v in sub.items()})
        routes.append(route)
        flags.append(flag)
        answers.append(answered)
        hits = hits + n
    sw["hits"] = ref.sat_add(sw["hits"], hits)
    answered = jnp.concatenate(answers)
    lat = jnp.full(answered.shape, SWITCH_US, g.tdt) + ref.BASE_RTT_US
    clients = dict(clients,
                   hist_switch=ref.sat_add(clients["hist_switch"], ref.lat_hist(lat, answered)),
                   rx_switch=ref.sat_add(clients["rx_switch"], hits))
    zero = jnp.zeros((), jnp.int32)
    stats = dict(hits=hits, overflow=zero, installs=zero, crn=zero, rx_switch=hits)
    return sw, jnp.concatenate(routes), jnp.concatenate(flags), stats, clients


def preload(g, st, perm, vlen_of):
    """Place the cacheable subset of the ``netcache_entries`` hottest keys,
    hottest first, probe by probe."""
    sw = {k: np.array(v) for k, v in st["switch"].items()}
    t, width = sw["valid"].shape[0], sw["val"].shape[1]
    keys = np.asarray(perm[:g.opt("netcache_entries")], np.int32)
    vlens = np.asarray(vlen_of)[keys]
    hk = ref.hash128(jnp.asarray(keys))
    probes = np.stack([np.asarray(probe(hk, t, s)) for s in PROBES], -1)
    values = np.asarray(ref.value_bytes(jnp.asarray(keys), jnp.zeros_like(keys), width))
    hk = np.asarray(hk)
    for i, (k, vl) in enumerate(zip(keys.tolist(), vlens.tolist())):
        if g.key_size > KEY_LIMIT or vl > width:
            continue
        for s in probes[i].tolist():
            if not sw["occupied"][s] or sw["kidx"][s] == k:
                sw["hkeys"][s], sw["occupied"][s], sw["kidx"][s] = hk[i], True, k
                sw["valid"][s], sw["vlen"][s] = True, vl
                sw["val"][s] = np.where(np.arange(width) < vl, values[i], 0)
                break
    return dict(st, switch={k: jnp.asarray(v) for k, v in sw.items()})


def program_state(policy) -> dict:
    """The program's switch table (``carry.policy``, a ``NetCacheState``
    with a leading point axis) under the reference's names, one row per
    slot whatever layout the program stores it in."""
    p, t = policy.hkeys.shape[:2]
    per_slot = lambda a: np.asarray(a).reshape(p, t)
    return {
        "switch.hkeys": policy.hkeys,
        **{f"switch.{k}": per_slot(getattr(policy, k))
           for k in ("occupied", "kidx", "valid", "vlen", "version")},
        "switch.val": np.asarray(policy.val).reshape(p, t, -1),
        "switch.hits": policy.hits}
