"""No cache in the ToR switch: the paper's baseline.  Requests go to their
servers, replies to their clients; the switch keeps no state."""
import jax.numpy as jnp

import reference as ref

PRELOAD = False
CONTROLLER = False


def init_switch(g):
    return {}


def switch_window(g, sw, sub, clients, now):
    op = sub["op"].reshape(-1)
    v = sub["valid"].reshape(-1)
    to_srv = v & ((op == ref.R_REQ) | (op == ref.W_REQ) | (op == ref.CRN_REQ)
                  | (op == ref.F_REQ))
    route = jnp.where(to_srv, ref.SERVER, ref.DROP)
    route = jnp.where(v & ((op == ref.R_REP) | (op == ref.W_REP)), ref.CLIENT, route)
    stats = {k: jnp.zeros((), jnp.int32)
             for k in ("hits", "overflow", "installs", "crn", "rx_switch")}
    return sw, route, sub["flag"].reshape(-1), stats, clients


def program_state(policy) -> dict:
    return {}
