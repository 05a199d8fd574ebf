"""The lint rules and their allowlists.

Every rule is registered in :data:`RULES` and has the signature
``rule(entry: EntryPoint) -> list[Finding]``.  Jaxpr rules walk the
entry's traced jaxpr; executable rules (donation, retrace-guard) lower /
compile / run the entry's jitted chunk and are skipped for entry points
that don't expose one.

Allowlists are per-rule sets of *user function names*: a flagged equation
is forgiven when any of its filtered user frames (see
:mod:`repro.analysis.jaxpr_walk`) is named in the rule's set.  Adding a
site to an allowlist is a reviewed change to this file — document the
justification in ``src/repro/analysis/README.md`` next to the rule.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from .findings import Finding, Severity
from .jaxpr_walk import (
    count_pallas_calls,
    is_library_internal,
    user_frame_names,
    user_site,
    walk_eqns,
)

RULES: dict = {}

# Reviewed exceptions (rationale in README.md):
#   install_window_values — the per-window row scatter installing fetched
#     value bytes into donated orbit buffers (the documented design: one
#     scatter per window, off the per-subround hot path).
#   server_step — the store-side key_version scatter-add; it models the
#     storage servers, not the switch data plane, and the O(num_keys)
#     one-hot alternative would be asymptotically wrong.
#   netcache_step — the NetCache baseline's value-install write; baseline
#     fidelity requires the in-scan update the real system performs in
#     stages.
ALLOWLISTS: dict = {
    "no-scatter": frozenset({
        "install_window_values", "server_step", "netcache_step",
    }),
    "dtype-promotion": frozenset(),
    "no-dynamic-cond-in-scan": frozenset(),
}


def rule(name: str):
    def deco(fn):
        fn.rule_name = name
        RULES[name] = fn
        return fn
    return deco


def _allowlisted(rule_name: str, eqn) -> bool:
    allowed = ALLOWLISTS.get(rule_name, frozenset())
    if not allowed:
        return False
    return any(fname in allowed for fname in user_frame_names(eqn))


# ---------------------------------------------------------------------------
# jaxpr rules
# ---------------------------------------------------------------------------
@rule("no-scatter")
def no_scatter(entry) -> list[Finding]:
    """No ``scatter*`` primitives on the hot path.

    Per-lane scatters serialize on CPU and have no MXU analogue — the
    whole point of the one-hot / unique-writer algebra.  Only the
    allowlisted per-window installs and the store-model server write may
    scatter."""
    out = []
    for item in walk_eqns(entry.jaxpr().jaxpr):
        name = item.eqn.primitive.name
        if not name.startswith("scatter"):
            continue
        if _allowlisted("no-scatter", item.eqn):
            continue
        out.append(Finding(
            rule="no-scatter", severity=Severity.ERROR, entry=entry.name,
            op=name, path=item.path, site=user_site(item.eqn),
            message=(f"scatter primitive on the hot path "
                     f"(scan depth {item.scan_depth}); use the one-hot / "
                     f"unique_writer algebra or allowlist the site"),
        ))
    return out


@rule("single-pallas-call")
def single_pallas_call(entry) -> list[Finding]:
    """Exactly the architectural number of ``pallas_call``s per trace.

    Kernel backends fuse each subround into ONE call (more means the
    fusion regressed into per-primitive kernels; fewer means a path fell
    back to the ref implementation silently).  The ref backend must stay
    kernel-free."""
    from .entry_points import backend_kind
    kind = backend_kind()
    expected = entry.expected_pallas.get(kind)
    if expected is None:
        return []
    n = count_pallas_calls(entry.jaxpr().jaxpr)
    if n == expected:
        return []
    return [Finding(
        rule="single-pallas-call", severity=Severity.ERROR, entry=entry.name,
        op="pallas_call",
        message=(f"{n} pallas_call(s) traced on the '{kind}' backend kind, "
                 f"expected {expected}"),
    )]


_ACCUM_PRIMS = {"add", "sub", "add_any"}


@rule("dtype-promotion")
def dtype_promotion(entry) -> list[Finding]:
    """No silent uint32→int32 demotion feeding an add/sub.

    ``uint32 + int32`` resolves to int32 in jax — a wrap hazard for the
    running counters, which is why ``types.sat_add`` exists.  In the
    jaxpr the footgun appears as ``convert_element_type[new_dtype=int32]``
    on a uint operand flowing straight into ``add``/``sub``.  Demotions
    inside jax.random internals (sample math in ``randint``/``poisson``)
    are library code, not counter arithmetic, and are skipped."""
    out = []
    seen = set()
    for item in walk_eqns(entry.jaxpr().jaxpr):
        if item.eqn.primitive.name not in _ACCUM_PRIMS:
            continue
        for v in item.eqn.invars:
            if not isinstance(v, jex_core.Var):
                continue
            src = item.defs.get(v)
            if src is None or src.primitive.name != "convert_element_type":
                continue
            new_dtype = src.params.get("new_dtype")
            operand = src.invars[0]
            old = getattr(getattr(operand, "aval", None), "dtype", None)
            if old is None or new_dtype is None:
                continue
            if not (jnp.issubdtype(old, jnp.unsignedinteger)
                    and jnp.issubdtype(new_dtype, jnp.signedinteger)):
                continue
            if is_library_internal(src) or is_library_internal(item.eqn):
                continue
            if _allowlisted("dtype-promotion", item.eqn):
                continue
            key = (item.path, user_site(item.eqn))
            if key in seen:
                continue
            seen.add(key)
            out.append(Finding(
                rule="dtype-promotion", severity=Severity.ERROR,
                entry=entry.name, op=item.eqn.primitive.name, path=item.path,
                site=user_site(item.eqn),
                message=(f"{old} operand demoted to {jnp.dtype(new_dtype)} "
                         f"before {item.eqn.primitive.name} — use "
                         f"types.sat_add / an explicit cast into the "
                         f"accumulator dtype"),
            ))
    return out


@rule("no-dynamic-cond-in-scan")
def no_dynamic_cond_in_scan(entry) -> list[Finding]:
    """No ``lax.cond`` inside compiled period/window scan bodies.

    The control plane runs at a STATIC position in the scan (PR 5's
    vmap-compatibility rule); a traced branch inside the scan body turns
    into a ``cond`` that vmap lowers to both-sides ``select`` — silently
    doubling work — or breaks batching outright."""
    out = []
    for item in walk_eqns(entry.jaxpr().jaxpr):
        if item.eqn.primitive.name != "cond" or item.scan_depth < 1:
            continue
        if _allowlisted("no-dynamic-cond-in-scan", item.eqn):
            continue
        out.append(Finding(
            rule="no-dynamic-cond-in-scan", severity=Severity.ERROR,
            entry=entry.name, op="cond", path=item.path,
            site=user_site(item.eqn),
            message=(f"lax.cond inside a scan body (depth "
                     f"{item.scan_depth}); hoist the branch to a static "
                     f"position or select on data"),
        ))
    return out


# ---------------------------------------------------------------------------
# compile/run rules
# ---------------------------------------------------------------------------
@rule("donation")
def donation(entry) -> list[Finding]:
    """Compiled chunk entry points must donate their carry — and the
    compiler must keep the aliasing.

    Intent is the ``tf.aliasing_output`` tags on the lowered stablehlo;
    reality is the ``input_output_alias`` table of the compiled
    executable.  A dropped donation means every window copies the full
    orbit value buffers."""
    from . import hlo as H
    if entry.donation is None:
        return []
    fn, args = entry.donation()
    lowered = fn.lower(*args)
    intent = H.donation_intent(lowered.as_text())
    if intent == 0:
        return [Finding(
            rule="donation", severity=Severity.ERROR, entry=entry.name,
            message="entry point does not donate its carry "
                    "(no donated-argument tags in the lowered module)",
        )]
    honored = H.donation_honored(lowered.compile().as_text())
    if honored == 0:
        return [Finding(
            rule="donation", severity=Severity.ERROR, entry=entry.name,
            message=(f"carry donation dropped by the compiler "
                     f"({intent} buffers donated, 0 aliased in the "
                     f"executable)"),
        )]
    if honored < intent:
        return [Finding(
            rule="donation", severity=Severity.WARNING, entry=entry.name,
            message=(f"partial donation: {intent} buffers donated, only "
                     f"{honored} aliased in the executable"),
        )]
    return []


@rule("retrace-guard")
def retrace_guard(entry) -> list[Finding]:
    """Sweeping a documented traced axis must not retrace.

    The chunk caches (`lru_cache` + jit) only pay off if host-side knob
    churn (offered load, ``active_size``, ``local_frac``) stays INSIDE
    one compilation.  The harness runs the chunk twice with argument sets
    differing only in the traced axis and asserts the jit cache did not
    grow."""
    if entry.retrace is None:
        return []
    fn, thunk_a, thunk_b, axis = entry.retrace()
    out_a = fn(*thunk_a())
    jax.block_until_ready(out_a)
    before = fn._cache_size()
    out_b = fn(*thunk_b())
    jax.block_until_ready(out_b)
    after = fn._cache_size()
    if after > before:
        return [Finding(
            rule="retrace-guard", severity=Severity.ERROR, entry=entry.name,
            message=(f"sweeping traced axis '{axis}' retraced the chunk "
                     f"(jit cache grew {before} -> {after}); the axis "
                     f"leaked into static structure"),
        )]
    return []
