"""Shared jaxpr traversal utilities for the lint rules.

Two traversals live here:

  * :func:`count_pallas_calls` — the structural-guarantee walker the switch
    regression tests rely on (migrated from
    ``tests/test_switch_regression.py``): counts ``pallas_call`` equations
    recursively through EVERY sub-jaxpr, including kernel bodies.
  * :func:`walk_eqns` — the rule walker: yields every equation with its
    path into the jaxpr, enclosing-``scan`` depth, and the defining-eqn
    map of its scope.  It does NOT descend into ``pallas_call`` bodies by
    default — kernel internals are covered by the ref-vs-kernel parity
    suites, and under the interpret backend ``pl.when`` lowers to ``cond``
    equations that would trip the scan rules.

Source attribution: ``user_site`` / ``user_frame_names`` use jax's
filtered user frames (the same attribution tracebacks use), while
:func:`is_library_internal` inspects the RAW traceback — jax.random
internals (``randint``/``poisson``) contain uint32→int32 demotions that
the filtered frames attribute to the nearest *user* line, so the dtype
rule must recognize them by the raw frames passing through
``jax/_src/random.py`` / ``jax/_src/prng.py``.
"""
from __future__ import annotations

from typing import Iterator, NamedTuple

from jax._src import source_info_util
from jax.extend import core as jex_core


def count_pallas_calls(jaxpr) -> int:
    """Count ``pallas_call`` equations recursively through all sub-jaxprs."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    n += count_pallas_calls(sub.jaxpr)
                elif isinstance(sub, jex_core.Jaxpr):
                    n += count_pallas_calls(sub)
    return n


class WalkItem(NamedTuple):
    eqn: object          # jex_core.JaxprEqn
    path: str            # e.g. "pjit/scan[3]/eqn[12]"
    scan_depth: int      # number of enclosing lax.scan bodies
    defs: dict           # Var -> defining eqn, for the eqn's own scope


def _sub_jaxprs(eqn):
    for key, v in eqn.params.items():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            if isinstance(sub, jex_core.ClosedJaxpr):
                yield key, sub.jaxpr
            elif isinstance(sub, jex_core.Jaxpr):
                yield key, sub


def walk_eqns(jaxpr, *, descend_into_pallas: bool = False,
              _prefix: str = "", _depth: int = 0) -> Iterator[WalkItem]:
    """Yield every equation with path / scan depth / scope defs."""
    defs: dict = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            if isinstance(ov, jex_core.Var):
                defs[ov] = eqn
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        here = f"{_prefix}eqn[{i}]:{name}"
        yield WalkItem(eqn, here, _depth, defs)
        if name == "pallas_call" and not descend_into_pallas:
            continue
        inner_depth = _depth + (1 if name == "scan" else 0)
        for key, sub in _sub_jaxprs(eqn):
            yield from walk_eqns(
                sub, descend_into_pallas=descend_into_pallas,
                _prefix=f"{_prefix}{name}[{i}].{key}/", _depth=inner_depth)


def _frames(eqn):
    return list(source_info_util.user_frames(eqn.source_info.traceback))


def user_frame_names(eqn) -> list[str]:
    """Function names of the user frames, innermost first."""
    return [f.function_name for f in _frames(eqn)]


def user_site(eqn) -> str:
    """``function @ file:line`` of the innermost user frame."""
    fr = _frames(eqn)
    if not fr:
        return ""
    f = fr[0]
    fname = f.file_name.rsplit("/", 1)[-1]
    return f"{f.function_name} @ {fname}:{f.start_line}"


_LIB_FILES = (
    "jax/_src/random.py",            # randint/poisson sample math
    "jax/_src/prng.py",              # key internals
)


def is_library_internal(eqn) -> bool:
    """True when the eqn originates inside jnp/jax.random algorithm internals.

    Walks the RAW traceback innermost-first: frames living under
    ``jax/`` are machinery; if a frame from one of the algorithmic
    library files appears before the first non-jax frame, the eqn is
    library code (e.g. the int32 sample math inside
    ``jax.random.randint``), not a repro-authored site.  Plain operator
    arithmetic (``a + b``) dispatches through ``array_methods``/``ufuncs``
    only, so user-authored counter math is never classified internal.
    """
    tb = getattr(eqn.source_info, "traceback", None)
    if tb is None:
        return False
    try:
        frames = tb.frames
    except Exception:
        return False
    for f in frames:
        fname = getattr(f, "file_name", "") or ""
        if any(lib in fname for lib in _LIB_FILES):
            return True
        if "/jax/" not in fname and "jax\\" not in fname:
            return False  # reached user code without passing random/prng
    return False
