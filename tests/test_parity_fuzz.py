"""Property-based parity fuzzing for the fused data plane.

Example-based edge cases (test_switch_regression) pin down scenarios we
thought of; adversarial key/slot collision patterns — the Limited
Associativity Caching lesson — break cache invariants example tests never
hit.  This suite drives RANDOM structured ingress through the production
paths and asserts the only two guarantees that matter:

  * ``kernels.subround`` ref-vs-interpret **bit-identity** over random
    key/op/vlen mixes, random queue fills, random recirculation budgets
    and random valid masks (including all-invalid and all-full extremes);
  * fused ``window_pipeline``-backed ``window_step`` vs the seed-composed
    window, **bit-identical carry and metrics**, for all three schemes.

Determinism: every example derives from a pinned integer seed.  With
``hypothesis`` installed the seeds are hypothesis-driven (derandomized —
CI uses the fixed profile below, and failures shrink to a minimal seed);
without it the same properties run over a pinned seed range, so the suite
is reproducible everywhere the repo runs.

Example counts: ``REPRO_FUZZ_EXAMPLES`` (default 20 — tier-1-friendly).
The ``slow``-marked deep profile at the bottom runs 200+ examples per
scheme on BOTH kernel-capable backends and stays out of tier-1; the CI
fuzz job runs the quick profile under ``REPRO_KERNEL_BACKEND=interpret``.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels as kn
from repro.core.hashing import hash128_u32
from repro.kernels.subround.ops import SubroundOuts
from repro.kernels.subround.ops import subround as subround_op
from repro.kernels.subround.ref import subround_ref

BASE_SEED = 20260727
N_EXAMPLES = int(os.environ.get("REPRO_FUZZ_EXAMPLES", "20"))

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # the container may not ship hypothesis
    HAVE_HYPOTHESIS = False


def fuzz(n: int | None = None):
    """Run ``fn(seed)`` over pinned seeds; hypothesis-driven when present.

    The decorated property takes ONE integer seed and derives every random
    choice from ``np.random.default_rng(seed)`` — so a failing seed is a
    complete reproducer on any machine, with or without hypothesis.
    """
    n_ex = n or N_EXAMPLES

    def deco(fn):
        if HAVE_HYPOTHESIS:
            @settings(max_examples=n_ex, deadline=None, derandomize=True,
                      suppress_health_check=list(HealthCheck))
            @given(st.integers(0, 2**31 - 1))
            def hyp_wrapper(seed):
                fn(seed)
            wrapper = hyp_wrapper
        else:
            def loop_wrapper():
                for i in range(n_ex):
                    seed = BASE_SEED + i
                    try:
                        fn(seed)
                    except AssertionError as e:
                        raise AssertionError(
                            f"fuzz example failed (seed={seed}): {e}") from e
            wrapper = loop_wrapper
        # NOT functools.wraps: __wrapped__ would make pytest read the
        # original (seed) signature and demand a 'seed' fixture
        wrapper.__name__ = fn.__name__
        wrapper.__doc__ = fn.__doc__
        return wrapper
    return deco


def _assert_trees_equal(a, b, label):
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for (path, la), lb in zip(fa, fb):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"{label}: mismatch at {jax.tree_util.keystr(path)}")


# ---------------------------------------------------------------------------
# property 1: kernels.subround ref-vs-interpret bit-identity
# ---------------------------------------------------------------------------
# Shapes stay in a pinned set so the jitted interpret kernel compiles once
# per combo; the CONTENT (keys, ops, queue fills, budgets, masks) is what
# fuzzes.  (b, c, s, f, j, block_b)
SUBROUND_SHAPES = ((32, 8, 4, 1, 4, 8), (48, 16, 8, 2, 8, 16))


def _fuzz_subround_case(rng: np.random.Generator, b, c, s, f):
    """Random-but-consistent full-subround inputs.

    Coverage knobs drawn per example: hit-heaviness (collision pressure on
    few entries), queue prefill (empty -> completely full), recirculation
    budget (zero / scarce / abundant), lane validity (dense -> all-dead).
    Gate masks include validity, as the kernel contract requires.
    """
    universe = int(rng.integers(c, 4 * c + 1))
    keys = rng.choice(2 * universe, c, replace=False).astype(np.int32)
    hot = rng.random() < 0.7
    if hot:  # collision-heavy: queries hammer few installed entries
        pool = keys[rng.integers(0, max(1, c // 2), b)]
    else:
        pool = rng.integers(0, 2 * universe, b).astype(np.int32)
    q = jnp.asarray(pool, jnp.int32)

    valid_p = rng.choice([0.0, 0.5, 0.9, 1.0])
    valid = rng.random(b) < valid_p
    op_class = rng.integers(0, 4, b)  # 0 read, 1 write, 2 install, 3 dead
    want = jnp.asarray(valid & (op_class == 0), jnp.int32)
    wreq = jnp.asarray(valid & (op_class == 1), jnp.int32)
    inst = jnp.asarray(valid & (op_class == 2), jnp.int32)

    fill = rng.choice(["empty", "random", "full"])
    if fill == "empty":
        qlen = np.zeros(c, np.int32)
    elif fill == "full":
        qlen = np.full(c, s, np.int32)
    else:
        qlen = rng.integers(0, s + 1, c).astype(np.int32)
    front = rng.integers(0, s, c).astype(np.int32)
    budget = int(rng.choice([0, 1, int(rng.integers(2, 10)), 10_000]))

    return (
        hash128_u32(q),
        want, wreq, inst,
        jnp.asarray(rng.integers(0, f + 1, b), jnp.int32),       # frag
        jnp.asarray(rng.integers(1, f + 1, b), jnp.int32),       # nfrags
        q,                                                       # kidx
        jnp.asarray(rng.integers(0, 1500, b), jnp.int32),        # vlen
        jnp.asarray(rng.integers(0, 8, b), jnp.int32),           # client
        jnp.asarray(rng.integers(0, 1 << 20, b), jnp.int32),     # seq
        jnp.asarray(rng.integers(0, 100, b), jnp.int32),         # port
        jnp.asarray(rng.random(b), jnp.float32),                 # ts
        hash128_u32(jnp.asarray(keys)),                          # table
        jnp.asarray(rng.integers(0, 2, c), jnp.int32),           # occupied
        jnp.asarray(rng.integers(0, 2, c), jnp.int32),           # st_valid
        jnp.asarray(rng.integers(0, 5, c), jnp.int32),           # st_version
        jnp.asarray(rng.integers(-1, 8, c * s), jnp.int32),      # rt_client
        jnp.asarray(rng.integers(0, 99, c * s), jnp.int32),      # rt_seq
        jnp.asarray(rng.integers(0, 99, c * s), jnp.int32),      # rt_port
        jnp.asarray(rng.random(c * s), jnp.float32),             # rt_ts
        jnp.zeros(c * s, jnp.int32),                             # rt_acked
        jnp.asarray(rng.integers(-1, 2000, c * s), jnp.int32),   # rt_kidx
        jnp.asarray(qlen), jnp.asarray(front),
        jnp.asarray((front + qlen) % s, jnp.int32),              # rear
        jnp.asarray(rng.integers(0, 2, c * f), jnp.int32),       # ob_live
        jnp.asarray(rng.integers(-1, 2000, c * f), jnp.int32),   # ob_kidx
        jnp.asarray(rng.integers(0, 5, c * f), jnp.int32),       # ob_version
        jnp.asarray(rng.integers(0, 1500, c * f), jnp.int32),    # ob_vlen
        jnp.asarray(rng.integers(1, f + 1, c), jnp.int32),       # ob_frags
        jnp.int32(budget),
    )


def _check_subround_parity(seed):
    rng = np.random.default_rng(seed)
    b, c, s, f, j, block = SUBROUND_SHAPES[seed % len(SUBROUND_SHAPES)]
    args = _fuzz_subround_case(rng, b, c, s, f)
    want = SubroundOuts(*subround_ref(
        *args, queue_size=s, max_frags=f, max_serves=j))
    got = subround_op(*args, s, f, j, block_b=block, interpret=True)
    for name, g, w in zip(SubroundOuts._fields, got, want):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w),
            err_msg=f"subround.{name} (seed={seed}, b={b}, c={c}, s={s}, "
                    f"f={f})")


@fuzz()
def test_fuzz_subround_ref_vs_interpret(seed):
    _check_subround_parity(seed)


# ---------------------------------------------------------------------------
# property 2: fused window_step vs the seed-composed window, all schemes
# ---------------------------------------------------------------------------
_SIM_CACHE: dict = {}


def _window_pair(scheme):
    """(sim, fused, composed) — jitted once per (scheme, kernel backend)."""
    from test_switch_regression import _composed_window_step

    from repro.kvstore import simulator as sim_mod
    from repro.kvstore.fleet import RackSimulator
    from repro.kvstore.simulator import RackConfig
    from repro.kvstore.workload import Workload, WorkloadConfig

    key = (scheme, kn.kernel_backend())
    if key in _SIM_CACHE:
        return _SIM_CACHE[key]
    wl = Workload(WorkloadConfig(num_keys=3_000, offered_rps=1.2e6,
                                 write_ratio=0.1))
    cfg = RackConfig(scheme=scheme, cache_entries=16, num_servers=2,
                     client_batch=64, fetch_lanes=16, value_pad=64,
                     server_queue=16, subrounds=2)
    sim = RackSimulator(cfg, wl)
    if scheme == "orbitcache":
        sim.preload(wl.hottest_keys(16))
    elif scheme == "netcache":
        sim.preload(wl.hottest_keys(300))
    fused = jax.jit(lambda w, cr: sim_mod.window_step(
        cfg, sim.server_cfg, sim.client_cfg, sim.key_size, w, cr))
    composed = jax.jit(lambda w, cr: _composed_window_step(
        cfg, sim.server_cfg, sim.client_cfg, sim.key_size, w, cr))
    _SIM_CACHE[key] = (sim, wl, fused, composed)
    return _SIM_CACHE[key]


def _check_window_parity(scheme, seed):
    rng = np.random.default_rng(seed)
    sim, wl, fused, composed = _window_pair(scheme)
    base = sim.carry
    # randomized operating point: load, write mix, clock, RNG stream
    carry = base._replace(
        rng=jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1))),
        offered=jnp.float32(float(base.offered) * rng.uniform(0.1, 2.0)),
        write_ratio=jnp.float32(rng.uniform(0.0, 0.4)),
        now=jnp.float32(rng.uniform(0.0, 1e5)),
    )
    windows = int(rng.integers(1, 3))
    ca = cb = carry
    for w in range(windows):
        ca, ma = fused(wl.arrays, ca)
        cb, mb = composed(wl.arrays, cb)
    _assert_trees_equal(ma, mb, f"{scheme} metrics (seed={seed})")
    _assert_trees_equal(ca, cb, f"{scheme} carry (seed={seed})")


@pytest.mark.parametrize("scheme", ["orbitcache", "netcache", "nocache"])
def test_fuzz_window_fused_vs_composed(scheme):
    @fuzz()
    def prop(seed):
        _check_window_parity(scheme, seed)
    prop()


# ---------------------------------------------------------------------------
# slow deep profile: 200+ examples per scheme, BOTH kernel-capable backends
# (the acceptance run; kept out of tier-1 — run locally / in the fuzz job)
# ---------------------------------------------------------------------------
DEEP_EXAMPLES = max(200, N_EXAMPLES)


@pytest.mark.slow
def test_fuzz_subround_parity_deep():
    for i in range(DEEP_EXAMPLES):
        _check_subround_parity(BASE_SEED + i)


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("scheme", ["orbitcache", "netcache", "nocache"])
def test_fuzz_window_parity_deep(scheme, backend):
    kn.set_kernel_backend(backend)
    try:
        for i in range(DEEP_EXAMPLES):
            _check_window_parity(scheme, BASE_SEED + i)
    finally:
        kn.set_kernel_backend(None)
