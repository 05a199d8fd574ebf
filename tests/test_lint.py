"""The lint subsystem's own test suite.

Two halves:

  * **liveness** — every rule must FIRE on its seeded-violation fixture
    (``repro.analysis.fixtures``) with the right structured finding, and
    must PASS the matching clean twin.  Without this the linter could rot
    into a no-op while the tree stays green.
  * **clean tree** — the jaxpr rules hold over all six production entry
    points right now (the compile/run rules are exercised by the
    ``python -m repro.analysis.lint`` CLI in the CI lint job, which runs
    every rule over every entry under the interpret backend).
"""
import jax.numpy as jnp
import pytest

from repro.analysis import fixtures as fx
from repro.analysis import run_lint
from repro.analysis.entry_points import build_entry_points
from repro.analysis.findings import Severity, errors
from repro.analysis.rules import RULES

XS = jnp.zeros((8,), jnp.float32)


def _run(entry, rule):
    return run_lint([entry], [rule])


# ---------------------------------------------------------------------------
# rule liveness: seeded violation fires, clean twin passes
# ---------------------------------------------------------------------------
def test_no_scatter_fires_on_scatterful_scan():
    f = _run(fx.entry_for("scatterful", fx.scatterful_scan, XS), "no-scatter")
    assert len(f) == 1
    assert f[0].rule == "no-scatter" and f[0].severity == Severity.ERROR
    assert f[0].op.startswith("scatter")
    assert "scan" in f[0].path          # the path pins the eqn inside the scan
    assert f[0].site                    # and the user site is attributed


def test_no_scatter_passes_on_one_hot_scan():
    assert not _run(fx.entry_for("clean", fx.scatter_free_scan, XS),
                    "no-scatter")


def test_dtype_promotion_fires_on_mixed_add():
    u = jnp.zeros((), jnp.uint32)
    i = jnp.ones((), jnp.int32)
    f = _run(fx.entry_for("mixed", fx.mixed_dtype_accumulate, u, i),
             "dtype-promotion")
    assert len(f) == 1
    assert f[0].severity == Severity.ERROR and f[0].op == "add"
    assert "uint32" in f[0].message and "int32" in f[0].message


def test_dtype_promotion_passes_on_sat_add():
    u = jnp.zeros((), jnp.uint32)
    i = jnp.ones((), jnp.int32)
    assert not _run(fx.entry_for("explicit", fx.explicit_dtype_accumulate,
                                 u, i), "dtype-promotion")


def test_cond_in_scan_fires_and_select_passes():
    bad = _run(fx.entry_for("condscan", fx.cond_in_scan, XS),
               "no-dynamic-cond-in-scan")
    assert len(bad) == 1 and bad[0].op == "cond"
    assert bad[0].severity == Severity.ERROR
    assert not _run(fx.entry_for("selscan", fx.select_in_scan, XS),
                    "no-dynamic-cond-in-scan")


def test_donation_fires_on_undonated_chunk():
    f = _run(fx.entry_for_donation("undonated", fx.undonated_chunk),
             "donation")
    assert len(f) == 1 and f[0].severity == Severity.ERROR
    assert "does not donate" in f[0].message


def test_donation_passes_on_donated_chunk():
    assert not errors(_run(fx.entry_for_donation("donated",
                                                 fx.donated_chunk),
                           "donation"))


def test_retrace_guard_fires_on_shape_leak():
    f = _run(fx.make_retracing_entry(), "retrace-guard")
    assert len(f) == 1 and f[0].severity == Severity.ERROR
    assert "width" in f[0].message


def test_retrace_guard_passes_on_traced_axis():
    assert not _run(fx.make_stable_entry(), "retrace-guard")


def test_single_pallas_call_fires_on_wrong_count():
    # an entry that claims N kernels while tracing none must fail on the
    # backend kind it claims them for
    from repro.analysis.entry_points import backend_kind
    kind = backend_kind()
    e = fx.entry_for("kernel-free", lambda x: x * 2.0, XS)
    e.expected_pallas = {kind: 3}
    f = _run(e, "single-pallas-call")
    assert len(f) == 1 and "expected 3" in f[0].message
    e2 = fx.entry_for("kernel-free-ok", lambda x: x * 2.0, XS)
    e2.expected_pallas = {kind: 0}
    assert not _run(e2, "single-pallas-call")


# ---------------------------------------------------------------------------
# clean tree: the jaxpr rules hold on every production entry point
# ---------------------------------------------------------------------------
_JAXPR_RULES = ["no-scatter", "single-pallas-call", "dtype-promotion",
                "no-dynamic-cond-in-scan"]


@pytest.mark.parametrize("entry_name", [
    "subround_pipeline", "window_pipeline", "fleet.controller_chunk",
    "fleet.window_step", "fabric_chunk", "fabric_controller_chunk",
])
def test_production_entry_jaxpr_rules_clean(entry_name):
    entries = build_entry_points([entry_name])
    findings = run_lint(entries, _JAXPR_RULES)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_rule_registry_complete():
    assert set(RULES) == {
        "no-scatter", "single-pallas-call", "dtype-promotion",
        "no-dynamic-cond-in-scan", "donation", "retrace-guard",
    }
