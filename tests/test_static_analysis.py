"""Static placement-contract verifier (`repro.analysis`).

The positive gate — every registered scheme, kernel entry point, and the
engine tick analyze clean — auto-extends to future schemes through the
registry parametrization, mirroring test_differential.py. The negative
gate runs every seeded violation fixture and asserts the *exact* finding
codes, so the analyzer is proven to still catch each contract-bug class.
"""

import json
import os
import subprocess
import sys

import pytest

from repro import analysis
from repro.analysis import fixtures, lints, tracing
from repro.core.placement import registry

JAX_SCHEMES = registry.jax_schemes()
CFG = tracing.probe_config()


@pytest.mark.parametrize("sd,impl", JAX_SCHEMES,
                         ids=[sd.name for sd, _ in JAX_SCHEMES])
def test_registered_schemes_analyze_clean(sd, impl):
    findings, manifests = analysis.analyze_scheme(CFG, sd.name,
                                                  sd.n_classes, impl)
    assert findings == [], [str(f) for f in findings]
    assert set(manifests) == {"user_class", "gc_classes"}


@pytest.mark.parametrize("sd,impl", JAX_SCHEMES,
                         ids=[sd.name for sd, _ in JAX_SCHEMES])
def test_manifests_stay_inside_slice(sd, impl):
    """Behavioral restatement of the slice contract: every write carries the
    scheme's own prefix, every read is own-slice or an allowed shared
    field."""
    prefix = registry.slice_prefix(sd.name)
    _, manifests = analysis.analyze_scheme(CFG, sd.name, sd.n_classes, impl)
    for entry, m in manifests.items():
        for key in m.writes:
            assert key.startswith(prefix), (sd.name, entry, key)
        for key in m.reads:
            assert key.startswith(prefix) or \
                key in analysis.ALLOWED_SHARED_READS, (sd.name, entry, key)


def test_known_manifest_contents():
    """Spot-check the manifests carry real information, not vacuous sets:
    sepbit is stateless given ℓ, fk reads the clock and updates its BIT
    table on user writes only."""
    impls = {sd.name: impl for sd, impl in JAX_SCHEMES}
    _, sepbit = analysis.analyze_scheme(CFG, "sepbit", 6, impls["sepbit"])
    assert sepbit["user_class"].reads == ("ell",)
    assert sepbit["user_class"].writes == ()
    _, fk = analysis.analyze_scheme(CFG, "fk", 6, impls["fk"])
    assert fk["user_class"].reads == ("sch_fk_bit", "t")
    assert fk["user_class"].writes == ("sch_fk_bit",)
    assert fk["gc_classes"].writes == ()


def test_kernels_analyze_clean():
    per_kernel = analysis.analyze_kernels()
    assert set(per_kernel) == {
        "kernels.classify", "kernels.segment_select",
        "kernels.segment_select_batch", "kernels.classify_ref",
        "kernels.segment_select_ref"}
    for label, findings in per_kernel.items():
        assert findings == [], (label, [str(f) for f in findings])


def test_engine_tick_analyzes_clean():
    """One full user step (write + GC loop, registry-wide dispatch) keeps
    the carried state spec fixed and stays pure/overflow-free."""
    assert analysis.analyze_engine(CFG) == []


@pytest.mark.parametrize("gc_sched", ["rate_limited", "idle_window"])
def test_timing_engine_analyzes_clean(gc_sched):
    """The timing/SLO paths (latency accounting, histogram bucketing, GC
    scheduling deferral and end-of-tick charging) keep the same contracts:
    the lat_* slices are part of the carried spec (SA202-checked) and the
    float→int histogram-bucket cast is clip-bounded (no SA201)."""
    cfg = tracing.probe_config(timing=True, gc_sched=gc_sched)
    findings = analysis.analyze_engine(cfg)
    assert findings == [], [str(f) for f in findings]


FIXTURES = fixtures.violation_fixtures()


@pytest.mark.parametrize("fx", FIXTURES, ids=[f.name for f in FIXTURES])
def test_violation_fixtures_flagged_exactly(fx):
    if fx.kind == "scheme":
        findings, _ = analysis.analyze_scheme(CFG, fx.name, fx.n_classes,
                                              fx.impl)
    else:
        findings = analysis.analyze_fleet_fixture(CFG, fx)
    got = frozenset(f.code for f in findings)
    assert got == fx.expect, [str(f) for f in findings]


def test_fixture_zoo_covers_every_code():
    covered = frozenset().union(*(fx.expect for fx in FIXTURES))
    assert covered == frozenset(lints.CODES), \
        "every finding code needs a fixture proving it fires"


def test_drift_lint_catches_spec_mismatch():
    """The engine drift check is live: a synthetic trace whose state dtype
    changes across the tick is reported as SA202."""
    import jax
    import jax.numpy as jnp

    rec = tracing.trace(
        "synthetic.step",
        lambda st, x: dict(st, a=st["a"] * 0.5),
        ({"a": jax.ShapeDtypeStruct((), jnp.int32),
          "b": jax.ShapeDtypeStruct((4,), jnp.float32)},
         jax.ShapeDtypeStruct((), jnp.int32)),
        state_arg=0, state_out="root")
    codes = [f.code for f in lints.lint_drift(rec)]
    assert codes == ["SA202"]


def test_interval_engine_sees_through_pjit():
    """jnp.clip lowers to a jit-wrapped sub-jaxpr; the interval engine
    must recurse into it to see the literal clamp bounds."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.intervals import UNKNOWN, IntervalAnalysis

    closed = jax.make_jaxpr(lambda x: jnp.clip(x, 0, 5))(
        jax.ShapeDtypeStruct((), jnp.int32))
    (iv,) = IntervalAnalysis().run(closed, [UNKNOWN])
    assert iv == (0.0, 5.0)


def _run_cli(*args, timeout=600):
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        filter(None, [os.path.join(root, "src"),
                      os.environ.get("PYTHONPATH", "")])))
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        env=env, cwd=root, capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_cli_json_and_selftest(tmp_path):
    out = tmp_path / "report.json"
    proc = _run_cli("--json", str(out))
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(out.read_text())
    assert report["n_findings"] == 0
    assert set(report["schemes"]) == {sd.name for sd, _ in JAX_SCHEMES}
    assert report["schemes"]["dac"]["manifest"]["user_class"]["writes"] == \
        ["sch_dac_region"]
    assert report["fleet"]["findings"] == []

    proc = _run_cli("--selftest")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-800:]
    n = len(FIXTURES)
    assert f"{n}/{n} fixtures" in proc.stdout


def test_cli_rejects_unknown_scheme():
    """--schemes with a name outside the registry is a usage error (exit 2)
    naming the valid schemes, not a silently empty report."""
    proc = _run_cli("--schemes", "sepbit,nope", "--no-kernels",
                    "--no-engine", "--no-fleet")
    assert proc.returncode == 2, (proc.stdout, proc.stderr)
    assert "unknown scheme(s): nope" in proc.stderr
    assert "sepbit" in proc.stderr  # the valid-scheme list is printed
