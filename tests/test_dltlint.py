"""dltlint: seeded-violation tests (each rule must catch its defect class)
plus clean-graph checks over the real registry."""

import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.dltlint import (
    Finding,
    LintReport,
    Severity,
    TraceArtifact,
    TraceTarget,
    Waiver,
    get_rules,
    iter_eqns,
    lint_registry,
    load_waivers,
    trace_target,
)
from repro.analysis.dltlint.rules import (
    BandedHonesty,
    BoundedLoops,
    ConstBloat,
    DtypeDrift,
    PallasVmem,
    RefineResidualPrecision,
    TransferPurity,
)
from repro.core.dlt.engine import DLTEngine
from repro.core.dlt.formulations import get_formulation
from repro.core.dlt.precision import (
    FP32_FACTOR_SCOPE,
    REFINE_RESIDUAL_SCOPE,
    x64_scope,
)
from repro.kernels.dlt_banded_chol.kernel import (
    banded_factor_pallas,
    vmem_estimate,
)


def _artifact(fn, *args, executor="local", max_iter=25, hlo_text=None,
              x64=True, precision="fp64"):
    """TraceArtifact for a hand-written function (seeded-defect harness)."""
    import contextlib
    ctx = x64_scope() if x64 else contextlib.nullcontext()
    with ctx:
        closed = jax.make_jaxpr(fn)(*args)
    return TraceArtifact(
        target=TraceTarget("seeded", "structured", executor,
                           precision=precision),
        jaxpr=closed, cache_key=("seeded",), max_iter=max_iter,
        hlo_text=hlo_text)


def _hits(findings, rule, severity=Severity.ERROR):
    return [f for f in findings
            if f.rule == rule and f.severity >= severity]


# ---------------------------------------------------------------------------
# DL001 — bounded loops
# ---------------------------------------------------------------------------

def test_dl001_catches_unbounded_while():
    def unbounded(x):
        # converges only through data: no iteration-count bound at all
        return jax.lax.while_loop(lambda v: jnp.max(v) > 1e-8,
                                  lambda v: v * 0.5, x)

    art = _artifact(unbounded, jnp.ones(4))
    errs = _hits(BoundedLoops().check(art), "DL001")
    assert errs and "no static integer trip bound" in errs[0].message


def test_dl001_catches_bound_above_budget():
    def overbudget(x):
        def cond(c):
            i, v = c
            return (i < 100) & (jnp.max(v) > 1e-8)

        def body(c):
            i, v = c
            return i + 1, v * 0.5

        return jax.lax.while_loop(cond, body, (0, x))[1]

    art = _artifact(overbudget, jnp.ones(4), max_iter=25)
    errs = _hits(BoundedLoops().check(art), "DL001")
    assert errs and errs[0].data["bound"] == 100


def test_dl001_accepts_budgeted_while():
    def budgeted(x):
        def cond(c):
            i, v = c
            return (i < 25) & (jnp.max(v) > 1e-8)

        def body(c):
            i, v = c
            return i + 1, v * 0.5

        return jax.lax.while_loop(cond, body, (0, x))[1]

    art = _artifact(budgeted, jnp.ones(4), max_iter=25)
    findings = BoundedLoops().check(art)
    assert not _hits(findings, "DL001")
    assert any(f.severity == Severity.INFO and f.data.get("bound") == 25
               for f in findings)


# ---------------------------------------------------------------------------
# DL002 — dtype drift
# ---------------------------------------------------------------------------

def test_dl002_catches_f64_truncation():
    def truncating(x):
        return (x.astype(jnp.float32) * 2.0).astype(jnp.float64)

    art = _artifact(truncating, jax.ShapeDtypeStruct((4,), jnp.float64))
    hits = _hits(DtypeDrift().check(art), "DL002", Severity.WARNING)
    assert hits and hits[0].data == {"from": "float64", "to": "float32"}


def test_dl002_clean_on_pure_f64():
    def pure(x):
        return jnp.sqrt(x) + x

    art = _artifact(pure, jax.ShapeDtypeStruct((4,), jnp.float64))
    assert not _hits(DtypeDrift().check(art), "DL002", Severity.WARNING)


def test_dl002_allowlists_scoped_fp32_factor_cast():
    def scoped(x):
        with jax.named_scope(FP32_FACTOR_SCOPE):
            y = x.astype(jnp.float32) * 2.0
        return y.astype(jnp.float64)

    art = _artifact(scoped, jax.ShapeDtypeStruct((4,), jnp.float64))
    findings = DtypeDrift().check(art)
    assert not _hits(findings, "DL002", Severity.WARNING)
    notes = [f for f in findings if f.data.get("scope") == FP32_FACTOR_SCOPE]
    assert notes and notes[0].severity == Severity.INFO


# ---------------------------------------------------------------------------
# DL007 — refinement residual precision
# ---------------------------------------------------------------------------

def test_dl007_catches_f32_residual():
    def bad(rhs, M):
        with jax.named_scope(REFINE_RESIDUAL_SCOPE):
            r = rhs.astype(jnp.float32) - M @ rhs.astype(jnp.float32)
        return r.astype(jnp.float64)

    art = _artifact(bad, jax.ShapeDtypeStruct((4,), jnp.float64),
                    jax.ShapeDtypeStruct((4, 4), jnp.float32),
                    precision="mixed")
    errs = _hits(RefineResidualPrecision().check(art), "DL007")
    assert errs and REFINE_RESIDUAL_SCOPE in errs[0].message


def test_dl007_warns_when_refinement_missing():
    def no_scope(x):
        return jnp.sqrt(x) + x

    art = _artifact(no_scope, jax.ShapeDtypeStruct((4,), jnp.float64),
                    precision="mixed")
    warns = _hits(RefineResidualPrecision().check(art), "DL007",
                  Severity.WARNING)
    assert warns and "missing" in warns[0].message


def test_dl007_silent_under_fp64_policy():
    def no_scope(x):
        return jnp.sqrt(x) + x

    art = _artifact(no_scope, jax.ShapeDtypeStruct((4,), jnp.float64))
    assert not RefineResidualPrecision().check(art)


def test_dl007_accepts_fp64_residual():
    def good(rhs, M):
        with jax.named_scope(REFINE_RESIDUAL_SCOPE):
            r = rhs - M @ rhs
        return r

    art = _artifact(good, jax.ShapeDtypeStruct((4,), jnp.float64),
                    jax.ShapeDtypeStruct((4, 4), jnp.float64),
                    precision="mixed")
    findings = RefineResidualPrecision().check(art)
    assert not _hits(findings, "DL007", Severity.WARNING)
    assert any(f.severity == Severity.INFO and f.data.get("eqns", 0) > 0
               for f in findings)


def test_dl007_real_mixed_trace_is_clean():
    """The engine's actual mixed banded program: scoped casts only, fp64
    residual — both precision rules must pass on the real graph."""
    art = trace_target(TraceTarget("nofrontend_reduced", "banded", "local",
                                   precision="mixed"))
    assert art.target.label.endswith("/mixed")
    d7 = RefineResidualPrecision().check(art)
    assert not _hits(d7, "DL007", Severity.WARNING)
    assert any(f.severity == Severity.INFO and f.data.get("eqns", 0) > 0
               for f in d7)
    assert not _hits(DtypeDrift().check(art), "DL002", Severity.WARNING)


# ---------------------------------------------------------------------------
# DL003 — const bloat
# ---------------------------------------------------------------------------

def test_dl003_catches_large_captured_constant():
    table = np.ones((256, 1024))          # 2 MiB, > 1 MiB threshold

    def bloated(x):
        return x + jnp.asarray(table).sum()

    art = _artifact(bloated, jnp.ones(4))
    errs = _hits(ConstBloat().check(art), "DL003")
    assert errs and errs[0].data["nbytes"] == table.nbytes
    assert "cache_key" in errs[0].data


def test_dl003_small_consts_are_info_only():
    small = np.ones(8)

    def fine(x):
        return x + jnp.asarray(small).sum()

    findings = ConstBloat().check(_artifact(fine, jnp.ones(4)))
    assert not _hits(findings, "DL003")
    assert any(f.severity == Severity.INFO for f in findings)


# ---------------------------------------------------------------------------
# DL004 — transfer purity
# ---------------------------------------------------------------------------

def test_dl004_catches_device_put_in_body():
    dev = jax.devices()[0]

    def impure(x):
        return jax.device_put(x, dev) * 2.0

    art = _artifact(impure, jnp.ones(4), executor="sharded")
    errs = _hits(TransferPurity().check(art), "DL004")
    assert errs and "device_put" in errs[0].message


def test_dl004_catches_host_callback():
    def cb(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2.0,
            jax.ShapeDtypeStruct((4,), jnp.float64), x)

    art = _artifact(cb, jnp.ones(4), executor="sharded")
    errs = _hits(TransferPurity().check(art), "DL004")
    assert errs and "pure_callback" in errs[0].message


def test_dl004_ignores_constant_staging():
    tbl = np.ones(8)

    def staging(x):
        # jnp.asarray of a numpy constant emits a placement-free
        # device_put — staging, not a transfer
        return x + jnp.asarray(tbl).sum()

    art = _artifact(staging, jnp.ones(4), executor="sharded")
    assert not TransferPurity().check(art)


# ---------------------------------------------------------------------------
# DL005 — banded-structure honesty
# ---------------------------------------------------------------------------

def test_dl005_catches_dishonest_structure():
    base = get_formulation("nofrontend_reduced")

    class Dishonest(type(base)):
        # drop every chain: rows keep their prefix-sum overlap, so the
        # normal equations are NOT block-tridiagonal anymore while the
        # blocks still claim they are
        name = "dishonest_nofrontend_reduced"

        def banded_structure(self, n, m):
            st = super().banded_structure(n, m)
            return st._replace(dprev=np.full_like(st.dprev, -1))

    errs = _hits(BandedHonesty().check_formulation(Dishonest()), "DL005")
    assert errs and errs[0].data["violations"] > 0


@pytest.mark.parametrize("name", ["frontend", "nofrontend",
                                  "nofrontend_reduced"])
def test_dl005_registry_formulations_are_honest(name):
    findings = BandedHonesty().check_formulation(get_formulation(name))
    assert findings and not _hits(findings, "DL005")


# ---------------------------------------------------------------------------
# DL006 — Pallas VMEM budget
# ---------------------------------------------------------------------------

def test_dl006_catches_oversized_blocks():
    K, s, p = 2, 512, 4                    # ~19 MiB working set
    f8 = jnp.float64

    def factor(D, O, U):
        return banded_factor_pallas(D, O, U, interpret=True)

    art = _artifact(factor,
                    jax.ShapeDtypeStruct((K, s, s), f8),
                    jax.ShapeDtypeStruct((K, s, s), f8),
                    jax.ShapeDtypeStruct((K, p, s), f8))
    errs = _hits(PallasVmem().check(art), "DL006")
    assert errs and errs[0].data["estimate_bytes"] > errs[0].data[
        "budget_bytes"]


def test_dl006_small_blocks_pass():
    K, s, p = 3, 8, 2
    f8 = jnp.float64

    def factor(D, O, U):
        return banded_factor_pallas(D, O, U, interpret=True)

    art = _artifact(factor,
                    jax.ShapeDtypeStruct((K, s, s), f8),
                    jax.ShapeDtypeStruct((K, s, s), f8),
                    jax.ShapeDtypeStruct((K, p, s), f8))
    findings = PallasVmem().check(art)
    assert not _hits(findings, "DL006")
    assert any(f.severity == Severity.INFO for f in findings)


def test_vmem_estimate_closed_form():
    assert vmem_estimate(512, 4) > 16 << 20
    assert vmem_estimate(8, 2) < 1 << 20


# ---------------------------------------------------------------------------
# real graphs stay clean; surfaces
# ---------------------------------------------------------------------------

def test_registry_sweep_is_clean():
    report = lint_registry(formulations=["nofrontend_reduced"],
                           kernels=["structured", "banded"],
                           executors=["local"])
    assert report.ok, report.format()
    # both precision legs per combination
    assert len(report.targets) == 4
    assert sum(t.endswith("/mixed") for t in report.targets) == 2


def test_engine_lint_surface():
    eng = DLTEngine(formulation="nofrontend_reduced", kernel="banded")
    report = eng.lint()
    assert report.ok, report.format()
    assert report.targets == ["nofrontend_reduced/banded/local"]


def test_trace_target_artifact_shape():
    art = trace_target(TraceTarget("nofrontend_reduced", "structured",
                                   "local"))
    prims = {e.primitive.name for e, _ in iter_eqns(art.jaxpr)}
    assert "while" in prims
    assert art.hlo_text is None            # no lowering unless asked


# ---------------------------------------------------------------------------
# diagnostics plumbing
# ---------------------------------------------------------------------------

def _err(rule="DL001", target="a/b/c"):
    return Finding(rule=rule, severity=Severity.ERROR, message="boom",
                   target=target)


def test_report_json_and_counts():
    rep = LintReport(findings=[_err()], targets=["a/b/c"])
    assert not rep.ok
    payload = json.loads(rep.to_json())
    assert payload["counts"]["error"] == 1
    assert payload["findings"][0]["rule"] == "DL001"


def test_waiver_downgrades_matching_error(tmp_path):
    path = tmp_path / "waivers.json"
    path.write_text(json.dumps(
        [{"rule": "DL001", "target": "a/b", "reason": "known, tracked"}]))
    rep = LintReport(findings=[_err(), _err(target="x/y/z")],
                     targets=["a/b/c", "x/y/z"])
    waived = rep.apply_waivers(load_waivers(str(path)))
    assert len(waived.errors) == 1         # only the non-matching one left
    downgraded = [f for f in waived.findings if f.data.get("waived")]
    assert downgraded and downgraded[0].severity == Severity.WARNING


def test_waiver_requires_reason(tmp_path):
    path = tmp_path / "waivers.json"
    path.write_text(json.dumps([{"rule": "DL001"}]))
    with pytest.raises(ValueError, match="reason"):
        load_waivers(str(path))


def test_severity_parse():
    assert Severity.parse("error") is Severity.ERROR
    assert Severity.parse(Severity.INFO) is Severity.INFO
    with pytest.raises(ValueError):
        Severity.parse("fatal")


def test_get_rules_rejects_unknown_ids():
    with pytest.raises(ValueError, match="DL999"):
        get_rules(["DL999"])
    assert [r.id for r in get_rules(["DL002", "DL001"])] == ["DL001",
                                                             "DL002"]


def test_waiver_matching_is_substring_on_target():
    w = Waiver(rule="DL001", target="banded", reason="r")
    assert w.matches(_err(target="nofrontend/banded/local"))
    assert not w.matches(_err(target="nofrontend/dense/local"))
