"""Resource-sharing + multi-installment families: parity and plumbing.

Headline properties:

* **Degenerate equivalences.**  ``resource_sharing`` at
  ``link_capacity=0`` IS the Sec 3.1 front-end LP; ``multi_installment``
  at ``installments=1`` IS the paper's Sec 2 single-source program.
  Both are exact (same optimum, 1e-6), which anchors the new rows to
  already-proven code.
* **Scalar-simplex oracle parity.**  Batched IPM solves match each
  formulation's own scalar simplex at 1e-6 over randomized sweeps —
  with verification on and the oracle fallback OFF, so kernel bugs
  cannot hide behind a silent re-solve.
* **Engine plumbing.**  Mixed precision certifies the same optima, the
  sharded executor is bit-identical to local, warm sweeps match cold,
  and ``SystemSpec.extras`` round-trips through stacking/scenario/take
  (with the legacy keyword shim warning on the old call shape).
"""

import warnings

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline fallback: seeded-random shim
    from _hyp import given, settings, strategies as st

from repro.core.dlt import DLTEngine, InfeasibleError, SystemSpec, solve
from repro.core.dlt.stacking import BatchedSystemSpec

REL_TOL = 1e-6

ENG = DLTEngine(max_iter=60, verify=True, oracle_fallback=False)


def _rs_spec(seed, n, m, ell=None):
    rng = np.random.default_rng(seed)
    return SystemSpec(
        G=np.sort(rng.uniform(0.05, 1.5, n)),
        R=rng.uniform(0.0, 2.0, n),
        A=np.sort(rng.uniform(0.2, 6.0, m)),
        J=float(rng.uniform(1.0, 100.0)),
        extras={"link_capacity": float(rng.uniform(0.0, 0.5))
                if ell is None else ell},
    )


def _mi_spec(seed, m, r):
    rng = np.random.default_rng(seed)
    return SystemSpec(
        G=[float(rng.uniform(0.05, 1.0))],
        R=[float(rng.uniform(0.0, 2.0))],
        A=np.sort(rng.uniform(0.2, 6.0, m)),
        J=float(rng.uniform(1.0, 100.0)),
        extras={"installments": r},
    )


# ---------------------------------------------------------------------------
# degenerate equivalences
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 5), m=st.integers(1, 8), seed=st.integers(0, 10**6))
def test_resource_sharing_uncontended_is_frontend(n, m, seed):
    """ell = 0: EqL degenerates to T_f >= R_1 (implied by Eq 5) — the
    same program, so the same optimum or, when the releases cannot be
    chained (Eq 3), the same infeasibility verdict."""
    spec = _rs_spec(seed, n, m, ell=0.0)
    try:
        ref = solve(spec, frontend=True).finish_time
    except InfeasibleError:
        with pytest.raises(InfeasibleError):
            solve(spec, formulation="resource_sharing")
        return
    got = solve(spec, formulation="resource_sharing").finish_time
    assert got == pytest.approx(ref, rel=REL_TOL)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 8), seed=st.integers(0, 10**6))
def test_multi_installment_single_round_is_sec2(m, seed):
    """R = 1 IS the paper's Sec 2 single-source program."""
    spec = _mi_spec(seed, m, r=1)
    got = solve(spec, formulation="multi_installment").finish_time
    classic = SystemSpec(G=spec.G, R=spec.R, A=spec.A, J=spec.J)
    ref = solve(classic, frontend=False).finish_time
    assert got == pytest.approx(ref, rel=REL_TOL)


def test_shared_link_binds_and_installments_help():
    spec_free = _rs_spec(3, 2, 4, ell=0.0)
    spec_slow = SystemSpec(G=spec_free.G, R=spec_free.R, A=spec_free.A,
                           J=spec_free.J, extras={"link_capacity": 2.0})
    assert (solve(spec_slow, formulation="resource_sharing").finish_time
            > solve(spec_free, formulation="resource_sharing").finish_time)
    base = _mi_spec(7, 5, r=1)
    multi = SystemSpec(G=base.G, R=base.R, A=base.A, J=base.J,
                       extras={"installments": 4})
    t1 = solve(base, formulation="multi_installment").finish_time
    t4 = solve(multi, formulation="multi_installment").finish_time
    assert t4 <= t1 + 1e-9      # more rounds never hurt


# ---------------------------------------------------------------------------
# batched engine vs the scalar-simplex oracle (no fallback to hide bugs)
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(n=st.integers(1, 5), m=st.integers(1, 8), seed=st.integers(0, 10**6))
def test_resource_sharing_oracle_parity(n, m, seed):
    specs = [_rs_spec(seed + k, n, m) for k in range(3)]
    sol = ENG.solve_batch(specs, formulation="resource_sharing")
    for k, sp in enumerate(specs):
        if sol.status[k] != 0:
            continue
        ref = solve(sp, formulation="resource_sharing").finish_time
        assert sol.finish_time[k] == pytest.approx(ref, rel=REL_TOL)


@settings(max_examples=8, deadline=None)
@given(m=st.integers(1, 8), r=st.integers(1, 4), seed=st.integers(0, 10**6))
def test_multi_installment_oracle_parity(m, r, seed):
    # mixed-R batch: lanes land in different installment buckets
    specs = [_mi_spec(seed + k, m, r=1 + (r + k - 1) % 4) for k in range(3)]
    sol = ENG.solve_batch(specs, formulation="multi_installment")
    for k, sp in enumerate(specs):
        if sol.status[k] != 0:
            continue
        ref = solve(sp, formulation="multi_installment").finish_time
        assert sol.finish_time[k] == pytest.approx(ref, rel=REL_TOL)
        # fields.beta folds rounds to per-processor totals, mass = J
        assert sol.beta[k].sum() == pytest.approx(sp.J, rel=1e-6)


def test_resource_sharing_wide_family():
    """The acceptance sweep's M = 32 corner, warm and cold."""
    specs = [_rs_spec(100 + k, 2, 32) for k in range(3)]
    cold = ENG.solve_batch(specs, formulation="resource_sharing")
    warm = ENG.solve_batch(specs, formulation="resource_sharing", warm=True)
    ok = cold.status == 0
    assert ok.all()
    np.testing.assert_allclose(warm.finish_time[ok], cold.finish_time[ok],
                               rtol=REL_TOL)
    ref = solve(specs[0], formulation="resource_sharing").finish_time
    assert cold.finish_time[0] == pytest.approx(ref, rel=REL_TOL)


# ---------------------------------------------------------------------------
# precision + executor legs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["resource_sharing", "multi_installment"])
def test_mixed_precision_certifies_the_same_optima(name):
    specs = ([_rs_spec(k, 2, 6) for k in range(4)]
             if name == "resource_sharing"
             else [_mi_spec(k, 6, r=1 + k % 3) for k in range(4)])
    sol64 = ENG.configured(precision="fp64").solve_batch(specs,
                                                         formulation=name)
    solmx = ENG.configured(precision="mixed").solve_batch(specs,
                                                          formulation=name)
    ok = (sol64.status == 0) & (solmx.status == 0)
    assert ok.sum() >= 3
    np.testing.assert_allclose(solmx.finish_time[ok], sol64.finish_time[ok],
                               rtol=REL_TOL)


@pytest.mark.parametrize("name", ["resource_sharing", "multi_installment"])
def test_sharded_executor_is_bit_identical(name):
    specs = ([_rs_spec(10 + k, 2, 5) for k in range(5)]
             if name == "resource_sharing"
             else [_mi_spec(10 + k, 5, r=1 + k % 2) for k in range(5)])
    local = ENG.configured(executor="local").solve_batch(specs,
                                                         formulation=name)
    shard = ENG.configured(executor="sharded",
                           devices=1).solve_batch(specs, formulation=name)
    assert np.array_equal(local.status, shard.status)
    assert np.array_equal(local.finish_time, shard.finish_time)
    assert np.array_equal(local.beta, shard.beta)


def test_scalar_engine_matches_batched():
    specs = [_mi_spec(20 + k, 4, r=1 + k % 3) for k in range(3)]
    batched = ENG.solve_batch(specs, formulation="multi_installment")
    scalar = DLTEngine(engine="scalar", solver="simplex").solve_batch(
        specs, formulation="multi_installment")
    ok = (batched.status == 0) & (scalar.status == 0)
    np.testing.assert_allclose(batched.finish_time[ok],
                               scalar.finish_time[ok], rtol=REL_TOL)
    np.testing.assert_allclose(batched.beta[ok], scalar.beta[ok],
                               atol=1e-5)


# ---------------------------------------------------------------------------
# extras plumbing: SystemSpec -> stacking -> scenario/take round-trip
# ---------------------------------------------------------------------------

def test_extras_round_trip_through_stacking():
    specs = [_rs_spec(k, 2, 3) for k in range(3)]
    bs = BatchedSystemSpec.from_specs(specs)
    assert set(bs.extras) == {"link_capacity"}
    for k, sp in enumerate(specs):
        assert bs.extras["link_capacity"][k] == sp.extras["link_capacity"]
        assert bs.scenario(k).extras == sp.extras
    sub = bs.take(np.array([2, 0]))
    assert sub.extras["link_capacity"][0] == specs[2].extras["link_capacity"]


def test_extras_uniform_presence_is_required():
    specs = [_rs_spec(0, 2, 3),
             SystemSpec(G=[0.2, 0.3], R=[0.5, 0.7], A=[1.0, 1.2, 0.9],
                        J=12.0)]
    with pytest.raises(ValueError, match="link_capacity"):
        BatchedSystemSpec.from_specs(specs)


def test_batch_level_extras_and_legacy_kwargs_shim():
    plain = [SystemSpec(G=[0.2], R=[0.5], A=[1.0, 1.2], J=8.0)
             for _ in range(2)]
    bs = BatchedSystemSpec.from_specs(plain,
                                      extras={"installments": [2, 3]})
    assert bs.extras["installments"].tolist() == [2.0, 3.0]
    # the pre-registry call shape still works, with a deprecation warning
    with pytest.warns(DeprecationWarning):
        bs2 = BatchedSystemSpec.from_specs(plain, installments=2.0)
    assert bs2.extras["installments"].tolist() == [2.0, 2.0]
    # colliding channels are an error, not a silent override
    with pytest.raises(ValueError, match="installments"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            BatchedSystemSpec.from_specs(
                plain, extras={"installments": [2, 3]}, installments=2.0)


def test_missing_extra_names_the_declared_axes():
    spec = SystemSpec(G=[0.2, 0.3], R=[0.5, 0.7], A=[1.0, 1.2], J=8.0)
    with pytest.raises(ValueError, match="link_capacity"):
        solve(spec, formulation="resource_sharing")


def test_installments_must_be_positive_integers():
    bad = SystemSpec(G=[0.2], R=[0.5], A=[1.0, 1.2], J=8.0,
                     extras={"installments": 2.5})
    with pytest.raises(ValueError, match="integers"):
        solve(bad, formulation="multi_installment")
