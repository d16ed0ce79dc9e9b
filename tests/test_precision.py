"""Property tests for the mixed-precision IPM and cross-bucket warm seeding.

Three contracts from the precision policy (README "Precision policy"):

* ``precision="mixed"`` matches the fp64 engine to 1e-6 relative on the
  certified objective — including ill-conditioned families (near-zero
  source rates, near-degenerate processor chains) where a bare fp32
  factorization would drift.
* The policy degrades loudly, never silently: with refinement disabled
  the fp64 endgame still certifies, and when phase 1 is pinned past its
  design range the engine re-solves the failed lanes with the full-fp64
  executable and says so (``stats.precision_fallback_lanes``).
* Cross-bucket warm seeding (``warm_transfer``) reproduces the cold
  sweep bit-for-tolerance while spending strictly fewer IPM iterations
  on prefix families that span multiple warm M-buckets.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline profile: seeded-random fallback shim
    from _hyp import given, settings, strategies as st

from repro.core.dlt import (
    DLTEngine,
    STATUS_MAXITER,
    STATUS_OPTIMAL,
    SystemSpec,
)
from repro.core.dlt import precision as _precision
from repro.core.dlt.engine import WARM_M_BUCKET_EDGES

REL_TOL = 1e-6

# Module-level engines share their compiled-executable caches across
# examples.  Verification and the oracle fallback are off so parity is a
# genuine IPM-path comparison (the fp64 engine is the reference here,
# not the simplex).  Precision is pinned explicitly so the CI
# $DLT_PRECISION matrix leg cannot re-point the reference engine.
_BASE = dict(verify=False, oracle_fallback=False, warm_start=False)
ENG64 = DLTEngine(precision="fp64", **_BASE)
ENGMX = DLTEngine(precision="mixed", **_BASE)


def _family(rng, count, m_lo=2, m_hi=10, kind="baseline"):
    """Bench-recipe feasible families, optionally ill-conditioned."""
    specs = []
    for _ in range(count):
        m = int(rng.integers(m_lo, m_hi + 1))
        G = rng.uniform(0.1, 1.0, 2)
        R = np.sort(rng.uniform(0.0, 2.0, 2))
        A = rng.uniform(0.5, 4.0, m)
        if kind == "slow_sources":
            # near-zero source rates stretch the finish time by ~1e2 and
            # skew the normal-equation scaling far beyond fp32 comfort
            G = G * 1e-2
        elif kind == "degenerate":
            # near-identical processor rates: the chain ordering is
            # decided by 1e-9-relative differences
            A = np.full(m, A[0]) * (1.0 + 1e-9 * np.arange(m))
        specs.append(SystemSpec(G=G, R=R, A=A,
                                J=float(rng.uniform(50.0, 200.0))))
    return specs


def _assert_parity(sol_ref, sol_mx):
    """Statuses agree and certified objectives match to REL_TOL."""
    decided = ((sol_ref.status != STATUS_MAXITER)
               & (sol_mx.status != STATUS_MAXITER))
    np.testing.assert_array_equal(sol_ref.status[decided],
                                  sol_mx.status[decided])
    ok = decided & (sol_ref.status == STATUS_OPTIMAL)
    assert ok.any(), "family produced no certified lanes to compare"
    rel = (np.abs(sol_mx.finish_time[ok] - sol_ref.finish_time[ok])
           / np.abs(sol_ref.finish_time[ok]))
    assert float(rel.max()) < REL_TOL, f"worst rel err {rel.max():.3e}"


@given(seed=st.integers(0, 2**31 - 1),
       kind=st.sampled_from(["baseline", "slow_sources", "degenerate"]))
@settings(max_examples=6, deadline=None)
def test_mixed_matches_fp64(seed, kind):
    rng = np.random.default_rng(seed)
    specs = _family(rng, 8, kind=kind)
    s64 = ENG64.solve_batch(specs, frontend=False)
    smx = ENGMX.solve_batch(specs, frontend=False)
    assert s64.precision == "fp64" and smx.precision == "mixed"
    # telemetry shape contract: mixed carries per-lane counters, fp64
    # carries none
    assert s64.refine_iterations is None
    assert s64.phase1_handover is None
    assert s64.precision_fallback_mask is None
    assert smx.refine_iterations is not None
    assert smx.refine_iterations.shape == (len(specs),)
    assert smx.phase1_handover.shape == (len(specs),)
    assert smx.precision_fallback_mask is not None
    _assert_parity(s64, smx)


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=3, deadline=None)
def test_unrefined_fp32_still_certifies(seed):
    """refine_max=0: every phase-1 direction is raw fp32, yet the fp64
    endgame (phase 2) still certifies and matches the reference."""
    eng = ENGMX.configured(refine_max=0)
    rng = np.random.default_rng(seed)
    specs = _family(rng, 8)
    before = eng.stats.refine_iterations
    sol = eng.solve_batch(specs, frontend=False)
    assert eng.stats.refine_iterations == before  # loop disabled
    assert int(np.asarray(sol.refine_iterations).sum()) == 0
    _assert_parity(ENG64.solve_batch(specs, frontend=False), sol)


def test_stalled_phase1_falls_back_to_fp64(monkeypatch):
    """Pin phase 1 on forever (SWITCH_MU=0) with refinement disabled:
    pure-fp32 directions cannot certify, the engine must re-solve the
    failed lanes with the full-fp64 executable and surface the lanes in
    ``stats.precision_fallback_lanes`` / ``precision_fallback_mask`` —
    degradation is loud, and the final answer still matches fp64."""
    monkeypatch.setattr(_precision, "SWITCH_MU", 0.0)
    # fresh engine + off-default refine_tol: the patched SWITCH_MU is
    # baked in at trace time but is not part of the compile-cache key,
    # so the key must differ from every other engine in this process
    eng = DLTEngine(precision="mixed", refine_max=0, refine_tol=3.7e-7,
                    **_BASE)
    rng = np.random.default_rng(7)
    specs = _family(rng, 8)
    sol = eng.solve_batch(specs, frontend=False)
    assert eng.stats.precision_fallback_lanes > 0
    assert bool(np.asarray(sol.precision_fallback_mask).any())
    _assert_parity(ENG64.solve_batch(specs, frontend=False), sol)


@pytest.mark.parametrize("kernel", ["structured", "banded"])
def test_failed_fp32_factor_hands_over_to_fp64(monkeypatch, kernel):
    """A negative ridge makes every fp32 factor non-SPD (NaN directions,
    so phase 1 cannot take a step).  The lane must leave phase 1 at
    once, say so (``HANDOVER_NO_STEP``, counted per lane) and certify in
    the fp64 loop of the same executable, not burn its budget there and
    need the fp64 re-solve."""
    monkeypatch.setattr(_precision, "FP32_RIDGE", -4.0)
    # off-default refine_tol: the patched ridge is baked in at trace
    # time but is not part of the compile-cache key
    eng = DLTEngine(precision="mixed", kernel=kernel, refine_tol=3.3e-7,
                    **_BASE)
    specs = _family(np.random.default_rng(3), 6)
    sol = eng.solve_batch(specs, frontend=False)
    assert np.all(sol.status == STATUS_OPTIMAL)
    assert eng.stats.precision_fallback_lanes == 0
    assert np.all(sol.phase1_handover == _precision.HANDOVER_NO_STEP)
    assert eng.stats.phase1_handover_lanes == len(specs)
    _assert_parity(ENG64.solve_batch(specs, frontend=False), sol)


# --- cross-bucket warm seeding --------------------------------------

#: Sec 6 prefix recipe whose m = 1..24 family spans three warm M-buckets
#: (WARM_M_BUCKET_EDGES starts 4, 16, 64) — the transfer path has at
#: least two cold bucket-anchors to seed.
_SWEEP_M = 24

ENG_COLD = DLTEngine(precision="fp64", verify=False, oracle_fallback=False,
                     warm_start=False)
ENG_WARM = DLTEngine(precision="fp64", verify=False, oracle_fallback=False,
                     warm_start=True, warm_transfer=True)


def _sweep_spec(rng):
    return SystemSpec(
        G=np.sort(rng.uniform(0.05, 2.0, 3)),
        R=rng.uniform(0.0, 3.0, 3),
        A=np.sort(rng.uniform(0.2, 8.0, _SWEEP_M)),
        J=50.0,
    )


@given(seed=st.integers(0, 10**6))
@settings(max_examples=3, deadline=None)
def test_cross_bucket_warm_sweep_matches_cold(seed):
    rng = np.random.default_rng(seed)
    spec = _sweep_spec(rng)

    cold_before = ENG_COLD.stats.ipm_iterations
    cold = ENG_COLD.sweep(spec, frontend=False)
    cold_iters = ENG_COLD.stats.ipm_iterations - cold_before

    warm_before = ENG_WARM.stats
    warm = ENG_WARM.sweep(spec, frontend=False)
    warm_after = ENG_WARM.stats
    warm_iters = warm_after.ipm_iterations - warm_before.ipm_iterations

    # identical results ...
    np.testing.assert_array_equal(warm.m, cold.m)
    rel = np.abs(warm.finish_time - cold.finish_time) / cold.finish_time
    assert float(rel.max()) < REL_TOL
    np.testing.assert_allclose(warm.cost, cold.cost,
                               rtol=REL_TOL, equal_nan=True)
    # ... for strictly fewer IPM iterations, with cross-bucket transfer
    # actually engaged on a family spanning >= 2 warm M-buckets
    assert warm_iters < cold_iters, (warm_iters, cold_iters)
    assert warm_after.transfer_lanes > warm_before.transfer_lanes
    buckets = set(np.searchsorted(np.asarray(WARM_M_BUCKET_EDGES), warm.m))
    assert len(buckets) >= 2


def test_precision_keys_the_compile_cache():
    """fp64 and mixed must never share a compiled executable: solving
    the same family under the other policy is a fresh compile."""
    eng64 = DLTEngine(precision="fp64", **_BASE)
    specs = _family(np.random.default_rng(0), 4)
    eng64.solve_batch(specs, frontend=False)
    misses = eng64.stats.cache_misses
    engmx = eng64.configured(precision="mixed")  # shares the cache
    engmx.solve_batch(specs, frontend=False)
    assert engmx.stats.cache_misses > misses
