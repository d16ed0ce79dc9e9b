"""Banded IPM kernel: structure invariants, parity, adaptive budgets.

The banded kernel factors the SAME LP in an equivalent row basis (rows
permuted into processor blocks, chained rows differenced), so its
arithmetic differs from the structured dense-Cholesky path — parity is
asserted at the solver's certification tolerance (1e-6, the same bound
the oracle verification uses), never bit-for-bit.  What IS exact is the
structure: for every formulation, shape and masked lane, the transformed
normal matrix must have the advertised block-tridiagonal-plus-border
pattern — that's the property test that catches a wrong permutation or
a missed dense coupling immediately.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline fallback: seeded-random shim
    from _hyp import given, settings, strategies as st

from repro.core.dlt import DLTEngine, EngineConfig, SystemSpec, solve
from repro.core.dlt.batched import (
    build_banded_family,
    build_family_lp,
)
from repro.core.dlt.formulations import (
    BatchFields,
    Formulation,
    FormulationCapabilities,
    get_formulation,
)
from repro.core.dlt.stacking import BatchedSystemSpec

REL_TOL = 1e-6
FORMULATIONS = ("frontend", "nofrontend", "nofrontend_reduced")


def _random_spec(seed, n, m):
    rng = np.random.default_rng(seed)
    return SystemSpec(
        G=np.sort(rng.uniform(0.05, 2.0, n)),
        R=rng.uniform(0.0, 3.0, n),
        A=np.sort(rng.uniform(0.2, 8.0, m)),
        J=float(rng.uniform(1.0, 200.0)),
    )


#: Module-level engines so the compiled-shape LRU amortizes across
#: examples (the property tests revisit the same padded shapes).
ENG_BANDED = DLTEngine(kernel="banded", verify=False, oracle_fallback=False,
                       banded_min_rows=1)
ENG_STRUCT = DLTEngine(kernel="structured", verify=False,
                       oracle_fallback=False)
ENG_DENSE = DLTEngine(kernel="dense", verify=False, oracle_fallback=False)


# ---------------------------------------------------------------------------
# structure invariants: the advertised pattern must actually hold
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", FORMULATIONS)
def test_banded_structure_is_block_tridiagonal(name):
    """F D F' under the banded transform never couples non-adjacent
    blocks — checked on random data for full AND masked lanes."""
    rng = np.random.default_rng(7)
    fm = get_formulation(name)
    for (N, M) in [(1, 1), (1, 6), (2, 1), (2, 8), (3, 5), (5, 8), (3, 16)]:
        dims = fm.family_dims(N, M)
        struct = fm.banded_structure(N, M)
        struct.validate(dims)
        specs = [_random_spec(int(rng.integers(1 << 30)), N, M),
                 _random_spec(int(rng.integers(1 << 30)),
                              max(1, N - 1), max(1, M // 2)),
                 _random_spec(int(rng.integers(1 << 30)), 1, max(1, M - 1))]
        bs = BatchedSystemSpec.from_specs(specs).take(
            np.arange(len(specs)), n_pad=N, m_pad=M)
        bfam = build_banded_family(build_family_lp(bs, fm), struct)
        g = bfam.geom
        block = struct.block
        band = block < g.K
        for lane in range(len(specs)):
            D = rng.uniform(0.5, 2.0, dims.nv)
            Mn = (bfam.F[lane] * D) @ bfam.F[lane].T
            coupled = np.abs(Mn) > 1e-12
            far = np.abs(block[:, None] - block[None, :]) > 1
            viol = coupled & far & band[:, None] & band[None, :]
            assert not viol.any(), (
                f"{name} ({N},{M}) lane {lane}: non-adjacent blocks coupled")


@pytest.mark.parametrize("name", FORMULATIONS)
def test_banded_transform_solves_the_same_lp(name):
    """The row transform is exactly invertible: transformed rows evaluated
    at a feasible point satisfy the transformed rhs identically."""
    fm = get_formulation(name)
    spec = _random_spec(3, 2, 5)
    bs = BatchedSystemSpec.from_specs([spec])
    fam = build_family_lp(bs, fm)
    bfam = build_banded_family(fam, fm.banded_structure(2, 5))
    g = bfam.geom
    rng = np.random.default_rng(0)
    z = rng.uniform(0.1, 2.0, fam.dims.nv)
    # residuals transform exactly like the rows: r_t - dcoef * r_prev
    r_std = fam.F[0] @ z - fam.b[0]
    r_perm = r_std[g.perm]
    expect = r_perm - bfam.dcoef[0] * np.where(
        g.has_prev, r_perm[g.dprev_c], 0.0)
    got = bfam.F[0] @ z - bfam.b[0]
    np.testing.assert_allclose(got, expect, atol=1e-10)


# ---------------------------------------------------------------------------
# kernel parity: banded == structured == dense to certification tolerance
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(n=st.integers(1, 5), m=st.integers(1, 8), seed=st.integers(0, 10**6))
def test_banded_matches_structured_kernel(n, m, seed):
    """Status parity and 1e-6 finish-time parity over N 1..5, M 1..8,
    with the unpacked fields verified against the ORIGINAL paper
    constraints (no oracle fallback to hide kernel bugs)."""
    specs = [_random_spec(seed + k, n, m) for k in range(4)]
    sol_b = ENG_BANDED.solve_batch(specs, frontend=False)
    sol_s = ENG_STRUCT.solve_batch(specs, frontend=False)
    assert np.array_equal(sol_b.status, sol_s.status)
    ok = sol_b.status == 0
    np.testing.assert_allclose(sol_b.finish_time[ok], sol_s.finish_time[ok],
                               rtol=REL_TOL, atol=1e-8)
    fm = get_formulation("nofrontend_reduced")
    bs = BatchedSystemSpec.from_specs(specs)
    verified = fm.verify_batch(bs, BatchFields(
        beta=sol_b.beta, finish=sol_b.finish_time,
        TS=sol_b.TS, TF=sol_b.TF))
    assert np.all(verified[ok])


@settings(max_examples=8, deadline=None)
@given(n=st.integers(1, 4), m=st.integers(1, 8), seed=st.integers(0, 10**6),
       frontend=st.booleans())
def test_banded_oracle_parity(n, m, seed, frontend):
    """The full pipeline (verify + oracle fallback) on the banded kernel
    agrees with the scalar simplex to 1e-6 on both formul. families."""
    specs = [_random_spec(seed + k, n, m) for k in range(3)]
    eng = ENG_BANDED.configured(verify=True, oracle_fallback=True)
    sol = eng.solve_batch(specs, frontend=frontend)
    for k, sp in enumerate(specs):
        if sol.status[k] != 0:
            continue
        ref = solve(sp, frontend=frontend).finish_time
        assert sol.finish_time[k] == pytest.approx(ref, rel=REL_TOL)


def test_dense_kernel_matches_structured():
    specs = [_random_spec(50 + k, 2, 6) for k in range(6)]
    sol_d = ENG_DENSE.solve_batch(specs, frontend=False)
    sol_s = ENG_STRUCT.solve_batch(specs, frontend=False)
    ok = (sol_d.status == 0) & (sol_s.status == 0)
    assert ok.sum() >= 4
    np.testing.assert_allclose(sol_d.finish_time[ok], sol_s.finish_time[ok],
                               rtol=REL_TOL, atol=1e-8)


def _ragged_family(seed, n_max, m_max, lanes=16):
    """A ragged family like the planning benchmark's: N cycles over
    1..n_max and M spreads over 1..m_max, so it pads to n_max x m_max."""
    rng = np.random.default_rng(seed)
    ms = np.linspace(1, m_max, lanes).round().astype(int)
    rng.shuffle(ms)
    return [SystemSpec(G=rng.uniform(0.5, 0.7, 1 + k % n_max),
                       R=np.sort(rng.uniform(2.0, 4.0, 1 + k % n_max)),
                       A=rng.uniform(1.1, 3.0, ms[k]),
                       J=float(rng.uniform(100.0, 500.0)))
            for k in range(lanes)]


@pytest.mark.parametrize("frontend,n,m,seed", [
    (False, 3, 20, 0), (False, 3, 20, 1), (True, 4, 16, 2)])
def test_small_block_path_matches_structured_kernel(frontend, n, m, seed):
    """The padded families whose fp64 blocks take the elementwise path
    (3 x 20 without a front end: s = 7; 4 x 16 with one: s = 4) solve as
    the structured kernel solves them, and the counter sees every lane."""
    specs = _ragged_family(seed, n, m)
    eng = DLTEngine(kernel="banded", bucket="none", verify=False,
                    oracle_fallback=False)
    st = DLTEngine(kernel="structured", bucket="none", verify=False,
                   oracle_fallback=False)
    sol_b = eng.solve_batch(specs, frontend=frontend)
    sol_s = st.solve_batch(specs, frontend=frontend)
    assert np.array_equal(sol_b.status, sol_s.status)
    ok = sol_b.status == 0
    assert ok.all()
    np.testing.assert_allclose(sol_b.finish_time, sol_s.finish_time,
                               rtol=1e-8)
    assert np.abs(sol_b.iterations - sol_s.iterations).max() <= 1
    assert eng.stats.banded_lanes == len(specs)
    assert eng.stats.small_block_lanes == len(specs)


def test_wide_blocks_keep_the_library_path():
    """Sixteen sources without a front end make s = 46 blocks, above
    SMALL_BLOCK_MAX: the factor keeps the library calls, the counter
    stays at 0, and the answers still match the structured kernel."""
    specs = [_random_spec(90 + k, 16, 2) for k in range(2)]
    eng = DLTEngine(kernel="banded", verify=False, oracle_fallback=False)
    sol_b = eng.solve_batch(specs, frontend=False)
    sol_s = ENG_STRUCT.solve_batch(specs, frontend=False)
    assert np.array_equal(sol_b.status, sol_s.status)
    np.testing.assert_allclose(sol_b.finish_time, sol_s.finish_time,
                               rtol=REL_TOL)
    assert eng.stats.banded_lanes == len(specs)
    assert eng.stats.small_block_lanes == 0


# ---------------------------------------------------------------------------
# kernel selection: auto routing, fallback, validation
# ---------------------------------------------------------------------------

def test_auto_routes_large_families_to_banded_small_to_structured():
    eng = DLTEngine(verify=False, oracle_fallback=False)  # kernel="auto"
    small = [_random_spec(k, 2, 4) for k in range(3)]     # 20 rows
    eng.solve_batch(small, frontend=False)
    assert eng.stats.banded_lanes == 0
    big = [_random_spec(k, 2, 16) for k in range(3)]      # 50 rows
    eng.solve_batch(big, frontend=False)
    assert eng.stats.banded_lanes == len(big)


class _NoStructureFormulation(Formulation):
    """A formulation that publishes no banded structure (base default)."""

    name = "test_no_structure"
    capabilities = FormulationCapabilities(
        supports_banded=False, supports_warm_transfer=False,
        oracle_kind="classic", spec_axes=("n", "m"))


def test_auto_falls_back_without_structure_banded_raises():
    base = get_formulation("nofrontend_reduced")
    fm = _NoStructureFormulation()
    # graft the reduced formulation's behavior, minus banded_structure
    for attr in ("family_dims", "build_batch_rows", "batch_column_mask",
                 "unpack_batch", "pack_batch", "constraint_checks"):
        setattr(fm, attr, getattr(base, attr))
    fm.frontend = False
    fm.has_intervals = True
    assert fm.banded_structure(2, 16) is None
    specs = [_random_spec(k, 2, 16) for k in range(3)]
    eng = DLTEngine(verify=False, oracle_fallback=False)
    sol = eng.solve_batch(specs, formulation=fm)       # auto: falls back
    assert eng.stats.banded_lanes == 0
    ref = ENG_STRUCT.solve_batch(specs, frontend=False)
    ok = (sol.status == 0) & (ref.status == 0)
    np.testing.assert_allclose(sol.finish_time[ok], ref.finish_time[ok],
                               rtol=REL_TOL)
    with pytest.raises(ValueError, match="supports_banded"):
        eng.configured(kernel="banded").solve_batch(specs, formulation=fm)


def test_kernel_and_budget_config_validation():
    with pytest.raises(ValueError, match="kernel"):
        EngineConfig(kernel="sparse")
    with pytest.raises(ValueError, match="banded_min_rows"):
        EngineConfig(banded_min_rows=0)
    with pytest.raises(ValueError, match="min_warm_iter"):
        EngineConfig(min_warm_iter=0)
    cfg = EngineConfig(kernel="banded", banded_min_rows=10, min_warm_iter=2,
                       adaptive_budget=False)
    assert cfg.replace(kernel="auto").kernel == "auto"


# ---------------------------------------------------------------------------
# adaptive warm budgets: policy + forced-failure recovery
# ---------------------------------------------------------------------------

def _prefix_spec(N=2, M=16):
    return SystemSpec(G=[0.5, 0.6, 0.65][:N], R=[2.0, 3.0, 3.5][:N],
                      A=np.round(np.linspace(1.1, 3.0, M), 10), J=100)


def test_warm_budget_policy():
    eng = DLTEngine(max_iter=25, min_warm_iter=4)
    nia = np.array([9, 10, 11, 13])
    sta = np.zeros(4, dtype=np.int64)
    b = eng._warm_budget(nia, sta)
    assert 4 <= b <= 25 and b % 2 == 0
    assert b == 12                                     # p75 = 11.5 -> 12
    # adaptive off, or no certified anchors -> full budget
    assert eng.configured(adaptive_budget=False)._warm_budget(nia, sta) == 25
    assert eng._warm_budget(nia, np.ones(4, dtype=np.int64)) == 25
    # floor + cap
    assert eng._warm_budget(np.array([1, 1]), np.zeros(2, np.int64)) == 4
    assert eng.configured(max_iter=6)._warm_budget(
        np.array([30, 30]), np.zeros(2, np.int64)) == 6


def test_forced_early_exit_lane_recovers_via_full_budget_resolve(monkeypatch):
    """Satellite: a warm lane that cannot converge within the (forced
    tiny) budget is re-solved cold at the full budget and still returns
    the correct, oracle-verified schedule."""
    spec = _prefix_spec(2, 16)
    eng = DLTEngine()
    monkeypatch.setattr(DLTEngine, "_warm_budget", lambda self, nia, sta: 1)
    sweep = eng.sweep(spec, frontend=False)
    assert eng.stats.warm_lanes > 0
    assert eng.stats.resolve_lanes > 0                 # budget 1 must fail
    cs = spec.canonical()[0]
    for m in (5, 11, 16):
        ref = solve(cs.subset_processors(m), frontend=False,
                    solver="simplex", presorted=True).finish_time
        k = int(np.flatnonzero(sweep.m == m)[0])
        assert sweep.finish_time[k] == pytest.approx(ref, rel=REL_TOL)


def test_banded_min_rows_consults_autotune_table(monkeypatch, tmp_path):
    """Satellite: banded_min_rows=None reads the per-backend table
    written by scripts/autotune_kernels.py; a pinned value beats it and
    the hard-coded 32 stays the fallback without a table."""
    import json

    path = tmp_path / "autotune.json"
    path.write_text(json.dumps({"cpu": {"banded_min_rows": 10}}))
    monkeypatch.setenv("DLT_KERNEL_AUTOTUNE", str(path))
    specs = [_random_spec(k, 2, 4) for k in range(3)]   # 20 rows: 10 < 20 < 32
    eng = DLTEngine(verify=False, oracle_fallback=False)
    eng.solve_batch(specs, frontend=False)
    assert eng.stats.banded_lanes == len(specs)         # tuned floor applies
    pinned = DLTEngine(verify=False, oracle_fallback=False,
                       banded_min_rows=25)
    pinned.solve_batch(specs, frontend=False)
    assert pinned.stats.banded_lanes == 0               # pin beats the table
    monkeypatch.setenv("DLT_KERNEL_AUTOTUNE",
                       str(tmp_path / "missing.json"))
    fallback = DLTEngine(verify=False, oracle_fallback=False)
    fallback.solve_batch(specs, frontend=False)
    assert fallback.stats.banded_lanes == 0             # default 32 again
    # malformed tables are ignored, never fatal
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    monkeypatch.setenv("DLT_KERNEL_AUTOTUNE", str(bad))
    DLTEngine(verify=False, oracle_fallback=False).solve_batch(
        specs, frontend=False)


def test_adaptive_budget_keeps_warm_sweep_results_identical():
    spec = _prefix_spec(2, 16)
    eng = DLTEngine()
    warm = eng.sweep(spec, frontend=False)
    cold = eng.configured(warm_start=False).sweep(spec, frontend=False)
    np.testing.assert_allclose(warm.finish_time, cold.finish_time,
                               rtol=REL_TOL)
    st = eng.stats
    assert st.warm_lanes > 0
    assert st.warm_iterations < st.cold_iterations
