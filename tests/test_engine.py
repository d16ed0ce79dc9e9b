"""DLTEngine session API: config validation, warm-started parametric
sweeps, strict schedule mode, streaming map, compiled-cache counters, and
the free-function compatibility shims."""


import jax
import numpy as np
import pytest

from repro.core.dlt import (
    DLTEngine,
    EngineConfig,
    InfeasibleError,
    STATUS_INFEASIBLE,
    STATUS_MAXITER,
    STATUS_OPTIMAL,
    SystemSpec,
    batched_solve,
    compile_cache_info,
    enable_compile_cache,
    get_default_engine,
    solve,
    sweep_processors,
)
from repro.core.dlt.engine import COMPILE_CACHE_ENV
from repro.core.dlt.speedup import speedup_grid
from repro.core.dlt.stacking import BatchedSystemSpec

REL_TOL = 1e-6

BAD_SPEC = SystemSpec(G=[0.5, 0.5], R=[0.0, 100.0], A=[1.0], J=1.0)
GOOD_SPEC = SystemSpec(G=[0.2, 0.4], R=[0.0, 2.0], A=[2.0, 3.0], J=100.0)


def _sec6_spec(n=2, m=16, cost=False):
    """The paper's Sec 6 staple, truncated to (n sources, m processors)."""
    G = [0.5, 0.6, 0.65, 0.7, 0.8][:n]
    R = [2.0, 3.0, 3.5, 4.0, 4.5][:n]
    A = np.round(np.linspace(1.1, 3.0, m), 10)
    C = np.linspace(29.0, 10.0, m) if cost else None
    return SystemSpec(G=G, R=R, A=A, C=C, J=100)


# ---------------------------------------------------------------------------
# EngineConfig validation
# ---------------------------------------------------------------------------

def test_config_defaults_and_replace():
    cfg = EngineConfig()
    assert cfg.engine == "batched" and cfg.solver == "auto"
    cfg2 = cfg.replace(max_iter=60, engine="scalar", solver="simplex")
    assert cfg2.max_iter == 60 and cfg.max_iter == 40  # original untouched
    assert isinstance(cfg2.m_bucket_edges, tuple)


def test_config_solver_pins_engine_is_an_error():
    """The silent solver->scalar downgrade is a validated error now."""
    for solver in ("simplex", "highs"):
        with pytest.raises(ValueError, match="engine='scalar'"):
            EngineConfig(solver=solver)          # engine defaults to batched
    # the combination that actually honors the solver stays valid
    assert EngineConfig(solver="simplex", engine="scalar").solver == "simplex"


@pytest.mark.parametrize("kw", [
    dict(engine="gpu"),
    dict(solver="cplex"),
    dict(bucket="hash"),
    dict(formulation="sec99"),
    dict(max_iter=0),
    dict(tol=0.0),
    dict(tol=1.5),
    dict(chunk_size=0),
    dict(m_bucket_edges=()),
    dict(m_bucket_edges=(4, 2)),
    dict(m_bucket_edges=(0, 4)),
    dict(m_bucket_edges=(4, 4, 8)),
    dict(warm_stride=1),
    dict(warm_shift=0.0),
    dict(warm_shift=2.0),
    dict(compile_cache_size=0),
])
def test_config_validation_errors(kw):
    with pytest.raises(ValueError):
        EngineConfig(**kw)


def test_engine_constructor_overrides_and_configured_views():
    eng = DLTEngine(max_iter=30)
    assert eng.config.max_iter == 30
    view = eng.configured(verify=False)
    assert view.config.verify is False and eng.config.verify is True
    assert view._state is eng._state      # shared cache + counters
    assert eng.configured() is eng
    with pytest.raises(ValueError):
        eng.configured(solver="simplex")  # views are validated too


# ---------------------------------------------------------------------------
# Workload surface parity
# ---------------------------------------------------------------------------

def test_engine_solve_matches_free_function():
    sched_e = DLTEngine(solver="simplex", engine="scalar").solve(
        GOOD_SPEC, frontend=True)
    sched_f = solve(GOOD_SPEC, frontend=True, solver="simplex")
    assert sched_e.finish_time == pytest.approx(sched_f.finish_time,
                                                rel=REL_TOL)


def test_engine_solve_batch_parity_and_strict_schedule():
    eng = DLTEngine()
    sol = eng.solve_batch([BAD_SPEC, GOOD_SPEC], frontend=True)
    assert list(sol.status) == [STATUS_INFEASIBLE, STATUS_OPTIMAL]
    ref = solve(GOOD_SPEC, frontend=True, solver="simplex")
    assert sol.finish_time[1] == pytest.approx(ref.finish_time, rel=REL_TOL)
    # non-strict: silent None; strict: a named error
    assert sol.schedule(0) is None
    with pytest.raises(InfeasibleError, match=r"lane 0 .*status=2"):
        sol.schedule(0, strict=True)
    assert sol.schedule(1, strict=True) is not None


def test_strict_schedule_names_uncertified_lanes():
    """Budget-starved lanes raise RuntimeError naming status + fallback."""
    eng = DLTEngine(max_iter=1, oracle_fallback=False)
    sol = eng.solve_batch([GOOD_SPEC], frontend=True)
    assert sol.status[0] == STATUS_MAXITER
    with pytest.raises(RuntimeError, match="iteration budget exhausted"):
        sol.schedule(0, strict=True)
    with pytest.raises(RuntimeError, match="oracle_fallback=False"):
        sol.schedules(strict=True)


def test_warm_sweep_fewer_iterations_and_oracle_parity():
    """Acceptance: the warm-started Sec 6 prefix family converges in
    measurably fewer total IPM iterations than cold start, with finish
    times matching the scalar simplex oracle to 1e-6."""
    spec = _sec6_spec(n=2, m=32)
    warm_eng = DLTEngine(warm_start=True)
    cold_eng = DLTEngine(warm_start=False)
    sw = warm_eng.sweep(spec, frontend=False)
    sc = cold_eng.sweep(spec, frontend=False)
    np.testing.assert_allclose(sw.finish_time, sc.finish_time, rtol=REL_TOL)
    ws, cs = warm_eng.stats, cold_eng.stats
    assert ws.warm_lanes > 0
    assert ws.ipm_iterations < cs.ipm_iterations
    cspec = spec.canonical()[0]
    for m in (1, 9, 24, 32):
        ref = solve(cspec.subset_processors(m), frontend=False,
                    solver="simplex", presorted=True)
        k = np.flatnonzero(sw.m == m)
        assert k.size == 1
        assert sw.finish_time[k[0]] == pytest.approx(ref.finish_time,
                                                     rel=REL_TOL)


def test_warm_grid_parity():
    spec = SystemSpec(G=[0.5] * 3, R=[0.0] * 3, A=[2.0] * 8, J=100)
    kw = dict(source_counts=(1, 2, 3), processor_counts=(2, 4, 6, 8),
              frontend=False)
    gw = DLTEngine(warm_start=True).grid(spec, **kw)
    gc = DLTEngine(warm_start=False).grid(spec, **kw)
    np.testing.assert_allclose(gw.finish_time, gc.finish_time, rtol=REL_TOL)
    np.testing.assert_allclose(gw.speedup, gc.speedup, rtol=1e-5)


def test_engine_sweep_matches_scalar_engine_sweep():
    spec = _sec6_spec(n=2, m=10, cost=True)
    batched = DLTEngine().sweep(spec, frontend=True)
    scalar = DLTEngine(engine="scalar").sweep(spec, frontend=True)
    np.testing.assert_array_equal(batched.m, scalar.m)
    np.testing.assert_allclose(batched.finish_time, scalar.finish_time,
                               rtol=REL_TOL)
    np.testing.assert_allclose(batched.cost, scalar.cost, rtol=1e-4)


def test_solve_batch_honors_scalar_engine_config():
    """engine='scalar' keeps the one-LP-at-a-time loop on EVERY path —
    including solve_batch/map — honoring the pinned solver."""
    eng = DLTEngine(engine="scalar", solver="simplex")
    sol = eng.solve_batch([GOOD_SPEC, BAD_SPEC], frontend=False)
    assert list(sol.status) == [STATUS_OPTIMAL, STATUS_INFEASIBLE]
    ref = solve(GOOD_SPEC, frontend=False, solver="simplex")
    assert sol.finish_time[0] == pytest.approx(ref.finish_time, rel=REL_TOL)
    assert sol.formulation == "nofrontend"   # classic scalar mapping
    assert sol.fallback_count == 0 and sol.iterations.sum() == 0
    assert sol.schedule(0, strict=True) is not None
    assert eng.compile_cache_info()["size"] == 0     # no IPM compiles
    sols = list(eng.map([GOOD_SPEC], frontend=True))  # map rides it too
    assert sols[0].status[0] == STATUS_OPTIMAL


def test_fallback_counter_only_counts_oracle_runs():
    eng = DLTEngine(max_iter=1, oracle_fallback=False)
    sol = eng.solve_batch([GOOD_SPEC], frontend=True)
    assert sol.fallback_count == 1           # mask still marks the lane
    assert eng.stats.fallback_lanes == 0     # but no oracle actually ran
    eng2 = DLTEngine(max_iter=1, oracle_fallback=True)
    eng2.solve_batch([GOOD_SPEC], frontend=True)
    assert eng2.stats.fallback_lanes == 1


def test_engine_grid_raises_on_infeasible_cell():
    spec = SystemSpec(G=[0.5, 0.5], R=[0.0, 100.0], A=[1.0, 1.5], J=1.0)
    for eng in (DLTEngine(), DLTEngine(engine="scalar", warm_start=False)):
        with pytest.raises(InfeasibleError):
            eng.grid(spec, (1, 2), (1, 2), frontend=True)


def test_engine_advisor_runs_the_planners():
    adv = DLTEngine().advisor(_sec6_spec(n=2, m=10, cost=True),
                              frontend=True)
    plan = adv.with_cost_budget(budget_dollars=3450.0)
    assert plan.feasible and plan.recommended_m >= 1
    plan_t = adv.with_time_budget(budget_seconds=1e9)
    assert plan_t.feasible


def test_engine_map_chunks_and_strict():
    eng = DLTEngine(chunk_size=4)
    specs = [GOOD_SPEC] * 10
    sols = list(eng.map(iter(specs), frontend=True))
    assert [s.batch for s in sols] == [4, 4, 2]
    ref = solve(GOOD_SPEC, frontend=True, solver="simplex")
    for sol in sols:
        np.testing.assert_allclose(sol.finish_time, ref.finish_time,
                                   rtol=REL_TOL)
    # strict mode surfaces failed lanes as named errors mid-stream
    with pytest.raises(InfeasibleError, match="status=2"):
        list(eng.map([GOOD_SPEC, BAD_SPEC, GOOD_SPEC], frontend=True))
    # non-strict keeps streaming
    sols = list(eng.map([GOOD_SPEC, BAD_SPEC, GOOD_SPEC], frontend=True,
                        strict=False))
    assert sols[0].status[1] == STATUS_INFEASIBLE


# ---------------------------------------------------------------------------
# Compiled-shape cache + stats
# ---------------------------------------------------------------------------

def test_compile_cache_counts_hits_and_misses():
    eng = DLTEngine()
    eng.solve_batch([GOOD_SPEC] * 3, frontend=True)
    info1 = eng.compile_cache_info()
    assert info1["misses"] >= 1 and info1["size"] >= 1
    eng.solve_batch([GOOD_SPEC] * 3, frontend=True)   # same family shape
    info2 = eng.compile_cache_info()
    assert info2["hits"] > info1["hits"]
    assert info2["misses"] == info1["misses"]
    # views share the cache; fresh engines do not
    view = eng.configured(verify=False)
    assert view.compile_cache_info()["size"] == info2["size"]
    assert DLTEngine().compile_cache_info()["size"] == 0


def test_compile_cache_lru_eviction():
    eng = DLTEngine(compile_cache_size=1)
    eng.solve_batch([GOOD_SPEC], frontend=True)
    eng.solve_batch([GOOD_SPEC.subset_processors(1)], frontend=True)
    info = eng.compile_cache_info()
    assert info["size"] == 1 and info["maxsize"] == 1


@pytest.fixture
def restore_cache_config():
    """Undo enable_compile_cache's process-wide JAX settings."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_persistent_cache_dir_is_created_and_reported(
        tmp_path, monkeypatch, restore_cache_config):
    cache_dir = tmp_path / "xla-cache"
    monkeypatch.delenv(COMPILE_CACHE_ENV, raising=False)
    assert enable_compile_cache(cache_dir) == str(cache_dir)
    eng = DLTEngine()
    eng.solve_batch([GOOD_SPEC], frontend=True)
    info = eng.compile_cache_info()
    assert info["persist_dir"] == str(cache_dir)
    assert cache_dir.is_dir()
    assert info["persist_entries"] >= 1


def test_compile_cache_env_dir_is_never_overridden(
        tmp_path, monkeypatch, restore_cache_config):
    """$JAX_COMPILATION_CACHE_DIR (read by JAX itself) wins over the
    entry point's default directory."""
    env_dir = str(tmp_path / "from-env")
    monkeypatch.setenv(COMPILE_CACHE_ENV, env_dir)
    jax.config.update("jax_compilation_cache_dir", env_dir)
    assert enable_compile_cache(tmp_path / "default") == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_stats_ledger_and_reset():
    eng = DLTEngine()
    eng.solve_batch([GOOD_SPEC] * 2, frontend=True)
    st = eng.stats
    assert st.batches == 1 and st.lanes == 2 and st.ipm_iterations > 0
    assert st.cache_misses >= 1 and st.compile_ms > 0
    eng.solve_batch([GOOD_SPEC] * 2, frontend=True)     # cache hits only
    assert eng.stats.compile_ms == st.compile_ms
    eng.reset_stats()
    st2 = eng.stats
    assert st2.lanes == 0 and st2.cache_misses == 0 and st2.compile_ms == 0
    assert eng.compile_cache_info()["size"] >= 1    # cache itself survives


# ---------------------------------------------------------------------------
# Free-function shims
# ---------------------------------------------------------------------------

def test_module_compile_cache_info_reports_default_engine():
    batched_solve([GOOD_SPEC], frontend=True)
    info = compile_cache_info()
    assert info is not None and info["size"] >= 1
    assert info == get_default_engine().compile_cache_info()


def test_shims_reject_the_silent_solver_downgrade():
    """The PR-1-era implicit solver->scalar-engine downgrade (deprecated
    since the session API landed) is gone: pinning a solver with the
    batched engine raises through EngineConfig on the shims too."""
    spec = _sec6_spec(n=2, m=4, cost=True)
    with pytest.raises(ValueError, match="engine='scalar'"):
        sweep_processors(spec, frontend=True, solver="simplex")
    # the explicit combination keeps working
    ref = sweep_processors(spec, frontend=True, solver="simplex",
                           engine="scalar")
    assert np.all(np.isfinite(ref.finish_time))
    with pytest.raises(ValueError, match="engine='scalar'"):
        speedup_grid(SystemSpec(G=[0.5], R=[0.0], A=[2.0, 2.0], J=10),
                     source_counts=(1,), processor_counts=(1, 2),
                     frontend=True, solver="simplex")


def test_shims_reject_unknown_engine():
    spec = _sec6_spec(n=2, m=4)
    with pytest.raises(ValueError, match="unknown engine"):
        sweep_processors(spec, engine="quantum")
    from repro.core.advisor import ClusterAdvisor
    with pytest.raises(ValueError, match="unknown engine"):
        ClusterAdvisor.from_system_spec(spec, engine="quantum")


# ---------------------------------------------------------------------------
# BatchedSystemSpec.take edge cases
# ---------------------------------------------------------------------------

def test_take_empty_index_set():
    bs = BatchedSystemSpec.from_specs([GOOD_SPEC, BAD_SPEC])
    sub = bs.take([])
    assert sub.batch == 0
    assert sub.n_max == bs.n_max and sub.m_max == bs.m_max
    sub2 = bs.take(np.asarray([], dtype=np.int64), n_pad=3, m_pad=5)
    assert sub2.batch == 0 and sub2.G.shape == (0, 3)


def test_take_pad_growth_uses_inert_fill():
    spec = SystemSpec(G=[0.2, 0.4], R=[0.0, 1.0], A=[2.0, 3.0], J=50.0,
                      C=[5.0, 4.0])
    bs = BatchedSystemSpec.from_specs([spec])
    sub = bs.take(np.asarray([0, 0]), n_pad=4, m_pad=6)
    assert sub.G.shape == (2, 4) and sub.A.shape == (2, 6)
    np.testing.assert_allclose(sub.G[:, 2:], 1.0)   # inert padding values
    np.testing.assert_allclose(sub.R[:, 2:], 0.0)
    np.testing.assert_allclose(sub.A[:, 2:], 1.0)
    np.testing.assert_allclose(sub.C[:, 2:], 0.0)
    # true sizes, masks and the scenario roundtrip are preserved
    assert list(sub.n_sources) == [2, 2] and list(sub.n_procs) == [2, 2]
    assert sub.cell_mask.sum() == 2 * 2 * 2
    back = sub.scenario(1)
    np.testing.assert_allclose(back.G, spec.G)
    np.testing.assert_allclose(back.C, spec.C)
    # grown padding solves identically to the tight embedding
    tight = batched_solve([spec], frontend=True)
    grown = get_default_engine().configured(bucket="none").solve_batch(
        sub, frontend=True)
    np.testing.assert_allclose(grown.finish_time,
                               np.repeat(tight.finish_time, 2),
                               rtol=REL_TOL)


def test_take_preserves_cost_mask():
    priced = SystemSpec(G=[0.2], R=[0.0], A=[2.0], J=10.0, C=[3.0])
    free = SystemSpec(G=[0.2], R=[0.0], A=[2.0, 3.0], J=10.0)
    bs = BatchedSystemSpec.from_specs([priced, free])
    sub = bs.take(np.asarray([1, 0]))
    assert list(sub.has_cost) == [False, True]
    assert sub.scenario(0).C is None
    assert sub.scenario(1).C is not None


def test_take_rejects_too_small_pad():
    bs = BatchedSystemSpec.from_specs([GOOD_SPEC])
    with pytest.raises(ValueError, match="bucket shape"):
        bs.take(np.asarray([0]), m_pad=1)
    with pytest.raises(ValueError, match=">= \\(1, 1\\)"):
        bs.take(np.asarray([0]), n_pad=0, m_pad=0)
