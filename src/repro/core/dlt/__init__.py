"""Divisible Load Theory core — the paper's contribution as a library.

Public API:
    SystemSpec, Schedule, InfeasibleError          (types)
    DLTEngine, EngineConfig, get_default_engine    (the session API)
    solve, verify_schedule                         (Sec 3.1 / 3.2 LPs)
    get_formulation, Formulation, ...              (formulation registry)
    solve_single_source                            (Sec 2 closed form)
    monetary_cost, sweep_processors, plan_*        (Sec 6 trade-offs)
    speedup_grid                                   (Sec 5 Amdahl analysis)
    batched_solve, BatchedSystemSpec, ...          (batched vmap engine)
    compile_cache_info, enable_compile_cache       (compiled-shape cache ops)

Every free function is a thin shim over one shared default
:class:`~repro.core.dlt.engine.DLTEngine`; configure a session of your
own (``DLTEngine(formulation=..., precision=...)``) to pin knobs
once and reuse warm-started parametric sweeps and the compiled-shape
cache across the whole workload surface.
"""

from .batched import (
    STATUS_INFEASIBLE,
    STATUS_MAXITER,
    STATUS_OPTIMAL,
    BatchedSolution,
    BatchedSystemSpec,
    batched_solve,
    compile_cache_info,
    solve_lp_batch,
)
from .engine import (
    DLTEngine,
    EngineConfig,
    EngineStats,
    enable_compile_cache,
    get_default_engine,
)
from .formulations import (
    Formulation,
    available_formulations,
    get_formulation,
    register_formulation,
)
from .cost import (
    ProcessorSweep,
    TradeoffPlan,
    finish_time_gradient,
    monetary_cost,
    plan_with_both_budgets,
    plan_with_cost_budget,
    plan_with_time_budget,
    sweep_processors,
)
from .simplex import LPResult, linprog_simplex
from .single_source import finish_time_single_source, solve_single_source
from .solve import solve, verify_schedule
from .speedup import SpeedupGrid, speedup_grid
from .types import InfeasibleError, Schedule, SystemSpec

__all__ = [
    "SystemSpec",
    "Schedule",
    "InfeasibleError",
    "DLTEngine",
    "EngineConfig",
    "EngineStats",
    "get_default_engine",
    "compile_cache_info",
    "enable_compile_cache",
    "solve",
    "batched_solve",
    "solve_lp_batch",
    "BatchedSystemSpec",
    "BatchedSolution",
    "STATUS_OPTIMAL",
    "STATUS_MAXITER",
    "STATUS_INFEASIBLE",
    "verify_schedule",
    "Formulation",
    "get_formulation",
    "register_formulation",
    "available_formulations",
    "solve_single_source",
    "finish_time_single_source",
    "monetary_cost",
    "sweep_processors",
    "finish_time_gradient",
    "plan_with_cost_budget",
    "plan_with_time_budget",
    "plan_with_both_budgets",
    "ProcessorSweep",
    "TradeoffPlan",
    "speedup_grid",
    "SpeedupGrid",
    "linprog_simplex",
    "LPResult",
]
