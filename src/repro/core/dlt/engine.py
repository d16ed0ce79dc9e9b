"""DLTEngine — one configured session object behind every solve path.

The paper's workloads are parametric families: Sec 5 sweeps
(sources x processors) grids, Sec 6 sweeps processor prefixes of one
system, and a serving deployment answers streams of near-identical
scheduling queries.  Before this module each entry point (``solve``,
``batched_solve``, ``sweep_processors``, ``speedup_grid``,
``ClusterAdvisor.from_system_spec``) re-exposed an overlapping knob set
and rebuilt solver state from scratch, throwing away everything a family
shares.  The session API keeps it:

* :class:`EngineConfig` — every solver / formulation / batching /
  verification knob in one validated frozen dataclass, with
  ``replace()``-style overrides.
* :class:`DLTEngine` — the whole workload surface as methods
  (``solve``, ``solve_batch``, ``sweep``, ``grid``, ``advisor``,
  ``map``) over one owned compiled-executable LRU (hit/miss counters,
  on-disk persistence through the JAX compilation cache) and
  one stats ledger.
* **Warm-started IPM for parametric families**: prefix/grid sweeps solve
  a strided subset of anchor lanes cold, then restart every remaining
  lane's homogeneous self-dual embedding from the nearest anchor's
  shifted solution triple — same padded LP shape, so no repacking — and
  converge in a fraction of the cold iteration budget.  Results stay
  verified against the paper constraint sets and simplex-certified on
  fallback, exactly like cold solves.
* **Pluggable executors** (:mod:`repro.core.dlt.executors`): the engine
  resolves *what* to run (the kernel plan) and hands the compiled-lane
  execution to the config's executor — single-device ``local`` or
  ``shard_map``-over-a-lane-mesh ``sharded`` — with bit-identical
  results either way; compile-cache keys carry the executor token.

The free functions in :mod:`repro.core.dlt` remain as thin shims over a
shared default engine (:func:`get_default_engine`), so repeat calls
share one compiled-shape cache.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import os
import threading
import time
from typing import Iterable, Iterator, Optional, Sequence, Tuple, Union

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ... import tracing
from ...kernels.dlt_banded_chol import ops as _chol_kernels
from . import precision as _precision
from .batched import (
    COMPILE_CACHE_SIZE,
    DEFAULT_M_BUCKET_EDGES,
    STATUS_INFEASIBLE,
    STATUS_MAXITER,
    STATUS_OPTIMAL,
    BandedFamilyLP,
    BatchedSolution,
    FamilyLP,
    _banded_geometry,
    _banded_take,
    _group_lanes,
    _hsde_ipm,
    _hsde_ipm_banded,
    _hsde_ipm_banded_warm,
    _hsde_ipm_structured,
    _hsde_ipm_structured_warm,
    _hsde_ipm_dense_warm,
    banded_dual_to_std,
    banded_row_transfer,
    banded_warm_convert,
    build_banded_family,
    build_family_lp,
    densify_family,
)
from .cost import ProcessorSweep
from .executors import (
    Executor,
    available_executors,
    microbatch_slots,
    resolve_executor,
)
from .formulations import (
    BatchFields,
    Formulation,
    FormulationCapabilities,
    default_batched_formulation,
    get_formulation,
)
from .single_source import single_source_intervals
from .solve import solve as _scalar_solve
from .speedup import SpeedupGrid
from .stacking import BatchedSystemSpec
from .types import InfeasibleError, Schedule, SystemSpec

__all__ = [
    "EngineConfig",
    "EngineStats",
    "DLTEngine",
    "enable_compile_cache",
    "get_default_engine",
]

_ENGINES = ("batched", "scalar")
_BUCKETS = ("size", "none")
_SOLVERS = ("auto", "simplex", "highs")
_KERNELS = ("auto", "banded", "pallas_banded", "structured", "dense")

#: Row-count floor below which ``kernel="auto"`` keeps the structured
#: path: the block-tridiagonal scan only amortizes its per-step overhead
#: once the normal equations are big enough (measured break-even ~30
#: rows on 2-core CPU; the win grows superlinearly past it — ~7x at 50
#: rows, ~20x at 100).  This is the FALLBACK when ``banded_min_rows``
#: is left ``None`` and no autotune table covers the current backend —
#: run ``scripts/autotune_kernels.py`` to measure the break-even on
#: yours (see :func:`_autotuned_min_rows`).
BANDED_MIN_ROWS = 32

#: Environment variable overriding where the engine looks for the
#: per-backend kernel autotune table written by
#: ``scripts/autotune_kernels.py``.
KERNEL_AUTOTUNE_ENV = "DLT_KERNEL_AUTOTUNE"

#: Default autotune-table path (relative to the working directory —
#: the autotune script writes to the repo root by default).
KERNEL_AUTOTUNE_PATH = "KERNEL_AUTOTUNE.json"


@functools.lru_cache(maxsize=16)
def _read_autotune_table(path: str, mtime: float) -> Optional[dict]:
    # mtime keys the cache so a rewritten table is picked up mid-process
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        return None
    return table if isinstance(table, dict) else None


def _autotuned_min_rows(backend: str,
                        precision: str = "fp64") -> Optional[int]:
    """Measured banded/structured break-even for ``backend``, if tabled.

    Reads the JSON table written by ``scripts/autotune_kernels.py``
    (``$DLT_KERNEL_AUTOTUNE`` or ``KERNEL_AUTOTUNE.json``), shaped
    ``{backend: {"banded_min_rows": int, ...}, ...}``.  The autotune
    script records one break-even per precision policy —
    ``"banded_min_rows"`` for fp64 and ``"banded_min_rows_mixed"`` for
    the fp32-factor path (whose different build/factor cost profile can
    shift the crossover); a missing per-precision entry falls back to
    the fp64 one.  Returns ``None`` when no table or no entry for this
    backend exists — callers fall back to the hard-coded
    :data:`BANDED_MIN_ROWS`.
    """
    path = os.environ.get(KERNEL_AUTOTUNE_ENV, KERNEL_AUTOTUNE_PATH)
    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        return None
    table = _read_autotune_table(path, mtime)
    if table is None:
        return None
    keys = ["banded_min_rows"]
    if precision != "fp64":
        keys.insert(0, f"banded_min_rows_{precision}")
    for key in keys:
        try:
            rows = int(table[backend][key])
        except (KeyError, TypeError, ValueError):
            continue
        return rows if rows >= 1 else None
    return None

FormulationLike = Union[Formulation, str, None]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Every knob of the DLT solving session, validated in one place.

    Attributes:
      formulation: registry name (or :class:`Formulation`) pinned for the
        whole session; ``None`` keeps the classic per-call mapping
        (``frontend=True`` -> Sec 3.1, ``False`` -> the column-reduced
        Sec 3.2 program on batched paths, the full Sec 3.2 on scalar).
      solver: scalar LP backend — ``"auto"`` (HiGHS when scipy is
        present, else the self-contained simplex), ``"simplex"`` or
        ``"highs"``.  Pinning a solver requires ``engine="scalar"``: the
        batched interior-point path does not run it, and silently
        downgrading (the pre-session behavior) hid that.
      engine: ``"batched"`` solves families as jitted vmapped
        interior-point batches; ``"scalar"`` keeps the one-LP-at-a-time
        loop on every path.
      verify: re-check solutions against the paper constraint sets.
      oracle_fallback: re-solve uncertified lanes with the scalar simplex
        (recorded in ``BatchedSolution.fallback_mask`` — never silent).
      max_iter / tol: interior-point iteration budget and residual
        tolerance.  Small bursts routed over a 4 x 128 fleet need 26-27
        iterations from the cold start; 40 leaves room above them, and
        lanes that converge earlier exit earlier.
      chunk_size: scenarios per device batch — also the chunk length of
        :meth:`DLTEngine.map`.
      bucket / m_bucket_edges: size-bucketed batching of ragged families.
      kernel: linear-algebra kernel of the batched interior point —
        ``"auto"`` picks the banded path whenever the formulation
        publishes a :class:`~repro.core.dlt.formulations.BandedStructure`
        and the family has at least ``banded_min_rows`` constraint rows
        (falling back to ``"structured"`` otherwise); on the TPU under
        ``precision="mixed"`` it takes the Pallas tier, whose kernel
        then factors the fp32 phase (the TPU kernel compiler has no
        64-bit types, so fp64 factors always run the XLA scans);
        ``"banded"`` pins the block-tridiagonal-arrowhead Cholesky
        scans (a ``ValueError`` at solve time if the formulation has no
        structure); ``"pallas_banded"`` pins the Pallas port of the
        fp32 factor (a ``ValueError`` unless ``precision="mixed"``, and
        off the TPU unless ``pallas_interpret`` is set);
        ``"structured"`` pins the ``[F | I]`` dense-Cholesky path;
        ``"dense"`` runs the generic dense kernel (debug /
        apples-to-apples baselines).
      banded_min_rows: minimum constraint-row count for ``"auto"`` to
        choose the banded kernel.  ``None`` (default) consults the
        per-backend autotune table written by
        ``scripts/autotune_kernels.py`` and falls back to the
        hard-coded 32-row break-even (a 2-core CPU measurement) when
        no table covers the current backend.
      pallas_interpret: run the Pallas kernel in interpret mode (the
        body executes as plain jnp ops) — the CPU parity knob; makes
        ``kernel="pallas_banded"`` legal off the TPU.  It never runs on
        the TPU and never changes ``"auto"`` routing: interpret mode is
        far slower than the scan kernels, so it only runs when pinned.
      executor: how compiled lane batches run — ``"local"`` (one
        ``jit(vmap)`` on the default device, the classic path),
        ``"sharded"`` (``shard_map`` over a 1-D lane mesh across the
        visible devices; per-shard IPM loops exit independently), or an
        :class:`~repro.core.dlt.executors.Executor` instance.
      devices: cap on how many visible devices a multi-device executor
        spreads lanes over (``None`` = all; must be ``None`` when
        ``executor`` is an instance).
      warm_start: warm-start parametric families (``sweep`` / ``grid``):
        cold-solve every ``warm_stride``-th lane, restart the rest from
        the nearest anchor's shifted solution triple.
      warm_stride: anchor spacing (>= 2) of the warm two-phase plan.
      warm_shift: relative interior shift added to an anchor solution
        before it seeds a warm start (keeps the restart strictly
        interior and centered).
      adaptive_budget: run warm-seeded lanes under a REDUCED iteration
        budget derived from the observed anchor convergence (see
        :meth:`DLTEngine._warm_budget`); lanes that fail the reduced
        budget are automatically re-solved cold at the full ``max_iter``
        (counted in ``stats.resolve_lanes``) before any oracle fallback,
        so results are unchanged — only the straggler wall-clock is.
      min_warm_iter: floor of the adaptive warm budget.
      precision: numeric policy of the batched IPM — ``"fp64"`` factors
        the normal equations in double precision everywhere; ``"mixed"``
        builds and factors them in fp32 (both the scan and Pallas banded
        kernels plus the structured/dense Cholesky) while iterates are
        far from the boundary, polishing every solve with a bounded
        fp64-residual iterative-refinement loop, then finishes with the
        plain fp64 loop so certification is identical.  Lanes the mixed
        path still cannot certify are transparently re-solved with a
        full-fp64 executable (``stats.precision_fallback_lanes``).
        ``None`` (default) defers to ``$DLT_PRECISION``, falling back
        to ``"fp64"``.  The policy keys the AOT compile cache.
      refine_max: iterative-refinement correction cap per normal solve
        under ``precision="mixed"`` (0 disables refinement: phase 1
        steps along the raw fp32 directions).
      refine_tol: relative fp64-residual target of the refinement loop.
      warm_transfer: allow warm sweeps to seed a bucket's anchors from a
        neighboring ``(N, M-bucket)`` bucket's completed anchors via the
        formulation's banded row maps (cross-bucket dual transfer;
        ``stats.transfer_lanes``).  Only buckets with the same source
        count and a published ``BandedStructure`` transfer; anything
        else cold-starts exactly as before.
      compile_cache_size: entries kept in the engine's AOT-compiled
        family-shape LRU.  Compiles persist across *processes* through
        JAX's compilation cache, which is process-wide and placed by the
        entry point (:func:`enable_compile_cache`), never by a session.
    """

    formulation: FormulationLike = None
    solver: str = "auto"
    engine: str = "batched"
    verify: bool = True
    oracle_fallback: bool = True
    max_iter: int = 40
    tol: float = 1e-8
    chunk_size: int = 256
    bucket: str = "size"
    m_bucket_edges: Tuple[int, ...] = DEFAULT_M_BUCKET_EDGES
    kernel: str = "auto"
    banded_min_rows: Optional[int] = None
    pallas_interpret: bool = False
    executor: Union[str, Executor] = "local"
    devices: Optional[int] = None
    warm_start: bool = True
    warm_stride: int = 8
    warm_shift: float = 1e-2
    adaptive_budget: bool = True
    min_warm_iter: int = 4
    precision: Optional[str] = None
    refine_max: int = _precision.DEFAULT_REFINE_MAX
    refine_tol: float = _precision.DEFAULT_REFINE_TOL
    warm_transfer: bool = True
    compile_cache_size: int = COMPILE_CACHE_SIZE

    def __post_init__(self):
        object.__setattr__(self, "m_bucket_edges",
                           tuple(int(e) for e in self.m_bucket_edges))
        if self.engine not in _ENGINES:
            raise ValueError(
                f"unknown engine {self.engine!r}: use one of {_ENGINES}")
        if self.solver not in _SOLVERS:
            raise ValueError(
                f"unknown solver {self.solver!r}: use one of {_SOLVERS}")
        if self.bucket not in _BUCKETS:
            raise ValueError(
                f"unknown bucket mode {self.bucket!r}: use one of {_BUCKETS}")
        if self.solver != "auto" and self.engine == "batched":
            raise ValueError(
                f"solver={self.solver!r} pins the scalar LP backend, which "
                "the batched interior-point engine never runs — pass "
                "engine='scalar' to honor the pinned solver, or leave "
                "solver='auto'")
        if self.formulation is not None:
            try:
                get_formulation(self.formulation)
            except KeyError as e:
                raise ValueError(str(e)) from None
        if self.max_iter < 1:
            raise ValueError(f"max_iter must be >= 1, got {self.max_iter}")
        if not (0.0 < self.tol < 1.0):
            raise ValueError(f"tol must be in (0, 1), got {self.tol}")
        if self.chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {self.chunk_size}")
        edges = self.m_bucket_edges
        if not edges or any(e < 1 for e in edges) or list(edges) != sorted(set(edges)):
            raise ValueError(
                "m_bucket_edges must be a non-empty strictly increasing "
                f"sequence of positive ints, got {edges}")
        if self.kernel not in _KERNELS:
            raise ValueError(
                f"unknown kernel {self.kernel!r}: use one of {_KERNELS}")
        if self.banded_min_rows is not None and self.banded_min_rows < 1:
            raise ValueError(
                f"banded_min_rows must be >= 1 (or None to consult the "
                f"autotune table), got {self.banded_min_rows}")
        if isinstance(self.executor, str):
            if self.executor not in available_executors():
                raise ValueError(
                    f"unknown executor {self.executor!r}: use one of "
                    f"{available_executors()} or an Executor instance")
        elif not isinstance(self.executor, Executor):
            raise ValueError(
                f"executor must be a registry name or an Executor "
                f"instance, got {type(self.executor).__name__}")
        if self.devices is not None:
            if isinstance(self.executor, Executor):
                raise ValueError(
                    "devices= cannot be combined with an Executor "
                    "instance — configure the instance itself")
            if self.devices < 1:
                raise ValueError(f"devices must be >= 1, got {self.devices}")
        if self.min_warm_iter < 1:
            raise ValueError(
                f"min_warm_iter must be >= 1, got {self.min_warm_iter}")
        if self.warm_stride < 2:
            raise ValueError(
                f"warm_stride must be >= 2 (1 makes every lane a cold "
                f"anchor), got {self.warm_stride}")
        if not (0.0 < self.warm_shift <= 1.0):
            raise ValueError(
                f"warm_shift must be in (0, 1], got {self.warm_shift}")
        if self.precision is not None:
            _precision.resolve_precision(self.precision)  # raises on junk
        if self.refine_max < 0:
            raise ValueError(
                f"refine_max must be >= 0, got {self.refine_max}")
        if not (0.0 < self.refine_tol < 1.0):
            raise ValueError(
                f"refine_tol must be in (0, 1), got {self.refine_tol}")
        if self.compile_cache_size < 1:
            raise ValueError(
                f"compile_cache_size must be >= 1, got {self.compile_cache_size}")

    def replace(self, **overrides) -> "EngineConfig":
        """A copy with ``overrides`` applied (re-validated)."""
        return dataclasses.replace(self, **overrides)


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """Cumulative session counters (snapshot — see ``DLTEngine.stats``)."""

    batches: int = 0            # solve_batch calls completed
    lanes: int = 0              # scenarios solved through the IPM
    warm_lanes: int = 0         # lanes restarted from an anchor solution
    cold_iterations: int = 0    # IPM iterations spent on cold lanes
    warm_iterations: int = 0    # IPM iterations spent on warm lanes
    banded_lanes: int = 0       # lanes routed through the banded scan kernel
    pallas_lanes: int = 0       # lanes routed through the Pallas banded kernel
    kernel_fallbacks: int = 0   # auto-routing downgrades (structureless
                                # formulation -> structured), per lane group
    resolve_lanes: int = 0      # warm lanes re-solved at the full budget
    fallback_lanes: int = 0     # lanes re-solved by the simplex oracle
    cache_hits: int = 0         # compiled-executable LRU hits
    cache_misses: int = 0       # compiled-executable LRU misses (compiles)
    cache_lookups: int = 0      # compiled-executable LRU lookups
                                # (invariant: hits + misses == lookups)
    cache_contention: int = 0   # lookups that blocked on a peer thread's
                                # in-flight compile of the same shape
    compile_ms: int = 0         # wall milliseconds spent compiling misses
                                # (summed over threads compiling at once)
    refine_iterations: int = 0  # fp64-residual refinement corrections
                                # spent by mixed-precision solves
    precision_fallback_lanes: int = 0  # mixed lanes re-solved with the
                                # full-fp64 executable
    phase1_handover_lanes: int = 0  # mixed lane solves whose fp32 phase
                                # ended on a step that did not lower mu
    transfer_lanes: int = 0     # anchors warm-seeded from a neighboring
                                # bucket via cross-bucket dual transfer
    ipm_lane_slots: int = 0     # lane-iterations the executables issued:
                                # per micro-batch, its width (pad lanes
                                # included) x its slowest lane's iterations
    lp_cells: int = 0           # sum of N*M over the lanes solved through
                                # the IPM
    lp_cell_slots: int = 0      # sum of N_pad*M_pad over the same lanes

    @property
    def ipm_iterations(self) -> int:
        """Total interior-point iterations across all lanes."""
        return self.cold_iterations + self.warm_iterations


class _CompileLatch:
    """One in-flight compile of one cache key.

    The owning thread compiles, publishes the executable (or the
    exception) here, then sets ``done``; peer threads that need the
    SAME key block on this event only — lookups of other keys never
    wait behind a compile.
    """

    __slots__ = ("done", "exe", "exc")

    def __init__(self):
        self.done = threading.Event()
        self.exe = None
        self.exc: Optional[BaseException] = None


#: Stripe count for the in-flight compile-latch table.  Only the latch
#: bookkeeping is striped — the ready-executable LRU stays behind one
#: (cheap, never held during a compile) lock so eviction order is the
#: exact global LRU the cache-size contract promises.
_LATCH_STRIPES = 8


class _EngineState:
    """Mutable session state shared by an engine and its configured() views.

    All of it is lock-protected: ``lru_lock`` guards the compiled-
    executable OrderedDict (held only for dict ops, never during a
    compile), each stripe lock guards one shard of the in-flight latch
    table, and ``counter_lock`` guards the stats ledger.  ``scopes``
    carries per-thread counter-scope stacks (see
    :meth:`DLTEngine.counter_scope`).
    """

    def __init__(self):
        from collections import OrderedDict

        self.compiled: "OrderedDict[tuple, object]" = OrderedDict()
        self.lru_lock = threading.Lock()
        self.stripe_locks = tuple(
            threading.Lock() for _ in range(_LATCH_STRIPES))
        self.inflight: Tuple[dict, ...] = tuple(
            {} for _ in range(_LATCH_STRIPES))
        self.counter_lock = threading.Lock()
        self.scopes = threading.local()
        self.counters = dict(
            batches=0, lanes=0, warm_lanes=0,
            cold_iterations=0, warm_iterations=0, banded_lanes=0,
            pallas_lanes=0, kernel_fallbacks=0,
            resolve_lanes=0, fallback_lanes=0,
            cache_hits=0, cache_misses=0,
            cache_lookups=0, cache_contention=0, compile_ms=0,
            refine_iterations=0, precision_fallback_lanes=0,
            phase1_handover_lanes=0, transfer_lanes=0,
            ipm_lane_slots=0, lp_cells=0, lp_cell_slots=0)

    def bump(self, **by):
        with self.counter_lock:
            for k, v in by.items():
                self.counters[k] += int(v)
        stack = getattr(self.scopes, "stack", None)
        if stack:
            for scope in stack:
                for k, v in by.items():
                    scope[k] += int(v)

    def stripe_of(self, key: tuple) -> int:
        return hash(key) % _LATCH_STRIPES

    def cache_get(self, key: tuple):
        """LRU lookup (refreshes recency); ``None`` when absent."""
        with self.lru_lock:
            exe = self.compiled.get(key)
            if exe is not None:
                self.compiled.move_to_end(key)
            return exe

    def cache_put(self, key: tuple, exe, maxsize: int) -> None:
        """Publish a compiled executable, evicting in exact LRU order."""
        with self.lru_lock:
            self.compiled[key] = exe
            self.compiled.move_to_end(key)
            while len(self.compiled) > maxsize:
                self.compiled.popitem(last=False)


#: The variable through which JAX itself places its persistent
#: compilation cache; where it is set, nothing here overrides it.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(default_dir: Union[str, os.PathLike]) -> str:
    """Persist every compiled executable of this process; returns the dir.

    Entry points (benchmarks, ``chip_smoke.py``) call this once before
    their first solve.  Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX
    has already read it and that directory is kept; otherwise
    ``default_dir`` is used — a fixed path in the checkout, since the
    path is part of what a later process must find again.  Either way
    only the size and time thresholds are lowered, so every engine
    executable persists, however small or quick to compile.
    """
    if not os.environ.get(COMPILE_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(default_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def _executable_name(kind: str, precision: str, warm: bool) -> str:
    """The compiled IPM's module name, e.g. ``dlt_ipm.banded.fp64.cold``.

    It names the executable's events in a profiler trace (as
    ``jit_<name>``), so that a reading of them survives edits of the
    program.
    """
    return f"dlt_ipm.{kind}.{precision}.{'warm' if warm else 'cold'}"


def _family_take(fam: FamilyLP, pos: np.ndarray) -> FamilyLP:
    """Lanes ``pos`` of a padded family (shape unchanged)."""
    return FamilyLP(c=fam.c[pos], F=fam.F[pos], b=fam.b[pos],
                    art=fam.art[pos], dims=fam.dims)


@dataclasses.dataclass(frozen=True)
class _KernelPlan:
    """One group's kernel routing: which instantiation + its built family.

    ``kind`` is the RESOLVED kernel ("structured" / "banded" / "dense" —
    never "auto"); ``bfam`` carries the banded-basis family when the
    banded kernel was selected and ``A`` the densified constraint tensor
    for the dense kernel.
    """

    kind: str
    fm_name: str
    fam: FamilyLP
    bfam: Optional[BandedFamilyLP] = None
    A: Optional[np.ndarray] = None


def _plan_take(plan: _KernelPlan, pos: np.ndarray) -> _KernelPlan:
    """Lanes ``pos`` of a kernel plan (kind and geometry unchanged)."""
    return dataclasses.replace(
        plan, fam=_family_take(plan.fam, pos),
        bfam=None if plan.bfam is None else _banded_take(plan.bfam, pos),
        A=None if plan.A is None else plan.A[pos])


#: Processor-count bucket edges used while warm-starting a parametric
#: family.  Much coarser than the throughput ladder on purpose: an
#: anchor can only seed lanes that share its padded LP shape, and the
#: two-phase anchor/rest plan pays a fixed dispatch cost per group, so
#: warm sweeps trade a bounded extra padding step for FEW large groups
#: in which most lanes start next to a solved neighbor instead of at
#: the cold HSDE point.
WARM_M_BUCKET_EDGES = (4, 16, 64, 256, 1024)


def _fields_take(fields: BatchFields, idx: np.ndarray) -> BatchFields:
    """Row-select batch fields, including per-formulation extras."""
    return BatchFields(
        beta=fields.beta[idx], finish=fields.finish[idx],
        TS=None if fields.TS is None else fields.TS[idx],
        TF=None if fields.TF is None else fields.TF[idx],
        extra=None if fields.extra is None else
        {k: v[idx] for k, v in fields.extra.items()})


class DLTEngine:
    """A configured DLT solving session.

    Construct once, then run the whole workload surface through it::

        eng = DLTEngine(max_iter=30)       # registry picks the formulation
        eng.solve(spec)                    # one Schedule
        eng.solve_batch(specs)             # BatchedSolution (ragged ok)
        eng.sweep(spec, m_max=32)          # Sec 6 prefix family (warm)
        eng.grid(spec, (1, 2, 3), (4, 8)) # Sec 5 speedup surface (warm)
        eng.advisor(spec)                  # Sec 6 budget planners
        for sol in eng.map(spec_stream):   # serving-style chunked stream
            ...

    The engine owns the AOT-compiled family-shape LRU (shared with every
    ``configured()`` view) and counts hits/misses/fallbacks/iterations
    in ``stats``; compiled executables persist across processes through
    the JAX compilation cache wherever the entry point enabled it
    (:func:`enable_compile_cache`).

    **Concurrency model.**  A session (and its ``configured()`` views)
    may be driven from many threads at once.  The solve path mutates no
    global state — the audit, per layer:

    - configs (``EngineConfig``), specs, formulation capabilities and
      compile keys are frozen dataclasses / plain tuples; each call
      allocates its own batch arrays and carries;
    - :func:`~.precision.x64_scope` (``jax.enable_x64(True)``, the
      dtype scope every solve chunk runs under) is thread-local in jax,
      so concurrent fp32 / fp64 sessions do not leak into each other;
    - module-level caches on the path (`formulations`/`executors`
      registries, autotune tables) are populated at import time or via
      ``functools.lru_cache`` — both safe to read concurrently;
    - the only shared MUTABLE state is this session's compiled-shape
      LRU and its stats ledger, both lock-protected: a missing shape is
      compiled by exactly one thread while peers block on that entry's
      latch (never the whole cache — see :meth:`compile_cache_info`'s
      ``contention`` counter), and counter bumps take a lock plus
      thread-local :meth:`counter_scope` deltas.

    Because compiled executables are pure functions of their key and
    every window pads onto the same micro-batch ladder, results are
    bit-identical no matter which thread (or how many) ran the solve.
    """

    def __init__(self, config: Optional[EngineConfig] = None, **overrides):
        if config is None:
            config = EngineConfig(**overrides)
        elif overrides:
            config = config.replace(**overrides)
        self.config = config
        self._state = _EngineState()
        self._executor: Optional[Executor] = None
        self._exec_lock = threading.Lock()

    # ---- configuration ---------------------------------------------------

    def configured(self, **overrides) -> "DLTEngine":
        """A view of this session with config overrides applied.

        The view shares the compiled-executable cache and the stats
        ledger with its parent, so shim calls with per-call knobs still
        amortize compilation across the process.
        """
        if not overrides:
            return self
        eng = object.__new__(DLTEngine)
        eng.config = self.config.replace(**overrides)
        eng._state = self._state
        eng._executor = None
        eng._exec_lock = threading.Lock()
        return eng

    def _formulation(self, frontend: bool,
                     formulation: FormulationLike) -> Formulation:
        which = formulation if formulation is not None else self.config.formulation
        if which is None:
            return default_batched_formulation(frontend)
        return get_formulation(which)

    @staticmethod
    def _caps(fm: Formulation) -> FormulationCapabilities:
        """The formulation's declared capabilities (required by the engine).

        Kernel routing, warm transfer and axis validation are all driven
        by the declaration — never by formulation names — so an instance
        without one cannot be scheduled.
        """
        caps = fm.capabilities
        if caps is None:
            raise ValueError(
                f"formulation {fm.name!r} declares no capabilities — set "
                "the `capabilities` class attribute (FormulationCapabilities) "
                "and add it to the registry via "
                "repro.core.dlt.formulations.register()")
        return caps

    # ---- stats + compiled-cache introspection ----------------------------

    @property
    def stats(self) -> EngineStats:
        with self._state.counter_lock:
            return EngineStats(**self._state.counters)

    def reset_stats(self) -> None:
        """Zero the counters (the compiled cache is kept)."""
        with self._state.counter_lock:
            for k in self._state.counters:
                self._state.counters[k] = 0

    @contextlib.contextmanager
    def counter_scope(self):
        """Counter deltas made by THIS thread while the scope is open.

        Yields a dict (every counter name, starting at zero) that
        accumulates each ``bump`` the calling thread performs inside
        the ``with`` block.  Unlike before/after :attr:`stats`
        snapshots, the deltas are unpolluted by concurrent solves on
        other threads sharing this session — the race-free way for a
        service loop to attribute compiles/fallbacks to its own window.
        Scopes nest (each open scope on this thread sees the bump).
        """
        st = self._state
        scope = {k: 0 for k in st.counters}
        stack = getattr(st.scopes, "stack", None)
        if stack is None:
            stack = st.scopes.stack = []
        stack.append(scope)
        try:
            yield scope
        finally:
            stack.remove(scope)

    def compile_cache_info(self) -> dict:
        """Compiled-family cache state: LRU shapes + hit/miss/persist.

        ``lookups`` / ``contention`` expose the concurrency counters
        (``hits + misses == lookups``; ``contention`` is lookups that
        blocked on a peer thread's in-flight compile); ``in_flight`` is
        the number of compiles currently owned by some thread and
        ``stripes`` the latch-table stripe count.  ``persist_dir`` is the
        process's JAX compilation-cache directory (``None`` while the
        persistent cache is off) and ``persist_entries`` its entry count.
        """
        cfg, st = self.config, self._state
        with st.lru_lock:
            size, keys = len(st.compiled), list(st.compiled)
        with st.counter_lock:
            hits = st.counters["cache_hits"]
            misses = st.counters["cache_misses"]
            lookups = st.counters["cache_lookups"]
            contention = st.counters["cache_contention"]
        info = {
            "size": size,
            "maxsize": cfg.compile_cache_size,
            "keys": keys,
            "hits": hits,
            "misses": misses,
            "lookups": lookups,
            "contention": contention,
            "in_flight": sum(len(t) for t in st.inflight),
            "stripes": len(st.stripe_locks),
            "persist_dir": None,
            "persist_entries": None,
        }
        persist = (jax.config.jax_compilation_cache_dir
                   if jax.config.jax_enable_compilation_cache else None)
        if persist:
            info["persist_dir"] = persist
            if os.path.isdir(persist):
                info["persist_entries"] = sum(1 for _ in os.scandir(persist))
        return info

    # ---- kernel routing + compiled executables ---------------------------

    def _resolve_executor(self) -> Executor:
        """The config's executor, instantiated once per engine view."""
        if self._executor is None:
            with self._exec_lock:
                if self._executor is None:
                    self._executor = resolve_executor(self.config.executor,
                                                      self.config.devices)
        return self._executor

    def _precision_policy(self) -> str:
        """The resolved numeric policy (config value or $DLT_PRECISION)."""
        return _precision.resolve_precision(self.config.precision)

    def banded_min_rows(self) -> Tuple[int, str]:
        """Effective ``auto`` break-even and where it came from.

        The source is ``"config"`` (a pinned ``banded_min_rows``), the
        path of the autotune table that covers this backend and policy,
        or ``"default"`` (:data:`BANDED_MIN_ROWS`).
        """
        if self.config.banded_min_rows is not None:
            return self.config.banded_min_rows, "config"
        tuned = _autotuned_min_rows(jax.default_backend(),
                                    self._precision_policy())
        if tuned is None:
            return BANDED_MIN_ROWS, "default"
        return tuned, os.environ.get(KERNEL_AUTOTUNE_ENV, KERNEL_AUTOTUNE_PATH)

    def _kernel_plan(self, fm: Formulation, sub: BatchedSystemSpec,
                     fam: FamilyLP) -> _KernelPlan:
        """Resolve the config's ``kernel`` knob for one padded group.

        ``auto`` routes through the banded kernel whenever the
        formulation publishes a banded structure AND the family is big
        enough to amortize the block scan (``banded_min_rows``, which
        consults the per-backend autotune table when left ``None``).
        On the TPU under ``precision="mixed"`` it takes the Pallas tier:
        there the kernel factors the fp32 phase, the one program of the
        kernel the chip's compiler accepts (it has no 64-bit types, so
        the fp64 factor always runs the XLA scans), and never in
        interpret mode.  It falls back to
        the structured dense-Cholesky path for structureless
        formulations (recorded in ``stats.kernel_fallbacks``) and for
        small families.  Pinning ``kernel="banded"`` on a structureless
        formulation, or ``kernel="pallas_banded"`` where the Pallas
        kernel cannot run, is a ``ValueError`` rather than a silent
        downgrade.
        """
        cfg = self.config
        kind = cfg.kernel
        struct = None
        if (kind in ("auto", "banded", "pallas_banded")
                and self._caps(fm).supports_banded):
            struct = fm.banded_structure(sub.n_max, sub.m_max)
        mixed = self._precision_policy() == "mixed"
        if kind == "pallas_banded":
            if struct is None:
                raise ValueError(
                    f"kernel='pallas_banded' but formulation {fm.name!r} "
                    "declares supports_banded=False — use kernel='auto' "
                    "(structured fallback) or kernel='structured'")
            if not mixed:
                raise ValueError(
                    "kernel='pallas_banded' factors the fp32 phase of "
                    "precision='mixed' only (the TPU kernel compiler has "
                    "no 64-bit types) — set precision='mixed' or use "
                    "kernel='banded'")
            backend = jax.default_backend()
            if not _chol_kernels.pallas_supported(
                    backend, interpret=cfg.pallas_interpret):
                raise ValueError(
                    f"kernel='pallas_banded' is not supported on the "
                    f"{backend!r} backend — the Pallas dlt_banded_chol "
                    "kernel compiles on TPU only, and pallas_interpret "
                    "(the CPU parity knob) never runs on the chip; use "
                    "kernel='auto' / 'banded'")
        elif kind in ("auto", "banded"):
            if struct is None:
                if kind == "banded":
                    raise ValueError(
                        f"kernel='banded' but formulation {fm.name!r} "
                        "declares supports_banded=False — use kernel='auto' "
                        "(structured fallback) or kernel='structured'")
                self._state.bump(kernel_fallbacks=1)
                kind = "structured"
            elif (kind == "auto"
                  and fam.dims.n_rows < self.banded_min_rows()[0]):
                kind = "structured"
            elif (kind == "auto" and mixed and not cfg.pallas_interpret
                  and _chol_kernels.pallas_supported(jax.default_backend())):
                kind = "pallas_banded"
            else:
                kind = "banded"
        if kind in ("banded", "pallas_banded"):
            return _KernelPlan(kind=kind, fm_name=fm.name, fam=fam,
                               bfam=build_banded_family(fam, struct))
        if kind == "dense":
            return _KernelPlan(kind="dense", fm_name=fm.name, fam=fam,
                               A=densify_family(fam))
        return _KernelPlan(kind="structured", fm_name=fm.name, fam=fam)

    def _executable(self, plan: _KernelPlan, B: int, warm: bool,
                    max_iter: int):
        """AOT-compiled kernel for one (plan, batch, budget) shape (LRU'd).

        The compile itself is delegated to the config's executor (one
        ``jit(vmap)`` locally, ``shard_map`` over the lane mesh when
        sharded); the LRU key carries the executor's ``cache_token`` so
        views with different placement never share an executable.

        Concurrency contract: exactly ONE thread compiles a missing
        shape.  Peers needing the same key block on that entry's latch
        (counted in ``cache_contention``) and take the published
        executable as a hit; lookups of other keys proceed without
        waiting.  Every call counts one ``cache_lookups`` and exactly
        one of ``cache_hits`` / ``cache_misses``, so
        ``hits + misses == lookups`` holds under any interleaving.
        """
        cfg, st = self.config, self._state
        executor = self._resolve_executor()
        key = self._cache_key(plan, B, warm, max_iter,
                              executor.cache_token())
        st.bump(cache_lookups=1)
        exe = st.cache_get(key)
        if exe is not None:
            st.bump(cache_hits=1)
            return exe
        stripe = st.stripe_locks[st.stripe_of(key)]
        table = st.inflight[st.stripe_of(key)]
        with stripe:
            # Re-check under the stripe lock: a peer may have published
            # between the LRU miss above and here (check-then-act race).
            exe = st.cache_get(key)
            if exe is not None:
                st.bump(cache_hits=1)
                return exe
            latch = table.get(key)
            owner = latch is None
            if owner:
                latch = table[key] = _CompileLatch()
        if not owner:
            latch.done.wait()
            if latch.exc is not None:
                st.bump(cache_misses=1, cache_contention=1)
                raise latch.exc
            st.bump(cache_hits=1, cache_contention=1)
            return latch.exe
        st.bump(cache_misses=1)
        prec = self._precision_policy()
        t0 = time.perf_counter()
        try:
            fn, in_axes, args = self._kernel_signature(plan, B, warm,
                                                       max_iter)
            with tracing.span("dlt.compile", kernel=plan.kind,
                              precision=prec, B=B, warm=warm):
                exe = executor.compile(
                    fn, in_axes, args,
                    name=_executable_name(plan.kind, prec, warm))
        except BaseException as e:
            latch.exc = e
            raise
        else:
            st.bump(compile_ms=round((time.perf_counter() - t0) * 1e3))
            latch.exe = exe
            st.cache_put(key, exe, cfg.compile_cache_size)
            return exe
        finally:
            with stripe:
                table.pop(key, None)
            latch.done.set()

    def _cache_key(self, plan: _KernelPlan, B: int, warm: bool,
                   max_iter: int, etok: Tuple) -> Tuple:
        """Compile-LRU key of one (plan, batch, budget, executor) shape.

        The precision policy (and, under ``"mixed"``, the refinement
        knobs) key every entry: an fp64 and a mixed executable of the
        same family shape are different compiled programs.
        """
        cfg = self.config
        tol = float(cfg.tol)
        dims = plan.fam.dims
        prec = self._precision_policy()
        ptok = (prec if prec == "fp64"
                else (prec, int(cfg.refine_max), float(cfg.refine_tol)))
        if plan.kind in ("banded", "pallas_banded"):
            g = plan.bfam.geom
            return (plan.kind, plan.fm_name, B, g.m, g.nv, g.K, g.s, g.p,
                    plan.bfam.w, max_iter, tol, warm,
                    cfg.pallas_interpret, ptok, etok)
        if plan.kind == "dense":
            return ("dense", B, dims.n_rows, dims.n_std, max_iter, tol,
                    warm, ptok, etok)
        return ("structured", B, dims.n_rows, dims.nv, dims.n_eq,
                max_iter, tol, warm, ptok, etok)

    def _kernel_signature(self, plan: _KernelPlan, B: int, warm: bool,
                          max_iter: int):
        """``(fn, in_axes, args)`` the executor compiles for one shape.

        ``fn`` is the per-lane IPM instantiation with the budget and
        tolerance baked in, ``in_axes`` its vmap axes and ``args`` the
        :class:`jax.ShapeDtypeStruct` stack of the padded operands —
        the exact compile contract, shared by :meth:`_executable` and
        the static tracer (:meth:`trace_plan`).
        """
        cfg = self.config
        tol = float(cfg.tol)
        dims = plan.fam.dims
        f8 = np.dtype(np.float64)
        sds = jax.ShapeDtypeStruct
        mrows, nv, n_std = dims.n_rows, dims.nv, dims.n_std
        pkw = {}
        if self._precision_policy() == "mixed":
            pkw = dict(precision="mixed", refine_max=int(cfg.refine_max),
                       refine_tol=float(cfg.refine_tol))
        winit = [sds((B, n_std), f8), sds((B, mrows), f8),
                 sds((B, n_std), f8)]
        if plan.kind in ("banded", "pallas_banded"):
            g = plan.bfam.geom
            w = plan.bfam.w
            kern = _hsde_ipm_banded_warm if warm else _hsde_ipm_banded
            kw = dict(max_iter=max_iter, tol=tol, geom=g, **pkw)
            if plan.kind == "pallas_banded":
                kw.update(impl="pallas", interpret=cfg.pallas_interpret)
            fn = functools.partial(kern, **kw)
            in_axes = ((0, 0, 0, 0, 0, None, 0, 0, 0, 0)
                       + ((0, 0, 0) if warm else ()))
            args = [sds((B, n_std), f8), sds((B, g.m, g.nv), f8),
                    sds((B, g.m), f8), sds((B, g.m), f8), sds((B, g.m), f8),
                    sds((g.K, w), np.dtype(np.int64)),
                    sds((B, g.K, g.s, w), f8), sds((B, g.K, g.s, w), f8),
                    sds((B, g.K, g.p, w), f8), sds((B, g.p, g.nv), f8)]
        elif plan.kind == "dense":
            kern = _hsde_ipm_dense_warm if warm else _hsde_ipm
            fn = functools.partial(kern, max_iter=max_iter, tol=tol, **pkw)
            in_axes = (0, 0, 0)
            args = [sds((B, n_std), f8), sds((B, mrows, n_std), f8),
                    sds((B, mrows), f8)]
        else:
            kern = _hsde_ipm_structured_warm if warm else _hsde_ipm_structured
            fn = functools.partial(kern, max_iter=max_iter, tol=tol, **pkw)
            in_axes = (0, 0, 0, 0)
            args = [sds((B, n_std), f8), sds((B, mrows, nv), f8),
                    sds((B, mrows), f8), sds((B, dims.n_eq), f8)]
        if warm and plan.kind not in ("banded", "pallas_banded"):
            in_axes = in_axes + (0, 0, 0)
        return fn, in_axes, tuple(args + (winit if warm else []))

    def trace_plan(self, plan: _KernelPlan, batch: int = 4,
                   warm: bool = False, max_iter: Optional[int] = None, *,
                   lower: bool = False):
        """Statically trace one plan's compiled program (no execution).

        Returns ``(closed_jaxpr, lowered, cache_key)`` for exactly the
        program :meth:`_executable` would compile at this shape —
        traced through the configured executor's
        :meth:`~.executors.Executor.wrap` inside the same
        ``x64_scope`` the runtime solve uses, so the jaxpr
        dtypes match execution.  ``lowered`` is the jit Lowering when
        ``lower`` is set (``None`` otherwise); nothing is compiled
        either way.  This is the entry point the
        :mod:`repro.analysis.dltlint` rules inspect.
        """
        executor = self._resolve_executor()
        mi = int(self.config.max_iter if max_iter is None else max_iter)
        Bp = executor.pad_batch(batch, warm)
        fn, in_axes, args = self._kernel_signature(plan, Bp, warm, mi)
        with _precision.x64_scope():
            closed, lowered = executor.trace(fn, in_axes, args, lower=lower)
        key = self._cache_key(plan, Bp, warm, mi, executor.cache_token())
        return closed, lowered, key

    def lint(self, *, rules: Optional[Sequence[str]] = None,
             with_hlo: bool = False, batch: int = 4):
        """Run the static graph linter over THIS engine's configuration.

        Traces the configured formulation x kernel x executor combo
        (resolving ``kernel="auto"``) and applies the registered
        dltlint rules; formulation-scope rules (DL005) run on the
        configured formulation.  Returns a
        :class:`repro.analysis.dltlint.LintReport`.  Use
        ``scripts/lint_graphs.py`` to sweep the whole registry instead.
        """
        from ...analysis.dltlint import lint_engine
        return lint_engine(self, rules=rules, with_hlo=with_hlo,
                           batch=batch)

    def _solve_family(self, plan: _KernelPlan, init=None,
                      want_state: bool = False,
                      max_iter: Optional[int] = None):
        """Run the plan's kernel over its family, chunked along the batch.

        Cold lane counts are padded to the next power of two (repeating
        the last lane) so the compiled-shape cache sees a bounded set of
        batch sizes; warm chunks pad to a multiple of 4 instead — the
        vmapped while_loop runs to the slowest lane, so po2-padding a
        warm rest pass with junk lanes would cost up to 2x, defeating
        the reduced budget.  Padding lanes are dropped before returning.
        vmap lanes are independent, so real lanes' results are
        unaffected.
        ``init`` (x0, y0, s0 stacks, STANDARD layout) switches to the
        warm kernel — the banded plan converts the triple into its row
        basis per chunk; with ``want_state`` the tau-scaled (x, y, s)
        solution triples are returned (y back in the standard row
        order) for seeding further warm starts.  ``max_iter`` overrides
        the config budget (the adaptive warm budget rides this).

        Returns ``(x, status, iters, n_refine, handover[, y, s])`` —
        the last two per-lane mixed-precision telemetry (zeros under
        the fp64 policy; ``handover`` holds ``HANDOVER_*`` codes, and
        every non-zero one is counted in
        ``stats.phase1_handover_lanes``).
        """
        cfg = self.config
        executor = self._resolve_executor()
        fam = plan.fam
        B = fam.c.shape[0]
        warm = init is not None
        mi = int(cfg.max_iter if max_iter is None else max_iter)
        xs, sts, nits, nrefs, hos, ys, ss = [], [], [], [], [], [], []
        with _precision.x64_scope():
            for lo in range(0, B, cfg.chunk_size):
                hi = min(lo + cfg.chunk_size, B)
                Bk = hi - lo
                Bp = executor.pad_batch(Bk, warm)
                chunk = np.arange(lo, hi)
                bchunk = None
                with tracing.span("dlt.assemble"):
                    if plan.kind in ("banded", "pallas_banded"):
                        bchunk = _banded_take(plan.bfam, chunk)
                        parts = [bchunk.c, bchunk.F, bchunk.b, bchunk.ext,
                                 bchunk.dcoef, bchunk.Fg, bchunk.Hg,
                                 bchunk.Ug, bchunk.Bq]
                        if warm:
                            parts += list(banded_warm_convert(
                                bchunk, *(a[lo:hi] for a in init)))
                    elif plan.kind == "dense":
                        parts = [fam.c[lo:hi], plan.A[lo:hi], fam.b[lo:hi]]
                        if warm:
                            parts += [a[lo:hi] for a in init]
                    else:
                        parts = [fam.c[lo:hi], fam.F[lo:hi], fam.b[lo:hi],
                                 fam.art[lo:hi]]
                        if warm:
                            parts += [a[lo:hi] for a in init]
                    if Bp != Bk:
                        parts = [np.concatenate(
                            [p, np.repeat(p[-1:], Bp - Bk, axis=0)])
                            for p in parts]
                exe = self._executable(plan, Bp, warm, mi)
                with tracing.span("dlt.to_device"):
                    jparts = [jnp.asarray(p, jnp.float64) for p in parts]
                    if plan.kind in ("banded", "pallas_banded"):
                        jparts.insert(5, jnp.asarray(plan.bfam.colix))
                    if tracing.active():
                        jax.block_until_ready(jparts)
                # the copies below wait for the outputs anyway: blocking
                # here first moves no wait, it only ends the span there
                with tracing.span("dlt.ipm"):
                    outs = jax.block_until_ready(exe(*jparts))
                with tracing.span("dlt.from_device"):
                    x, _, st, ni, y, s, nref, ho = outs
                    ni = np.asarray(ni)
                    xs.append(np.asarray(x)[:Bk])
                    sts.append(np.asarray(st)[:Bk])
                    nits.append(ni[:Bk])
                    nrefs.append(np.asarray(nref)[:Bk])
                    hos.append(np.asarray(ho)[:Bk])
                    if want_state:
                        yk = np.asarray(y)[:Bk]
                        ss.append(np.asarray(s)[:Bk])
                self._state.bump(
                    phase1_handover_lanes=np.count_nonzero(hos[-1]),
                    ipm_lane_slots=microbatch_slots(ni))
                if want_state:
                    if plan.kind in ("banded", "pallas_banded"):
                        yk = banded_dual_to_std(bchunk, yk)
                    ys.append(yk)
        out = (np.concatenate(xs), np.concatenate(sts), np.concatenate(nits),
               np.concatenate(nrefs), np.concatenate(hos))
        if want_state:
            return out + (np.concatenate(ys), np.concatenate(ss))
        return out

    def _warm_init(self, fm: Formulation, sub: BatchedSystemSpec,
                   fam: FamilyLP, rest: np.ndarray, anchor: np.ndarray,
                   src: np.ndarray, xa: np.ndarray, ya: np.ndarray,
                   sta: np.ndarray):
        """Build ``(x0, y0, s0)`` seeding lanes ``rest`` from their anchors.

        A neighboring prefix's *formulation fields* are the part of the
        solution that transfers (beta moves by a few percent, the dual
        ``y`` barely at all); raw LP vectors do not — newly activated
        interval columns jump from ~0 to the chain position and copied
        slacks break primal feasibility.  So the seed is completed, not
        copied:

        * beta from the anchor, cleared outside the lane's real cells and
          renormalized to the lane's Eq 6/14 mass;
        * transmission intervals on activated cells filled along the
          minimal chain ``TF_{i,j} = max(TF_{i,j-1}, TF_{i-1,j}) +
          G_i beta_{i,j}`` (cells the anchor also had keep its values);
        * slack/artificial coordinates recomputed from the lane's own
          rows, so the seed starts near-feasible for the lane's program;
        * dual: the anchor's ``y`` with ``s = c - A'y`` re-derived.

        Both sides are floored ``warm_shift`` (relative) into the
        interior.  Lanes whose anchor was not certified optimal are
        seeded with the cold HSDE point instead.
        """
        sub_a = sub.take(anchor)
        fields_src = _fields_take(fm.unpack_batch(sub_a, xa), src)
        return self._warm_init_from(fm, sub, fam, rest, fields_src,
                                    sub_a.cell_mask[src], ya[src].copy(),
                                    sta[src])

    def _warm_init_from(self, fm: Formulation, sub: BatchedSystemSpec,
                        fam: FamilyLP, dest: np.ndarray,
                        fields_src: BatchFields, cell_src: np.ndarray,
                        y0: np.ndarray, st_src: np.ndarray):
        """Seed lanes ``dest`` from per-lane source fields + mapped dual.

        The source side is already selected per destination lane and
        padded to the destination ``(N, M)`` shape: ``fields_src`` /
        ``cell_src`` from any bucket of the same family (cross-bucket
        callers pad the M axis and map the dual through
        :func:`banded_row_transfer`; the within-bucket caller passes the
        anchor rows through unchanged).  ``y0`` is in the destination's
        standard row order.
        """
        cfg = self.config
        nv, n_ub = fam.dims.nv, fam.dims.n_ub
        bsr = sub.take(dest)
        # Field completion (mass renorm, chain-fill of newly activated
        # cells) is the formulation's business: the hook owns the layout.
        v = fm.pack_batch(bsr, fm.warm_fields(bsr, fields_src, cell_src))

        Fr, br = fam.F[dest], fam.b[dest]
        cr, artr = fam.c[dest], fam.art[dest]
        eps_x = cfg.warm_shift * (1.0 + np.abs(v).max(axis=1, keepdims=True))
        v = np.maximum(v, eps_x)
        Fv = np.einsum("brv,bv->br", Fr, v)
        sl = np.clip(br[:, :n_ub] - Fv[:, :n_ub], eps_x, None)
        ar = np.where(artr > 0,
                      np.clip(br[:, n_ub:] - Fv[:, n_ub:], eps_x, None),
                      eps_x)
        x0 = np.concatenate([v, sl, ar], axis=1)
        FTy = np.einsum("brv,br->bv", Fr, y0)
        s_cat = np.concatenate(
            [cr[:, :nv] - FTy,
             cr[:, nv: nv + n_ub] - y0[:, :n_ub],
             cr[:, nv + n_ub:] - artr * y0[:, n_ub:]], axis=1)
        eps_s = cfg.warm_shift * (1.0 + np.abs(s_cat).max(axis=1,
                                                          keepdims=True))
        s0 = np.maximum(s_cat, eps_s)
        bad = st_src != STATUS_OPTIMAL      # junk anchors seed nothing
        x0[bad], y0[bad], s0[bad] = 1.0, 0.0, 1.0
        return x0, y0, s0

    def _transfer_init(self, fm: Formulation, sub: BatchedSystemSpec,
                       fam: FamilyLP, anchor: np.ndarray, transfer: dict):
        """Cross-bucket warm seed for this group's anchor lanes.

        ``transfer`` carries a neighboring (same source count, smaller
        M-bucket) group's completed anchors: solution fields, cell
        masks, standard-layout duals and the bucket's banded geometry.
        Each destination anchor is seeded from the carried anchor with
        the nearest processor count; formulation fields are padded on
        the M axis (newly activated cells are chain-filled by the
        formulation's ``warm_fields`` hook) and the dual transfers
        through the :func:`banded_row_transfer` row maps.  Returns
        ``None`` when the formulation declares no warm transfer or when
        either bucket lacks a banded geometry (no row correspondence
        to transfer through).
        """
        if not self._caps(fm).supports_warm_transfer:
            return None
        geom_src = transfer.get("geom")
        if geom_src is None:
            return None
        struct = fm.banded_structure(sub.n_max, sub.m_max)
        if struct is None:
            return None
        geom_dst = _banded_geometry(struct, fam.dims)
        src_rows, dst_rows = banded_row_transfer(geom_src, geom_dst)

        mp_dst = np.asarray(sub.n_procs)[anchor]
        mp_src = np.asarray(transfer["n_procs"])
        src = np.argmin(np.abs(mp_src[None, :] - mp_dst[:, None]), axis=1)

        f = transfer["fields"]
        pad_n = sub.n_max - f.beta.shape[1]
        pad_m = sub.m_max - f.beta.shape[2]
        if pad_n < 0 or pad_m < 0:
            return None     # only grow into a larger bucket

        def pad(a):
            return (None if a is None else
                    np.pad(a[src], ((0, 0), (0, pad_n), (0, pad_m))))

        fields_src = BatchFields(beta=pad(f.beta),
                                 finish=f.finish[src].copy(),
                                 TS=pad(f.TS), TF=pad(f.TF))
        cell_src = np.pad(transfer["cell"][src],
                          ((0, 0), (0, pad_n), (0, pad_m)))
        y0 = np.zeros((anchor.size, fam.dims.n_rows))
        y0[:, dst_rows] = transfer["y"][src][:, src_rows]
        return self._warm_init_from(fm, sub, fam, anchor, fields_src,
                                    cell_src, y0, transfer["st"][src])

    def _warm_budget(self, nia: np.ndarray, sta: np.ndarray) -> int:
        """Reduced iteration budget for warm-seeded lanes.

        Derived from the observed anchor convergence of the SAME family.
        A seeded lane restarts next to the central path and needs ~0.7x
        the cold iteration count (measured to be nearly independent of
        the seed's anchor distance), so a healthy warm lane NEVER needs
        more than its family's cold anchors — but under vmap the whole
        warm chunk's while_loop runs to its slowest lane, so one
        pathological lane (junk seed, near-infeasible prefix) would
        otherwise drag every lane of the pass to the full ``max_iter``.
        The budget is the anchors' p75 iteration count — neutral for
        healthy lanes (they exit earlier anyway), a ~2x haircut for
        pathological ones — floored at ``min_warm_iter``, rounded up to
        a multiple of 2 (bounding the compiled-budget shapes the LRU
        sees) and capped at ``max_iter``.  Lanes that exhaust it are
        re-solved cold at the full budget in one batched pass, so an
        aggressive budget costs a re-solve — never a wrong result.
        """
        cfg = self.config
        if not cfg.adaptive_budget:
            return cfg.max_iter
        ok = nia[sta == STATUS_OPTIMAL]
        if ok.size == 0:
            return cfg.max_iter
        budget = int(np.ceil(np.percentile(ok, 75)))
        budget = max(budget, cfg.min_warm_iter)
        return int(min(cfg.max_iter, 2 * ((budget + 1) // 2)))

    def _make_carry(self, fm: Formulation, sub: BatchedSystemSpec,
                    fam: FamilyLP, plan: _KernelPlan, anchor: np.ndarray,
                    xa: np.ndarray, ya: np.ndarray, sta: np.ndarray,
                    nia: np.ndarray) -> Optional[dict]:
        """Package this group's anchors for cross-bucket transfer."""
        if not self._caps(fm).supports_warm_transfer:
            return None
        struct = fm.banded_structure(sub.n_max, sub.m_max)
        if struct is None:
            return None
        geom = (plan.bfam.geom if plan.kind in ("banded", "pallas_banded")
                else _banded_geometry(struct, fam.dims))
        sub_a = sub.take(anchor)
        return dict(fields=fm.unpack_batch(sub_a, xa),
                    cell=sub_a.cell_mask, y=ya, st=sta, ni=nia,
                    n_procs=np.asarray(sub.n_procs)[anchor], geom=geom)

    def _precision_fallback(self, plan: _KernelPlan, x: np.ndarray,
                            st: np.ndarray, ni: np.ndarray,
                            nref: np.ndarray, ho: np.ndarray):
        """Full-fp64 re-factor of lanes the mixed path could not certify.

        The mixed policy's safety net: any budget-exhausted lane (a
        phase 1 that wasted the budget shows up here) re-runs
        cold through the fp64 executable of the same plan — surfaced in
        ``stats.precision_fallback_lanes``, never silent.  Infeasibility
        verdicts are not re-run: the mixed kernel's certification phase
        is already pure fp64 (and the oracle fallback re-checks every
        non-optimal lane anyway).
        """
        pfb = np.zeros(st.shape[0], dtype=bool)
        self._state.bump(refine_iterations=nref.sum())
        if self._precision_policy() != "mixed":
            return x, st, ni, nref, ho, pfb
        failed = np.flatnonzero(st == STATUS_MAXITER)
        if failed.size:
            xf, stf, nif, _, _ = self.configured(
                precision="fp64")._solve_family(_plan_take(plan, failed))
            x[failed], st[failed] = xf, stf
            ni[failed] += nif
            pfb[failed] = True
            self._state.bump(precision_fallback_lanes=failed.size,
                             cold_iterations=nif.sum())
        return x, st, ni, nref, ho, pfb

    def _solve_group(self, fm: Formulation, sub: BatchedSystemSpec,
                     fam: FamilyLP, warm: bool,
                     transfer: Optional[dict] = None,
                     want_carry: bool = False):
        """Solve one padded family, warm two-phase when asked & worthwhile.

        Warm plan: lanes are already ordered by processor count, so every
        ``warm_stride``-th lane is solved cold (anchor pass) and each
        remaining lane restarts the HSDE from a completed seed built off
        its nearest anchor's solution (see :meth:`_warm_init`), under
        the reduced adaptive budget (see :meth:`_warm_budget`) — lanes
        failing it are automatically re-solved cold at the full budget.
        The padded LP shape is shared group-wide, so seeds transfer with
        no reshaping.

        ``transfer`` (a neighboring bucket's — or, for the routing
        service, a previous solve's — anchor carry) upgrades the anchor
        pass itself to a warm start (see :meth:`_transfer_init`);
        anchors the transferred seed cannot certify re-run cold, so a
        bad transfer costs a re-solve, never a result.  In the flat
        (no anchor/rest split) branch a transfer seeds EVERY lane.

        ``want_carry`` forces anchor-carry collection even on cold flat
        solves — the routing service collects a carry from every
        admission window so a later drift re-solve can warm-start from
        it.  Collecting state never changes the compiled program or the
        results, only what is copied back off-device.

        Returns ``(x, st, ni, nref, ho, pfb, carry)``: per-lane
        solutions, statuses, iterations, refinement counts, phase-1
        handover codes (the largest of the lane's solves), the
        mixed-precision fallback mask and (when collected) the anchor
        carry for the next bucket / window.
        """
        st8 = self._state
        cfg = self.config
        B = fam.c.shape[0]
        with tracing.span("dlt.assemble"):
            plan = self._kernel_plan(fm, sub, fam)
        cells = sub.cell_mask
        st8.bump(lp_cells=np.count_nonzero(cells), lp_cell_slots=cells.size)
        if plan.kind == "banded":
            st8.bump(banded_lanes=B)
        elif plan.kind == "pallas_banded":
            st8.bump(pallas_lanes=B)
        want_carry = (want_carry or warm) and cfg.warm_transfer

        if not warm or B <= cfg.warm_stride:
            # flat branch: every lane solves in one pass — seeded from
            # the carried anchors when a transfer is available (the
            # routing service's drift re-solve path), cold otherwise
            init0 = (self._transfer_init(fm, sub, fam, np.arange(B),
                                         transfer)
                     if warm and transfer is not None else None)
            out = self._solve_family(plan, init=init0,
                                     want_state=want_carry)
            x, st, ni, nref, ho = out[:5]
            y = out[5] if want_carry else None
            if init0 is not None:
                st8.bump(transfer_lanes=B, warm_lanes=B,
                         warm_iterations=ni.sum())
                # transferred-seed failures re-run cold at full budget
                failed = np.flatnonzero(st != STATUS_OPTIMAL)
                if failed.size:
                    fout = self._solve_family(_plan_take(plan, failed),
                                              want_state=want_carry)
                    x[failed], st[failed] = fout[0], fout[1]
                    ni[failed] += fout[2]
                    nref[failed] += fout[3]
                    ho[failed] = np.maximum(ho[failed], fout[4])
                    if want_carry:
                        y[failed] = fout[5]
                    st8.bump(resolve_lanes=failed.size,
                             cold_iterations=fout[2].sum())
                st8.bump(lanes=B)
            else:
                st8.bump(lanes=B, cold_iterations=ni.sum())
            carry = None
            if want_carry:
                carry = self._make_carry(fm, sub, fam, plan, np.arange(B),
                                         x, y, st, ni)
            return (self._precision_fallback(plan, x, st, ni, nref, ho)
                    + (carry,))

        anchor = np.arange(0, B, cfg.warm_stride)
        rest = np.setdiff1d(np.arange(B), anchor)
        anchor_plan = _plan_take(plan, anchor)
        init_a = (None if transfer is None
                  else self._transfer_init(fm, sub, fam, anchor, transfer))
        xa, sta, nia, nra, hoa, ya, sa = self._solve_family(
            anchor_plan, init=init_a, want_state=True)
        if init_a is not None:
            st8.bump(transfer_lanes=anchor.size, warm_lanes=anchor.size,
                     warm_iterations=nia.sum())
            # anchors must be trustworthy — they enter the results AND
            # seed the rest pass — so transferred-seed failures re-run
            # cold at the full budget
            failed = np.flatnonzero(sta != STATUS_OPTIMAL)
            if failed.size:
                xf, stf, nif, nrf, hof, yf, sf = self._solve_family(
                    _plan_take(anchor_plan, failed), want_state=True)
                xa[failed], sta[failed] = xf, stf
                ya[failed], sa[failed] = yf, sf
                nia[failed] += nif
                nra[failed] += nrf
                hoa[failed] = np.maximum(hoa[failed], hof)
                st8.bump(resolve_lanes=failed.size,
                         cold_iterations=nif.sum())
        else:
            st8.bump(cold_iterations=nia.sum())
        carry = None
        if want_carry:
            carry = self._make_carry(fm, sub, fam, plan, anchor,
                                     xa, ya, sta, nia)
        # nearest anchor (either side) seeds each remaining lane
        hi = np.clip(np.searchsorted(anchor, rest), 0, anchor.size - 1)
        lo = np.clip(hi - 1, 0, anchor.size - 1)
        src = np.where(np.abs(anchor[hi] - rest) < np.abs(rest - anchor[lo]),
                       hi, lo)
        init = self._warm_init(fm, sub, fam, rest, anchor, src, xa, ya, sta)
        budget = self._warm_budget(nia, sta)
        rest_plan = _plan_take(plan, rest)
        xr, str_, nir, nrr, hor = self._solve_family(rest_plan, init=init,
                                                     max_iter=budget)
        st8.bump(warm_iterations=nir.sum())
        if budget < cfg.max_iter:
            # adaptive-budget safety net: lanes the reduced budget could
            # not certify re-run cold at the full budget (still cheaper
            # than letting every straggler gate the whole warm chunk)
            failed = np.flatnonzero(str_ == STATUS_MAXITER)
            if failed.size:
                xf, stf, nif, nrf, hof = self._solve_family(
                    _plan_take(rest_plan, failed))
                xr[failed], str_[failed] = xf, stf
                nir[failed] += nif
                nrr[failed] += nrf
                hor[failed] = np.maximum(hor[failed], hof)
                st8.bump(resolve_lanes=failed.size,
                         cold_iterations=nif.sum())
        x = np.empty_like(fam.c)
        st = np.empty(B, dtype=sta.dtype)
        ni = np.empty(B, dtype=nia.dtype)
        nref = np.empty(B, dtype=nra.dtype)
        ho = np.empty(B, dtype=hoa.dtype)
        x[anchor], st[anchor], ni[anchor], nref[anchor] = xa, sta, nia, nra
        x[rest], st[rest], ni[rest], nref[rest] = xr, str_, nir, nrr
        ho[anchor], ho[rest] = hoa, hor
        st8.bump(lanes=B, warm_lanes=rest.size)
        return (self._precision_fallback(plan, x, st, ni, nref, ho)
                + (carry,))

    def _solve_batch_scalar(self, bspec: BatchedSystemSpec, frontend: bool,
                            formulation: FormulationLike) -> BatchedSolution:
        """The scalar engine's batch path: one LP at a time, config solver.

        Follows the classic scalar mapping (``formulation=None`` +
        ``frontend=False`` uses the full Sec 3.2 program or the Sec 2
        closed form), so ``engine="scalar"`` batches match a loop of
        ``solve()`` calls exactly.
        """
        which = (formulation if formulation is not None
                 else self.config.formulation)
        fm = get_formulation(which if which is not None else frontend)
        frontend = fm.frontend
        B, Nmax, Mmax = bspec.batch, bspec.n_max, bspec.m_max
        beta = np.zeros((B, Nmax, Mmax))
        finish = np.full(B, np.nan)
        TS = TF = None
        if fm.has_intervals:
            TS = np.zeros((B, Nmax, Mmax))
            TF = np.zeros((B, Nmax, Mmax))
        status = np.full(B, STATUS_INFEASIBLE, dtype=np.int64)
        for k in range(B):
            try:
                sched = self.solve(bspec.scenario(k), frontend=frontend,
                                   presorted=True, formulation=which)
            except InfeasibleError:
                continue
            sp = sched.spec
            n, m = sp.num_sources, sp.num_processors
            beta[k, :n, :m] = fm.fold_schedule(sched)
            finish[k] = sched.finish_time
            if TS is not None:
                if sched.TS is not None:
                    TS[k, :n, :m] = sched.TS
                    TF[k, :n, :m] = sched.TF
                else:
                    # Sec 2 closed form (single source): back-to-back chain
                    TS[k, 0, :m], TF[k, 0, :m] = single_source_intervals(
                        sp.R[0], sp.G[0], sched.beta[0])
            status[k] = STATUS_OPTIMAL
        self._state.bump(batches=1)
        return BatchedSolution(
            spec=bspec, frontend=frontend, finish_time=finish, beta=beta,
            status=status, iterations=np.zeros(B, dtype=np.int64),
            TS=TS, TF=TF, formulation=fm.name,
            fallback_mask=np.zeros(B, dtype=bool),
        )

    def _require_axes(self, fm: Formulation, axes: Tuple[str, ...],
                      what: str) -> None:
        """Fail fast when a family API varies an axis ``fm`` ignores.

        ``sweep`` varies the processor count and ``grid`` additionally
        varies the source count; a formulation that does not declare
        the axis in ``capabilities.spec_axes`` would silently solve the
        same program per cell (or blow up inside tracing), so the
        mismatch is a ``ValueError`` naming the declared axes instead.
        """
        declared = self._caps(fm).spec_axes
        missing = [a for a in axes if a not in declared]
        if missing:
            raise ValueError(
                f"{what} varies the {missing[0]!r} axis but formulation "
                f"{fm.name!r} declares spec_axes={declared!r} — family "
                "APIs only vary declared axes")

    # ---- the workload surface -------------------------------------------

    def solve(self, spec: SystemSpec, frontend: bool = True, *,
              formulation: FormulationLike = None,
              presorted: bool = False) -> Schedule:
        """One schedule through the scalar path (config solver/verify)."""
        cfg = self.config
        return _scalar_solve(
            spec, frontend=frontend, solver=cfg.solver, verify=cfg.verify,
            presorted=presorted,
            formulation=formulation if formulation is not None
            else cfg.formulation)

    def solve_batch(self, specs, frontend: bool = True,
                    formulation: FormulationLike = None, *,
                    presorted: bool = False,
                    warm: bool = False) -> BatchedSolution:
        """Solve a whole family of DLT programs in one session call.

        Accepts a ragged list of :class:`SystemSpec` or a prebuilt
        :class:`BatchedSystemSpec`.  ``warm=True`` applies the two-phase
        anchor plan within each size bucket (lanes are re-ordered by
        processor count internally) — meant for parametric families
        whose neighbors share structure; ``sweep``/``grid`` pass the
        config's ``warm_start`` automatically.
        """
        return self._solve_batch_impl(specs, frontend, formulation,
                                      presorted=presorted, warm=warm)[0]

    def solve_batch_carry(
            self, specs, frontend: bool = True,
            formulation: FormulationLike = None, *,
            presorted: bool = False, warm: bool = False,
            carry_in: Optional[dict] = None,
    ) -> Tuple[BatchedSolution, dict]:
        """Service-facing :meth:`solve_batch`: ``(solution, carry)``.

        Identical results to :meth:`solve_batch` — collecting anchor
        state never changes the compiled program — plus an **anchor
        carry**: per source-count bucket, the solved lanes' formulation
        fields, duals and banded geometry, exactly the package the
        cross-bucket ``warm_transfer`` path seeds from.  Feed a previous
        call's carry back through ``carry_in`` together with
        ``warm=True`` to warm-start THIS batch from those solutions
        (counted in ``stats.transfer_lanes``; lanes the transferred
        seed cannot certify re-run cold, so a stale carry costs a
        re-solve, never a result).  This is the always-on routing
        service's drift re-solve hook: window *t*'s carry anchors
        window *t+1* after the fleet's measured stats drift.

        The carry maps source-count -> opaque anchor package; treat it
        as a token to pass back, not a stable API.  On the scalar
        engine (or with ``warm_transfer`` disabled) the carry is empty
        and ``carry_in`` is ignored.
        """
        return self._solve_batch_impl(specs, frontend, formulation,
                                      presorted=presorted, warm=warm,
                                      carry_in=carry_in, want_carry=True)

    def _solve_batch_impl(
            self, specs, frontend: bool = True,
            formulation: FormulationLike = None, *,
            presorted: bool = False, warm: bool = False,
            carry_in: Optional[dict] = None, want_carry: bool = False,
    ) -> Tuple[BatchedSolution, dict]:
        with tracing.span("dlt.solve_batch") as root:
            cfg = self.config
            fm = self._formulation(frontend, formulation)
            with tracing.span("dlt.assemble"):
                bspec = (specs if isinstance(specs, BatchedSystemSpec)
                         else BatchedSystemSpec.from_specs(
                             specs, presorted=presorted))
            if cfg.engine == "scalar":
                # honor the config contract: the scalar engine keeps the
                # one-LP-at-a-time loop (and its pinned solver) on every
                # path
                root.set(lanes=bspec.batch)
                return (self._solve_batch_scalar(bspec, frontend,
                                                 formulation), {})
            return self._solve_batch_groups(
                root, bspec, fm, warm=warm, carry_in=carry_in,
                want_carry=want_carry)

    def _solve_batch_groups(self, root, bspec: BatchedSystemSpec,
                            fm: Formulation, *, warm: bool,
                            carry_in: Optional[dict], want_carry: bool,
                            ) -> Tuple[BatchedSolution, dict]:
        """The batched engine's :meth:`solve_batch` under its root span."""
        cfg = self.config
        frontend = fm.frontend
        B, Nmax, Mmax = bspec.batch, bspec.n_max, bspec.m_max

        beta = np.zeros((B, Nmax, Mmax))
        finish = np.full(B, np.nan)
        TS = TF = None
        if fm.has_intervals:
            TS = np.zeros((B, Nmax, Mmax))
            TF = np.zeros((B, Nmax, Mmax))
        status = np.full(B, STATUS_MAXITER, dtype=np.int64)
        iters = np.zeros(B, dtype=np.int64)
        prec = self._precision_policy()
        refits = np.zeros(B, dtype=np.int64)
        handover = np.zeros(B, dtype=np.int32)
        pfb_all = np.zeros(B, dtype=bool)

        m_edges = WARM_M_BUCKET_EDGES if warm else cfg.m_bucket_edges
        with tracing.span("dlt.assemble"):
            groups = list(_group_lanes(bspec, cfg.bucket, m_edges,
                                       fm=fm).items())
        root.set(lanes=B, groups=len(groups))
        if warm:
            # visit buckets of one source count in ascending M-edge order
            # so each bucket's anchors can seed the next (cross-bucket
            # warm transfer keyed on the bucket-free part of the key)
            groups.sort(key=lambda kv: kv[0])
        carry_by_nb: dict = dict(carry_in) if carry_in else {}
        verified = np.ones(B, dtype=bool)
        for key, idx in groups:
            # key = (n_sources, m_bucket) + formulation group axes
            nb, mb = key[0], key[1]
            ckey = (nb,) + key[2:]
            # never pad past the group's true max — a group's padded shape
            # then depends only on its own lanes, so solving it inside a
            # ragged batch or alone is the same computation
            mb = min(mb, int(bspec.n_procs[idx].max()))
            if warm:  # anchors seed neighbors: order the family by size
                idx = idx[np.argsort(bspec.n_procs[idx], kind="stable")]
            with tracing.span("dlt.assemble"):
                sub = bspec.take(idx, n_pad=nb, m_pad=mb)
                fam = build_family_lp(sub, fm)
            transfer = (carry_by_nb.get(ckey)
                        if warm and cfg.warm_transfer else None)
            x, st, ni, nref, ho, pfb, carry = self._solve_group(
                fm, sub, fam, warm, transfer=transfer,
                want_carry=want_carry)
            if carry is not None:
                carry_by_nb[ckey] = carry
            # clean first (exact zeros on padded cells — the IPM leaves
            # ~tol-level dust on masked vars), verify per group so
            # formulation extras (per-round splits etc.) reach the checks
            with tracing.span("dlt.unpack"):
                fields = fm.clean_batch(sub, fm.unpack_batch(sub, x))
            if cfg.verify:
                with tracing.span("dlt.verify"):
                    verified[idx] = fm.verify_batch(sub, fields)
            with tracing.span("dlt.unpack"):
                sl = np.ix_(idx, np.arange(nb), np.arange(mb))
                beta[sl] = fields.beta
                finish[idx] = fields.finish
                if fm.has_intervals:
                    TS[sl] = fields.TS
                    TF[sl] = fields.TF
                status[idx] = st
                iters[idx] = ni
                refits[idx] = nref
                handover[idx] = ho
                pfb_all[idx] = pfb

        # exact zeros on padding of lanes no group wrote (defensive)
        cell = bspec.cell_mask
        beta[~cell] = 0.0
        if TS is not None:
            TS[~cell] = 0.0
            TF[~cell] = 0.0

        ok = status == STATUS_OPTIMAL
        if cfg.verify:
            demoted = ok & ~verified
            status[demoted] = STATUS_MAXITER
            ok &= verified

        fallback_mask = ~ok
        if cfg.oracle_fallback and fallback_mask.any():
            # every uncertified lane — including IPM infeasibility verdicts,
            # which the simplex either confirms or overturns with a
            # solution.  Classic-oracle formulations re-check against the
            # paper's scalar mapping; self-oracle formulations re-solve
            # their own scalar LP (there is no independent paper program).
            fkw = ({} if self._caps(fm).oracle_kind == "classic"
                   else {"formulation": fm})
            with tracing.span("dlt.oracle"):
                for k in np.flatnonzero(~ok):
                    try:
                        sched = _scalar_solve(
                            bspec.scenario(k), frontend=frontend,
                            solver="simplex", presorted=True, **fkw)
                    except InfeasibleError:
                        status[k] = STATUS_INFEASIBLE
                        continue
                    sp = sched.spec
                    n, m = sp.num_sources, sp.num_processors
                    beta[k] = 0.0
                    beta[k, :n, :m] = fm.fold_schedule(sched)
                    finish[k] = sched.finish_time
                    if TS is not None:
                        TS[k] = 0.0
                        TF[k] = 0.0
                        if sched.TS is not None:
                            TS[k, :n, :m] = sched.TS
                            TF[k, :n, :m] = sched.TF
                        else:
                            # Sec 2 closed form (single source):
                            # back-to-back
                            TS[k, 0, :m], TF[k, 0, :m] = (
                                single_source_intervals(
                                    sp.R[0], sp.G[0], sched.beta[0]))
                    status[k] = STATUS_OPTIMAL

        infeasible = status == STATUS_INFEASIBLE
        finish[infeasible] = np.nan
        beta[infeasible] = 0.0      # interior-point ray junk, not a schedule
        if TS is not None:
            TS[infeasible] = 0.0
            TF[infeasible] = 0.0
        # the counter records lanes the oracle actually re-solved; with the
        # fallback disabled the mask still marks them, but no oracle ran
        self._state.bump(batches=1,
                         fallback_lanes=(fallback_mask.sum()
                                         if cfg.oracle_fallback else 0))
        return (BatchedSolution(
            spec=bspec, frontend=frontend, finish_time=finish, beta=beta,
            status=status, iterations=iters, TS=TS, TF=TF,
            formulation=fm.name, fallback_mask=fallback_mask,
            precision=prec,
            refine_iterations=refits if prec == "mixed" else None,
            phase1_handover=handover if prec == "mixed" else None,
            precision_fallback_mask=pfb_all if prec == "mixed" else None,
        ), carry_by_nb)

    def sweep(self, spec: SystemSpec, frontend: bool = True,
              m_max: Optional[int] = None, *,
              formulation: FormulationLike = None) -> ProcessorSweep:
        """Sec 6 prefix family: T_f(m) and Cost(m) for m = 1..M.

        On the batched engine the whole family is one (warm-started, when
        ``warm_start``) session call; infeasible prefixes are dropped
        from the sweep exactly like the scalar loop drops them.
        """
        cfg = self.config
        self._require_axes(self._formulation(frontend, formulation),
                           ("m",), "sweep()")
        cspec = spec.canonical()[0]
        M = (cspec.num_processors if m_max is None
             else min(m_max, cspec.num_processors))
        if cfg.engine == "scalar":
            ms, tfs, costs = [], [], []
            for m in range(1, M + 1):
                sub = cspec.subset_processors(m)
                try:
                    sched = self.solve(sub, frontend=frontend,
                                       presorted=True,
                                       formulation=formulation)
                except InfeasibleError:
                    continue
                ms.append(m)
                tfs.append(sched.finish_time)
                costs.append(sched.monetary_cost()
                             if cspec.C is not None else np.nan)
            return ProcessorSweep(np.asarray(ms), np.asarray(tfs),
                                  np.asarray(costs))
        subs = [cspec.subset_processors(m) for m in range(1, M + 1)]
        sol = self.solve_batch(subs, frontend=frontend,
                               formulation=formulation, presorted=True,
                               warm=cfg.warm_start)
        keep = sol.status == STATUS_OPTIMAL
        ms = np.flatnonzero(keep) + 1
        costs = (sol.monetary_cost()[keep] if cspec.C is not None
                 else np.full(int(keep.sum()), np.nan))
        return ProcessorSweep(ms, sol.finish_time[keep], costs)

    def grid(self, spec: SystemSpec, source_counts: Sequence[int],
             processor_counts: Sequence[int], frontend: bool = False, *,
             formulation: FormulationLike = None) -> SpeedupGrid:
        """Sec 5 Eq 16 speedup surface over (sources x processors).

        Each source-count row is one session call over the processor
        prefixes (warm-started when ``warm_start``); any infeasible grid
        cell raises :class:`InfeasibleError` on either engine.
        """
        cfg = self.config
        self._require_axes(self._formulation(frontend, formulation),
                           ("n", "m"), "grid()")
        cspec = spec.canonical()[0]
        P, Q = len(source_counts), len(processor_counts)
        tf = np.full((P, Q), np.nan)
        if cfg.engine == "scalar":
            for a, p in enumerate(source_counts):
                sub_s = cspec.subset_sources(p)
                for b_, n in enumerate(processor_counts):
                    sched = self.solve(sub_s.subset_processors(n),
                                       frontend=frontend, presorted=True,
                                       formulation=formulation)
                    tf[a, b_] = sched.finish_time
        else:
            # a grid row is one parametric family (shared source count):
            # solve it as a single padded shape so warm anchors can seed
            # every other cell of the row
            eng = (self.configured(bucket="none") if cfg.warm_start
                   else self)
            for a, p in enumerate(source_counts):
                sub_s = cspec.subset_sources(p)
                subs = [sub_s.subset_processors(n) for n in processor_counts]
                sol = eng.solve_batch(subs, frontend=frontend,
                                      formulation=formulation,
                                      presorted=True, warm=cfg.warm_start)
                bad = np.flatnonzero(sol.status == STATUS_INFEASIBLE)
                if bad.size:  # match the scalar engine's behavior
                    raise InfeasibleError(
                        f"grid cell (sources={p}, processors="
                        f"{processor_counts[int(bad[0])]}) infeasible")
                tf[a, :] = sol.finish_time
        base = tf[0:1, :]  # row of the smallest source count (paper: 1)
        return SpeedupGrid(
            sources=np.asarray(source_counts),
            processors=np.asarray(processor_counts),
            finish_time=tf,
            speedup=base / tf,
        )

    def advisor(self, spec: SystemSpec, frontend: bool = True,
                m_max: Optional[int] = None, *,
                formulation: FormulationLike = None):
        """Sec 6 budget planners over this engine's processor sweep."""
        from ..advisor import ClusterAdvisor  # local: avoid import cycle

        return ClusterAdvisor(sweep=self.sweep(
            spec, frontend=frontend, m_max=m_max, formulation=formulation))

    def map(self, specs: Iterable[SystemSpec], frontend: bool = True, *,
            formulation: FormulationLike = None, presorted: bool = False,
            strict: bool = True) -> Iterator[BatchedSolution]:
        """Stream serving-style traffic: chunk, bucket, solve, yield.

        Pulls ``chunk_size`` specs at a time from ``specs`` (any
        iterable, including generators), solves each chunk as one
        bucketed batch, and yields its :class:`BatchedSolution`.  With
        ``strict=True`` (default) a lane without a certified schedule
        raises through ``BatchedSolution.schedule(k, strict=True)`` —
        naming the lane's status and fallback state — instead of
        surfacing as a silent ``None`` downstream.
        """
        it = iter(specs)
        while True:
            chunk = list(itertools.islice(it, self.config.chunk_size))
            if not chunk:
                return
            sol = self.solve_batch(chunk, frontend=frontend,
                                   formulation=formulation,
                                   presorted=presorted)
            if strict:
                for k in np.flatnonzero(sol.status != STATUS_OPTIMAL):
                    sol.schedule(int(k), strict=True)
            yield sol


_DEFAULT_ENGINE: Optional[DLTEngine] = None
_DEFAULT_ENGINE_LOCK = threading.Lock()


def get_default_engine() -> DLTEngine:
    """The process-wide default session the free-function shims run on.

    Created lazily (thread-safely) with a default :class:`EngineConfig`;
    shims apply their keyword knobs through :meth:`DLTEngine.configured`,
    so every call still shares one compiled-shape cache and stats ledger.
    """
    global _DEFAULT_ENGINE
    if _DEFAULT_ENGINE is None:
        with _DEFAULT_ENGINE_LOCK:
            if _DEFAULT_ENGINE is None:
                _DEFAULT_ENGINE = DLTEngine()
    return _DEFAULT_ENGINE
