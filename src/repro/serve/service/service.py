"""Always-on DLT routing service: deadline batching + drift re-solves.

The one-shot :func:`repro.serve.engine.route_requests` answers "how do I
drain THIS burst"; a serving fleet needs the question answered
continuously, with a latency distribution.  ``RouterService`` is that
loop:

  * **Admission.** ``submit(num_requests)`` enqueues a route query and
    returns a future.  The service solves whatever accumulated every
    ``admit_window_ms`` (deadline batching): one batched engine call per
    window, every lane padded onto the executor micro-batch ladder so
    repeat windows hit the session compile cache and each decision is
    bit-identical to the same burst routed one-shot.
  * **Drift.** ``observe(measured_A)`` feeds replica seconds/request into
    an EWMA tracker; when any replica's smoothed rate moves more than
    ``drift_threshold`` (relative) from the rates the service last
    solved against, the next window re-solves against the new estimate,
    warm-seeded from the previous window's solution via the engine's
    cross-bucket ``warm_transfer`` carry (``warm_policy="transfer"``).
  * **Accounting.** A ``ServiceStats`` ledger mirrors the engine-counter
    idiom (windows, warm/cold splits, transfer/resolve/fallback lane
    deltas) plus the SLO ledger: per-decision admission-to-decision
    latency with p50/p99/p999 quantiles.  Failed lanes surface through
    ``schedule(strict=True)`` — the future carries the lane's exception,
    never a silently-degenerate schedule.

``step()`` runs one admission window synchronously (deterministic; what
the tests drive); ``start()``/``stop()`` run the same loop on a daemon
thread for real Poisson traffic (what the bench drives).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from concurrent.futures import Future
from typing import List, Optional, Sequence

import numpy as np

from repro.core.dlt import get_default_engine
from repro.core.dlt.executors import LANE_MICROBATCH

from ..engine import RouterStats, _burst_specs, _decision
from .drift import DriftTracker
from .observer import RateObserver
from .queue import AdmissionQueue
from .stats import _LATENCY_RESERVOIR, ServiceStats

__all__ = ["ServiceConfig", "RouteDecision", "RouterService"]

_WARM_POLICIES = ("transfer", "cold")

_log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Knobs for the always-on router (validated on construction).

    Attributes:
        admit_window_ms: deadline batching window — the service solves
            whatever admissions accumulated this many milliseconds after
            the window-opening arrival.  The knob trades per-decision
            latency against batching efficiency (and compile-cache
            locality: windows of any size pad onto the same lane
            ladder).
        max_window: cap on admissions drained per window (None =
            unbounded).  Overflow stays queued for the next window.
        drift_threshold: relative EWMA change in any replica's measured
            seconds/request that triggers a re-solve against the new
            estimate.
        ewma_alpha: smoothing factor for the drift tracker's moving
            average (1.0 = trust only the latest observation).
        warm_policy: ``"transfer"`` seeds drift re-solves from the
            previous window's solution via the engine's warm_transfer
            carry; ``"cold"`` re-solves from scratch (the control arm —
            measure the transfer win before trusting it).
        frontend: solve the Sec 3.1 frontend formulation (False: the
            source-free Sec 3.2 program).
        strict: resolve futures with ``schedule(strict=True)`` — a
            failed lane raises into the future instead of returning a
            degenerate schedule.
        refresh_on_drift: when drift fires with an empty queue, re-solve
            the previous window's burst sizes anyway so the warm anchor
            (and the next real window's seed) tracks the new rates.
        stable_shapes: solve windows with the engine's adaptive warm
            budget disabled, so warm re-solves compile ONE full-budget
            shape instead of a new reduced-budget variant whenever the
            anchors' iteration profile shifts.  An always-on service
            pays compiles as p99 latency cliffs; the fixed-length warm
            scan is the cheaper trade (see the SLO bench).  Turn off to
            reuse a long-running engine's existing adaptive-budget
            executables.
        latency_reservoir: per-decision latencies retained for the SLO
            quantiles (most recent window).  A quantile ``q`` needs
            roughly ``1 / (1 - q)`` samples to mean anything — below
            that the readout is the sample max (see
            ``ServiceStats.latency_quantile``) — so keep this at least
            ~1k if the p999 readout matters.
    """

    admit_window_ms: float = 5.0
    max_window: Optional[int] = None
    drift_threshold: float = 0.15
    ewma_alpha: float = 0.3
    warm_policy: str = "transfer"
    frontend: bool = True
    strict: bool = True
    refresh_on_drift: bool = True
    stable_shapes: bool = True
    latency_reservoir: int = _LATENCY_RESERVOIR

    def __post_init__(self):
        if not (self.admit_window_ms > 0):
            raise ValueError(
                f"admit_window_ms must be positive, got {self.admit_window_ms}")
        if self.max_window is not None and self.max_window < 1:
            raise ValueError(
                f"max_window must be None or >= 1, got {self.max_window}")
        if not (self.drift_threshold > 0):
            raise ValueError(
                f"drift_threshold must be positive, got {self.drift_threshold}")
        if not (0.0 < self.ewma_alpha <= 1.0):
            raise ValueError(
                f"ewma_alpha must be in (0, 1], got {self.ewma_alpha}")
        if self.warm_policy not in _WARM_POLICIES:
            raise ValueError(
                f"warm_policy must be one of {_WARM_POLICIES}, "
                f"got {self.warm_policy!r}")
        if self.latency_reservoir < 1:
            raise ValueError(
                f"latency_reservoir must be >= 1, got {self.latency_reservoir}")


@dataclasses.dataclass(frozen=True)
class RouteDecision:
    """One resolved admission: shares + provenance of the solve."""

    shares: np.ndarray          # requests per replica (sums to the query)
    makespan: float             # LP drain time for this burst
    uniform_makespan: float     # naive equal-split drain time (reference)
    schedule: object            # the full Schedule (canonical order undone)
    warm: bool                  # solved in a drift window (warm-seeded)
    window_size: int            # admissions that shared this window
    solve_seconds: float        # engine wall time for the whole window
    latency_seconds: float      # admission-to-decision, this query


@dataclasses.dataclass
class _Pending:
    count: int
    future: Future
    t_submit: float


class RouterService:
    """Continuously running router in front of the shared DLT session."""

    def __init__(self, stats: RouterStats, config: ServiceConfig = None, *,
                 engine=None):
        self.config = config if config is not None else ServiceConfig()
        self._engine = engine if engine is not None else get_default_engine()
        # the solving view shares the engine's compile LRU and counters;
        # stable_shapes pins warm windows to the full iteration budget so
        # the service's executable set is fixed after prewarm()
        self._solver = (self._engine.configured(adaptive_budget=False)
                        if self.config.stable_shapes else self._engine)
        self._mu = threading.RLock()        # service state (stats/drift/carry)
        self._step_mu = threading.Lock()    # serializes admission windows
        self._queue = AdmissionQueue()
        self._ledger = ServiceStats(reservoir=self.config.latency_reservoir)
        self._tracker = DriftTracker(self.config.ewma_alpha)
        self._stats = stats                 # RouterStats currently solved
        self._baseline_A = np.asarray(
            stats.replica_seconds_per_request, np.float64)
        self._carry: Optional[dict] = None  # warm_transfer anchor token
        self._drift_pending = False
        self._last_counts: Optional[List[int]] = None
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()

    # -- admission ----------------------------------------------------------

    def submit(self, num_requests: int) -> Future:
        """Enqueue a route query; resolves to a :class:`RouteDecision`."""
        n = int(num_requests)
        if n < 1:
            raise ValueError(f"num_requests must be >= 1, got {num_requests}")
        item = _Pending(count=n, future=Future(),
                        t_submit=time.perf_counter())
        self._queue.put(item)
        return item.future

    def observe(self, replica_seconds_per_request: Sequence[float]) -> None:
        """Feed one measured A_j vector into the drift tracker.

        Both the manual override path and the sink a
        :meth:`rate_observer` pushes through — safe to call from any
        thread, including replica serving threads mid-``generate``.
        """
        self._tracker.observe(replica_seconds_per_request)
        with self._mu:
            if (not self._drift_pending
                    and self._tracker.drifted(self._baseline_A,
                                              self.config.drift_threshold)):
                self._drift_pending = True
                self._ledger.bump(drift_events=1)

    def rate_observer(self, **kw) -> RateObserver:
        """A :class:`RateObserver` feeding this service's drift tracker.

        Hand the result to each replica's ``ServeEngine(observer=...,
        replica=j)``: measured ``generate`` timings then flow into
        :meth:`observe` automatically, so drift re-solves fire from
        real traffic with no operator in the loop.  Keyword arguments
        (``window``, ``min_samples``) pass through to the observer; the
        baseline is the A_j vector the service currently solves against.
        """
        with self._mu:
            baseline = self._baseline_A
        return RateObserver(baseline, sink=self.observe, **kw)

    # -- the window ---------------------------------------------------------

    def step(self) -> int:
        """Run ONE admission window synchronously; returns decisions made.

        Deterministic building block: drains up to ``max_window`` pending
        admissions, applies any pending drift rebase, and solves the
        window in one batched engine call.  The background loop and the
        tests both drive this.
        """
        with self._step_mu:
            items = self._queue.drain(self.config.max_window)
            with self._mu:
                warm = False
                if self._drift_pending:
                    self._rebase_to_ewma()
                    warm = (self.config.warm_policy == "transfer"
                            and self._carry is not None)
                    self._drift_pending = False
                    if not items:
                        if self.config.refresh_on_drift and self._last_counts:
                            self._solve_window([], warm=warm,
                                               probe_counts=self._last_counts)
                        return 0
                if not items:
                    return 0
                self._solve_window(items, warm=warm)
                return len(items)

    def flush(self) -> int:
        """Solve every pending admission now (possibly several windows)."""
        total = 0
        while True:
            n = self.step()
            if n == 0 and self._queue.depth == 0:
                return total
            total += n

    def _rebase_to_ewma(self) -> None:
        ewma = self._tracker.ewma
        if ewma is None:
            return
        self._stats = RouterStats(
            frontend_seconds_per_request=np.asarray(
                self._stats.frontend_seconds_per_request, np.float64),
            frontend_release=np.asarray(
                self._stats.frontend_release, np.float64),
            replica_seconds_per_request=ewma,
        )
        self._baseline_A = ewma

    def _solve_window(self, items: List[_Pending], warm: bool,
                      probe_counts: Optional[List[int]] = None) -> None:
        counts = [it.count for it in items] if items else list(probe_counts)
        specs, pperm = _burst_specs(self._stats, counts)
        pad = max(LANE_MICROBATCH - len(specs), 0)
        # counter_scope: this thread's engine-counter deltas only — a
        # before/after stats snapshot would blame sibling fleets' lanes
        # on this window when several loops share the session
        with self._engine.counter_scope() as deltas:
            t0 = time.perf_counter()
            try:
                sol, carry = self._solver.solve_batch_carry(
                    specs + [specs[-1]] * pad, frontend=self.config.frontend,
                    presorted=True, warm=warm,
                    carry_in=self._carry if warm else None)
            except Exception as exc:
                # the window's futures carry the real error (a compile or
                # solve failure), and the loop lives on to take the next
                # window; a probe window has no futures, so its error is
                # logged, and it keeps the previous carry
                for it in items:
                    it.future.set_exception(exc)
                self._ledger.bump(failed_decisions=len(items))
                if not items:
                    _log.warning("drift probe window failed", exc_info=exc)
                return
            dt = time.perf_counter() - t0
        self._carry = carry if carry else self._carry
        self._last_counts = counts
        self._ledger.bump(
            windows=1,
            warm_windows=int(warm), cold_windows=int(not warm),
            transfer_lanes=deltas["transfer_lanes"],
            resolve_lanes=deltas["resolve_lanes"],
            fallback_lanes=deltas["fallback_lanes"],
            solve_seconds_total=dt)
        now = time.perf_counter()
        for k, it in enumerate(items):
            try:
                sched = sol.schedule(k, strict=self.config.strict)
                d = _decision(self._stats, sched, it.count, pperm)
                dec = RouteDecision(
                    shares=d["shares"], makespan=d["makespan"],
                    uniform_makespan=d["uniform_makespan"], schedule=sched,
                    warm=warm, window_size=len(items), solve_seconds=dt,
                    latency_seconds=now - it.t_submit)
                it.future.set_result(dec)
                self._ledger.bump(decisions=1)
                self._ledger.record_latency(dec.latency_seconds)
            except Exception as exc:
                it.future.set_exception(exc)
                self._ledger.bump(failed_decisions=1)

    def prewarm(self) -> None:
        """Compile the service's window executables before taking traffic.

        Runs one cold and one warm-seeded micro-batch-wide solve against
        the current fleet stats (outside the window ledger), so the
        first real admission window — and the first drift re-solve —
        hit the compile cache instead of paying an XLA compile as
        admission latency.  The warm pass also leaves a carry anchor,
        so a drift that precedes any real window still transfers.
        """
        with self._mu:
            counts = [1] * LANE_MICROBATCH
            specs, _ = _burst_specs(self._stats, counts)
            _, carry = self._solver.solve_batch_carry(
                specs, frontend=self.config.frontend, presorted=True)
            self._solver.solve_batch_carry(
                specs, frontend=self.config.frontend, presorted=True,
                warm=True, carry_in=carry)
            if self._carry is None:
                self._carry = carry or None

    # -- the loop -----------------------------------------------------------

    def start(self) -> "RouterService":
        """Run the admission loop on a daemon thread."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._run, name="dlt-router-service", daemon=True)
        self._thread.start()
        return self

    def stop(self, flush: bool = True) -> None:
        """Stop the loop; by default drain pending admissions first."""
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if flush:
            self.flush()

    def _run(self) -> None:
        window_s = self.config.admit_window_ms / 1000.0
        # the idle poll bounds how stale an empty-queue drift refresh can
        # get; the window itself bounds admission latency
        idle_poll = max(window_s, 0.005)
        while not self._stop_evt.is_set():
            got = self._queue.wait_first(timeout=idle_poll)
            if self._stop_evt.is_set():
                break
            if got:
                # deadline batching: admit everything that arrives within
                # admit_window_ms of the window-opening request
                self._stop_evt.wait(window_s)
            if got or self._drift_pending:
                self.step()

    def __enter__(self) -> "RouterService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- introspection ------------------------------------------------------

    @property
    def stats(self):
        """Counter snapshot (includes current queue depth)."""
        return self._ledger.snapshot(queue_depth=self._queue.depth)

    @property
    def ledger(self) -> ServiceStats:
        """The live mutable ledger (for latency quantiles)."""
        return self._ledger

    @property
    def current_stats(self) -> RouterStats:
        """The fleet stats the service is currently solving against."""
        with self._mu:
            return self._stats

    @property
    def queue_depth(self) -> int:
        return self._queue.depth
