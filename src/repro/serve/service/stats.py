"""Service-level counters and the admission-to-decision latency ledger.

Mirrors the ``DLTEngine`` stats idiom (cumulative integer counters,
snapshot on read) and adds what a *service* needs that a solver does
not: a latency reservoir with tail quantiles, because an always-on
router is judged by its p99, not its mean.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List

import numpy as np

__all__ = ["ServiceStats", "ServiceStatsSnapshot"]

# Default cap on retained per-decision latencies (override per ledger
# via ``ServiceStats(reservoir=...)`` / the service's ``latency_reservoir``
# knob).  At say 1k decisions/sec a day of uptime is ~86M samples; the
# reservoir keeps the most recent window instead — SLOs are about recent
# behavior anyway.  Sizing note: a quantile ``q`` needs roughly
# ``1 / (1 - q)`` samples before its readout means anything (p999 ~1k),
# so shrinking the reservoir below that silently degrades the tail
# quantiles to the max (see ``latency_quantile``).
_LATENCY_RESERVOIR = 65536


@dataclasses.dataclass(frozen=True)
class ServiceStatsSnapshot:
    """Immutable view of the service counters at one instant."""

    windows: int                # admission windows solved
    cold_windows: int           # windows solved from the cold start point
    warm_windows: int           # drift windows warm-seeded from an anchor
    decisions: int              # futures resolved with a RouteDecision
    failed_decisions: int       # futures failed (strict-lane errors or
                                # a window solve that raised)
    drift_events: int           # times the EWMA crossed the threshold
    transfer_lanes: int         # engine lanes seeded via warm_transfer
    resolve_lanes: int          # warm lanes the engine re-solved cold
    fallback_lanes: int         # lanes the engine sent to the oracle
    queue_depth: int            # pending admissions right now
    solve_seconds_total: float  # wall time inside engine solves


class ServiceStats:
    """Mutable, thread-safe ledger owned by a ``RouterService``."""

    def __init__(self, reservoir: int = _LATENCY_RESERVOIR):
        if reservoir < 1:
            raise ValueError(f"reservoir must be >= 1, got {reservoir}")
        self.reservoir = int(reservoir)
        self._lock = threading.Lock()
        self.windows = 0
        self.cold_windows = 0
        self.warm_windows = 0
        self.decisions = 0
        self.failed_decisions = 0
        self.drift_events = 0
        self.transfer_lanes = 0
        self.resolve_lanes = 0
        self.fallback_lanes = 0
        self.solve_seconds_total = 0.0
        self._latencies: List[float] = []

    def bump(self, **by) -> None:
        with self._lock:
            for k, v in by.items():
                setattr(self, k, getattr(self, k) + v)

    def record_latency(self, seconds: float) -> None:
        with self._lock:
            self._latencies.append(float(seconds))
            if len(self._latencies) > self.reservoir:
                del self._latencies[: len(self._latencies)
                                    - self.reservoir]

    def latencies(self) -> List[float]:
        """Copy of the retained per-decision latencies (seconds)."""
        with self._lock:
            return list(self._latencies)

    def latency_quantile(self, q: float) -> float:
        """Admission-to-decision latency quantile in seconds (NaN if none).

        Small-sample honesty: a quantile ``q`` estimated from ``n``
        samples with fewer than one expected sample above it
        (``n * (1 - q) < 1`` — e.g. p999 below ~1k observations) would
        just interpolate between the top two order statistics, reading
        as a confident tail number that the data cannot support.  Those
        readouts return the sample MAX instead — pessimistic, never
        fabricated — and ``latency_summary`` reports ``n`` alongside so
        a consumer can tell which quantiles are saturated.
        """
        with self._lock:
            if not self._latencies:
                return float("nan")
            arr = np.asarray(self._latencies)
            if arr.size * (1.0 - q) < 1.0:
                return float(arr.max())
            return float(np.quantile(arr, q))

    def latency_summary(self) -> Dict[str, float]:
        """The SLO triple p50 / p99 / p999 in seconds, plus ``n`` — the
        sample count backing them (quantiles with ``n * (1 - q) < 1``
        are the sample max, see :meth:`latency_quantile`)."""
        with self._lock:
            n = len(self._latencies)
        return {"p50": self.latency_quantile(0.50),
                "p99": self.latency_quantile(0.99),
                "p999": self.latency_quantile(0.999),
                "n": n}

    def snapshot(self, queue_depth: int = 0) -> ServiceStatsSnapshot:
        with self._lock:
            return ServiceStatsSnapshot(
                windows=self.windows,
                cold_windows=self.cold_windows,
                warm_windows=self.warm_windows,
                decisions=self.decisions,
                failed_decisions=self.failed_decisions,
                drift_events=self.drift_events,
                transfer_lanes=self.transfer_lanes,
                resolve_lanes=self.resolve_lanes,
                fallback_lanes=self.fallback_lanes,
                queue_depth=queue_depth,
                solve_seconds_total=self.solve_seconds_total,
            )
