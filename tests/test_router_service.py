"""Always-on routing service: admission windows, drift re-solves, stats.

Covers the service subsystem (``repro.serve.service``) plus the routing
core it shares with one-shot ``route_requests``:

* ``RouterStats`` construction validation (each bad field named),
* ``_round_shares`` settling the integer remainder in BOTH directions,
* the micro-batch bit-identity invariant: a batched admission window's
  decisions are bit-identical to one-shot ``route_requests`` on the
  same stats, regardless of window size,
* deadline batching (``step`` / ``flush`` / ``max_window`` / the
  background thread),
* EWMA drift detection triggering warm-transfer re-solves with 1e-6
  scalar-oracle parity, including the empty-queue refresh,
* strict-lane failure semantics (the future carries the lane error),
* the service stats ledger and latency quantiles.

Every test shares the process-default engine session so compiled window
shapes are paid for once across the module.
"""

import numpy as np
import pytest

from repro.core.dlt import SystemSpec, get_default_engine, solve
from repro.core.dlt.executors import LANE_MICROBATCH
from repro.serve import (RateObserver, RouteDecision, RouterService,
                         RouterStats, ServiceConfig)
from repro.serve.engine import (_round_shares, route_requests,
                                route_requests_batch)
from repro.serve.service import DriftTracker, ServiceStats

FLEET_G = [0.001, 0.002]
FLEET_R = [0.0, 0.0]
FLEET_A = [0.05, 0.10, 0.20, 0.08]


def fleet() -> RouterStats:
    return RouterStats(FLEET_G, FLEET_R, FLEET_A)


# ---------------------------------------------------------------------------
# RouterStats validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs, field", [
    (dict(frontend_seconds_per_request=[0.0],
          frontend_release=[0.0],
          replica_seconds_per_request=[1.0]),
     "frontend_seconds_per_request"),
    (dict(frontend_seconds_per_request=[-0.1],
          frontend_release=[0.0],
          replica_seconds_per_request=[1.0]),
     "frontend_seconds_per_request"),
    (dict(frontend_seconds_per_request=[0.1],
          frontend_release=[0.0],
          replica_seconds_per_request=[0.0, 1.0]),
     "replica_seconds_per_request"),
    (dict(frontend_seconds_per_request=[0.1],
          frontend_release=[0.0],
          replica_seconds_per_request=[np.nan]),
     "replica_seconds_per_request"),
    (dict(frontend_seconds_per_request=[0.1],
          frontend_release=[0.0, 0.0],
          replica_seconds_per_request=[1.0]),
     "frontend_release"),
    (dict(frontend_seconds_per_request=[0.1],
          frontend_release=[-1.0],
          replica_seconds_per_request=[1.0]),
     "frontend_release"),
    (dict(frontend_seconds_per_request=[],
          frontend_release=[],
          replica_seconds_per_request=[1.0]),
     "frontend_seconds_per_request"),
    (dict(frontend_seconds_per_request=[0.1],
          frontend_release=[np.inf],
          replica_seconds_per_request=[1.0]),
     "frontend_release"),
])
def test_router_stats_validation_names_the_field(kwargs, field):
    with pytest.raises(ValueError, match=field):
        RouterStats(**kwargs)


def test_router_stats_accepts_valid_input():
    s = fleet()
    assert len(s.replica_seconds_per_request) == 4


# ---------------------------------------------------------------------------
# share rounding (both remainder directions)
# ---------------------------------------------------------------------------

def test_round_shares_positive_remainder():
    # floors sum to 6, two units short: largest fractional parts win
    out = _round_shares(np.array([1.4, 2.3, 3.45]), 8)
    assert out.tolist() == [2, 2, 4]
    assert out.sum() == 8


def test_round_shares_negative_remainder():
    # processor_load sums ABOVE J (LP tolerance dust): floors already
    # over-count and the smallest fractional claims give units back
    out = _round_shares(np.array([2.6, 2.7, 2.9]), 7)
    assert out.sum() == 7
    assert out.tolist() == [2, 2, 3]


def test_round_shares_never_negative():
    out = _round_shares(np.array([0.1, 0.1, 5.9]), 3)
    assert out.sum() == 3
    assert (out >= 0).all()


def test_round_shares_randomized_exact_sum():
    rng = np.random.default_rng(0)
    for _ in range(200):
        m = int(rng.integers(1, 9))
        j = int(rng.integers(1, 120))
        load = rng.uniform(0, 1, m)
        load = load / load.sum() * j
        # perturb both ways past J to exercise each remainder branch
        for scale in (0.98, 1.0, 1.02):
            out = _round_shares(load * scale, j)
            assert out.sum() == j
            assert (out >= 0).all()


def test_route_requests_shares_sum_exact():
    for j in (1, 7, 40, 137):
        out = route_requests(fleet(), j)
        assert out["shares"].sum() == j
        assert (out["shares"] >= 0).all()


# ---------------------------------------------------------------------------
# batched routing == one-shot routing, bit for bit
# ---------------------------------------------------------------------------

def test_batch_bit_identical_to_oneshot():
    stats = fleet()
    counts = [40, 17, 8, 3, 64, 40]
    batch = route_requests_batch(stats, counts)
    for c, d in zip(counts, batch):
        one = route_requests(stats, c)
        np.testing.assert_array_equal(d["shares"], one["shares"])
        np.testing.assert_array_equal(d["schedule"].beta,
                                      one["schedule"].beta)
        assert d["makespan"] == one["makespan"]


def test_batch_empty_counts():
    assert route_requests_batch(fleet(), []) == []


def test_service_window_bit_identical_to_oneshot():
    stats = fleet()
    counts = [40, 17, 8]
    svc = RouterService(stats, ServiceConfig())
    futs = [svc.submit(c) for c in counts]
    assert svc.step() == len(counts)
    for c, f in zip(counts, futs):
        dec = f.result(timeout=0)
        one = route_requests(stats, c)
        assert isinstance(dec, RouteDecision)
        assert dec.window_size == len(counts)
        assert not dec.warm
        np.testing.assert_array_equal(dec.shares, one["shares"])
        np.testing.assert_array_equal(dec.schedule.beta,
                                      one["schedule"].beta)
        assert dec.makespan == one["makespan"]


# ---------------------------------------------------------------------------
# admission windows
# ---------------------------------------------------------------------------

def test_step_empty_queue_is_noop():
    svc = RouterService(fleet(), ServiceConfig())
    assert svc.step() == 0
    assert svc.stats.windows == 0


def test_submit_validates_count():
    svc = RouterService(fleet(), ServiceConfig())
    with pytest.raises(ValueError, match="num_requests"):
        svc.submit(0)


def test_max_window_caps_and_flush_drains():
    svc = RouterService(fleet(), ServiceConfig(max_window=2))
    futs = [svc.submit(5) for _ in range(5)]
    assert svc.step() == 2
    assert svc.queue_depth == 3
    assert svc.flush() == 3
    assert svc.queue_depth == 0
    for f in futs:
        assert f.result(timeout=0).shares.sum() == 5
    s = svc.stats
    assert s.windows == 3 and s.decisions == 5


def test_window_larger_than_microbatch():
    # windows above LANE_MICROBATCH pad up the lane ladder and stay
    # bit-identical to one-shot (the micro-batch invariant)
    stats = fleet()
    n = LANE_MICROBATCH + 4
    svc = RouterService(stats, ServiceConfig())
    futs = [svc.submit(9) for _ in range(n)]
    assert svc.step() == n
    one = route_requests(stats, 9)
    for f in futs:
        np.testing.assert_array_equal(f.result(timeout=0).shares,
                                      one["shares"])


def test_ledger_counters_and_latency():
    svc = RouterService(fleet(), ServiceConfig())
    svc.submit(12)
    svc.submit(30)
    svc.step()
    s = svc.stats
    assert s.windows == 1 and s.cold_windows == 1 and s.warm_windows == 0
    assert s.decisions == 2 and s.failed_decisions == 0
    assert s.queue_depth == 0
    assert s.solve_seconds_total > 0
    q = svc.ledger.latency_summary()
    assert 0 < q["p50"] <= q["p99"] <= q["p999"]


# ---------------------------------------------------------------------------
# drift detection and warm re-solves
# ---------------------------------------------------------------------------

def _drift(svc, factor=1.5, times=4):
    for _ in range(times):
        svc.observe(np.asarray(FLEET_A) * factor)


def test_drift_triggers_warm_resolve_with_oracle_parity():
    svc = RouterService(fleet(), ServiceConfig(drift_threshold=0.15,
                                               ewma_alpha=0.5))
    f0 = svc.submit(40)
    svc.step()
    assert not f0.result(timeout=0).warm
    _drift(svc, 1.5)
    assert svc.stats.drift_events == 1
    f1 = svc.submit(40)
    svc.step()
    dec = f1.result(timeout=0)
    s = svc.stats
    assert dec.warm
    assert s.warm_windows == 1
    assert s.transfer_lanes > 0          # warm_transfer seeded the window
    # the service now solves against the EWMA estimate (exactly 1.5x A)
    np.testing.assert_allclose(
        np.asarray(svc.current_stats.replica_seconds_per_request),
        np.asarray(FLEET_A) * 1.5)
    # 1e-6 parity vs the scalar simplex oracle on the drifted fleet
    oracle = solve(SystemSpec(G=FLEET_G, R=FLEET_R,
                              A=np.asarray(FLEET_A) * 1.5, J=40.0),
                   frontend=True, solver="simplex")
    assert abs(dec.makespan - oracle.finish_time) < 1e-6 * max(
        1.0, oracle.finish_time)


def test_below_threshold_drift_stays_cold():
    svc = RouterService(fleet(), ServiceConfig(drift_threshold=0.15,
                                               ewma_alpha=1.0))
    svc.submit(40)
    svc.step()
    _drift(svc, 1.05)                    # 5% move: under the threshold
    f = svc.submit(40)
    svc.step()
    assert not f.result(timeout=0).warm
    s = svc.stats
    assert s.drift_events == 0 and s.warm_windows == 0


def test_empty_queue_drift_refresh():
    svc = RouterService(fleet(), ServiceConfig(drift_threshold=0.15,
                                               ewma_alpha=0.5,
                                               refresh_on_drift=True))
    svc.submit(40)
    svc.step()
    _drift(svc, 1.5)
    assert svc.step() == 0               # no admissions: refresh window
    s = svc.stats
    assert s.windows == 2 and s.warm_windows == 1
    assert s.decisions == 1              # refresh resolves no futures
    # the next real window solves against the refreshed stats, cold
    f = svc.submit(40)
    svc.step()
    dec = f.result(timeout=0)
    assert not dec.warm
    one = route_requests(svc.current_stats, 40)
    np.testing.assert_array_equal(dec.shares, one["shares"])


def test_cold_warm_policy_skips_transfer():
    svc = RouterService(fleet(), ServiceConfig(drift_threshold=0.15,
                                               ewma_alpha=0.5,
                                               warm_policy="cold"))
    svc.submit(40)
    svc.step()
    _drift(svc, 1.5)
    f = svc.submit(40)
    svc.step()
    assert not f.result(timeout=0).warm
    s = svc.stats
    assert s.drift_events == 1 and s.warm_windows == 0
    assert s.transfer_lanes == 0


def test_prewarm_seeds_first_drift_window():
    svc = RouterService(fleet(), ServiceConfig(drift_threshold=0.15,
                                               ewma_alpha=0.5))
    svc.prewarm()
    assert svc.stats.windows == 0        # prewarm stays off the ledger
    _drift(svc, 1.5)
    f = svc.submit(40)
    svc.step()
    assert f.result(timeout=0).warm      # anchor came from prewarm


def test_drift_tracker_unit():
    t = DriftTracker(alpha=0.5)
    assert t.relative_drift([1.0]) == 0.0
    t.observe([2.0])
    np.testing.assert_allclose(t.ewma, [2.0])
    t.observe([1.0])
    np.testing.assert_allclose(t.ewma, [1.5])
    assert t.drifted([1.0], 0.4)
    assert not t.drifted([1.5], 0.4)
    with pytest.raises(ValueError):
        t.observe([1.0, 2.0])            # replica-count mismatch
    with pytest.raises(ValueError):
        t.observe([-1.0])
    with pytest.raises(ValueError):
        DriftTracker(alpha=0.0)


# ---------------------------------------------------------------------------
# strict-lane failure semantics
# ---------------------------------------------------------------------------

def test_failed_lane_raises_into_future():
    # a 1-iteration budget with verification on and the oracle fallback
    # off cannot certify any lane: strict schedule() must raise and the
    # service must forward that into the future, not hand back a
    # degenerate schedule
    eng = get_default_engine().configured(
        max_iter=1, min_warm_iter=1, oracle_fallback=False)
    svc = RouterService(fleet(), ServiceConfig(), engine=eng)
    f = svc.submit(40)
    svc.step()
    with pytest.raises(Exception):
        f.result(timeout=0)
    s = svc.stats
    assert s.failed_decisions == 1 and s.decisions == 0


# ---------------------------------------------------------------------------
# background thread
# ---------------------------------------------------------------------------

def test_background_loop_resolves_futures():
    svc = RouterService(fleet(), ServiceConfig(admit_window_ms=5.0))
    with svc:
        futs = [svc.submit(j) for j in (5, 9, 13)]
        decs = [f.result(timeout=60) for f in futs]
    assert [int(d.shares.sum()) for d in decs] == [5, 9, 13]
    assert svc.stats.queue_depth == 0
    assert all(d.latency_seconds > 0 for d in decs)


def test_stop_flushes_pending():
    svc = RouterService(fleet(), ServiceConfig(admit_window_ms=1000.0))
    svc.start()
    f = svc.submit(21)
    svc.stop()                           # long window: flush must drain it
    assert f.result(timeout=0).shares.sum() == 21


class _FailingSolver:
    """Engine view whose window solves raise (a compile error stand-in)."""

    def __init__(self, engine):
        self._engine = engine

    def solve_batch_carry(self, *args, **kwargs):
        raise RuntimeError("injected window solve failure")

    def __getattr__(self, name):
        return getattr(self._engine, name)


def test_failed_window_solve_fails_futures_fast_and_loop_survives(caplog):
    svc = RouterService(fleet(), ServiceConfig(admit_window_ms=5.0))
    good_solver = svc._solver
    svc._solver = _FailingSolver(good_solver)
    with svc:
        futs = [svc.submit(j) for j in (5, 9)]
        for f in futs:
            with pytest.raises(RuntimeError, match="injected window solve"):
                f.result(timeout=10)
        assert svc.stats.failed_decisions == 2
        assert svc.stats.decisions == 0
        # the loop thread is still alive and serves the next window
        svc._solver = good_solver
        assert svc.submit(13).result(timeout=60).shares.sum() == 13
    assert svc.stats.failed_decisions == 2
    assert svc.stats.decisions == 1
    # a drift probe window (empty queue) has no future to fail: its
    # error is logged, not lost
    svc._solver = _FailingSolver(good_solver)
    svc.observe(np.asarray(FLEET_A) * 3.0)
    assert svc.step() == 0
    assert "drift probe window failed" in caplog.text
    assert svc.stats.failed_decisions == 2


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs, match", [
    (dict(admit_window_ms=0.0), "admit_window_ms"),
    (dict(admit_window_ms=-1.0), "admit_window_ms"),
    (dict(max_window=0), "max_window"),
    (dict(drift_threshold=0.0), "drift_threshold"),
    (dict(ewma_alpha=0.0), "ewma_alpha"),
    (dict(ewma_alpha=1.5), "ewma_alpha"),
    (dict(warm_policy="lukewarm"), "warm_policy"),
    (dict(latency_reservoir=0), "latency_reservoir"),
])
def test_service_config_validation(kwargs, match):
    with pytest.raises(ValueError, match=match):
        ServiceConfig(**kwargs)


# ---------------------------------------------------------------------------
# drift tracker cold start (regression: EWMA must seed from the FIRST
# observation, never from the configured rates)
# ---------------------------------------------------------------------------

def test_drift_tracker_seeds_from_first_observation():
    t = DriftTracker(alpha=0.3)
    baseline = [0.1, 0.1]
    t.observe([0.3, 0.1])
    # the first observation IS the ewma — no blend with any baseline
    np.testing.assert_array_equal(t.ewma, [0.3, 0.1])
    # so a genuinely drifted cold start registers at full magnitude
    # after ONE window, not after 1/(1-alpha)^k of them
    assert t.relative_drift(baseline) == pytest.approx(2.0)
    assert t.drifted(baseline, threshold=0.15)


def test_drift_fires_on_first_observation_through_the_service():
    svc = RouterService(fleet(), ServiceConfig(drift_threshold=0.15))
    svc.observe([a * 2.0 for a in FLEET_A])   # single cold-start sample
    assert svc.stats.drift_events == 1


# ---------------------------------------------------------------------------
# latency ledger: small-sample quantiles + the reservoir knob
# ---------------------------------------------------------------------------

def test_latency_quantile_small_sample_returns_max():
    led = ServiceStats()
    for ms in range(1, 11):                   # n = 10 samples
        led.record_latency(ms / 1000.0)
    q = led.latency_summary()
    assert q["n"] == 10
    # p50 has 5 expected samples above it: interpolation is honest
    assert q["p50"] == pytest.approx(0.0055)
    # p99/p999 have < 1 expected sample above: the readout is the max,
    # never an interpolated tail the data cannot support
    assert q["p99"] == 0.010
    assert q["p999"] == 0.010
    # past ~1/(1-q) samples the quantile interpolates again
    for ms in range(11, 1201):
        led.record_latency(ms / 1000.0)
    assert led.latency_quantile(0.999) < 1.2


def test_latency_reservoir_knob_bounds_retention():
    led = ServiceStats(reservoir=4)
    for ms in (1, 2, 3, 4, 5, 6):
        led.record_latency(float(ms))
    assert led.latencies() == [3.0, 4.0, 5.0, 6.0]   # most recent window
    with pytest.raises(ValueError, match="reservoir"):
        ServiceStats(reservoir=0)
    svc = RouterService(fleet(), ServiceConfig(latency_reservoir=2))
    assert svc.ledger.reservoir == 2


# ---------------------------------------------------------------------------
# rate observer: measured generate() timings -> drift tracker
# ---------------------------------------------------------------------------

def test_rate_observer_reports_baseline_until_observed():
    obs = RateObserver(FLEET_A, window=4)
    np.testing.assert_array_equal(obs.rates(), FLEET_A)
    obs.record(2, num_requests=4, seconds=1.6)       # 0.4 s/request
    got = obs.rates()
    assert got[2] == pytest.approx(0.4)
    np.testing.assert_array_equal(np.delete(got, 2),
                                  np.delete(np.asarray(FLEET_A), 2))
    assert obs.sample_counts() == {2: 1}


def test_rate_observer_window_mean_and_validation():
    obs = RateObserver([0.1], window=2)
    obs.record(0, 1, 0.1)
    obs.record(0, 1, 0.2)
    obs.record(0, 1, 0.4)                 # evicts the 0.1 sample
    assert obs.rates()[0] == pytest.approx(0.3)
    with pytest.raises(ValueError, match="replica"):
        obs.record(1, 1, 0.1)
    with pytest.raises(ValueError, match="num_requests"):
        obs.record(0, 0, 0.1)
    with pytest.raises(ValueError, match="seconds"):
        obs.record(0, 1, -0.1)
    with pytest.raises(ValueError, match="window"):
        RateObserver([0.1], window=0)
    with pytest.raises(ValueError, match="baseline"):
        RateObserver([0.0])


def test_rate_observer_feeds_service_drift_automatically():
    svc = RouterService(fleet(), ServiceConfig(drift_threshold=0.15))
    obs = svc.rate_observer(window=4)
    assert obs.num_replicas == len(FLEET_A)
    # replica 1 measured at 2x its solved-against rate: one qualifying
    # sample pushes the full vector into observe() and trips drift,
    # with no operator call anywhere
    obs.record(1, num_requests=2, seconds=2 * 2 * FLEET_A[1])
    assert svc.stats.drift_events == 1
    ewma = svc._tracker.ewma
    assert ewma[1] == pytest.approx(2 * FLEET_A[1])
    # unobserved replicas came through at baseline: no phantom drift
    np.testing.assert_allclose(np.delete(ewma, 1),
                               np.delete(np.asarray(FLEET_A), 1))
