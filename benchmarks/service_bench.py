"""Always-on routing service under Poisson load: the SLO latency bench.

The batched-engine bench answers "scenarios/second"; a continuously
running router is judged by its latency *distribution*.  This bench
drives :class:`repro.serve.service.RouterService` — the async admission
queue + deadline-batching + drift-re-solve loop in front of the shared
DLT session — and measures what the service layer adds on top of the
solver:

* **Window/one-shot bit-identity.**  A batched admission window's
  decisions must be bit-identical to one-shot ``route_requests`` on the
  same stats: every routing solve pads onto the executor micro-batch
  ladder (``LANE_MICROBATCH`` lanes), so the per-lane program — and
  therefore each decision's bits — never depends on how many queries
  shared the window.  Checked here and asserted in
  tests/test_router_service.py.
* **Drift-triggered warm re-solves.**  Replica rates are drifted past
  the EWMA threshold; the next window must re-solve against the new
  estimate warm-seeded from the previous window's solution via the
  engine's ``warm_transfer`` carry (``transfer_lanes > 0``), and its
  makespan must match the scalar simplex oracle to 1e-6.
* **SLO under Poisson load.**  A real-time arrival process (exponential
  inter-arrival gaps) submits route queries against the service running
  on its background thread, with a mid-run rate drift to exercise warm
  re-solves under load.  Reports p50/p99/p999 admission-to-decision
  latency and sustained decisions/sec.
* **Concurrency (multi-fleet).**  1/2/4 ``FleetRouter`` loops over ONE
  shared engine session at a fixed aggregate Poisson rate: per-window
  decisions must stay bit-identical to one-shot routing under loop
  contention, with zero failed decisions.  A closed-loop saturation leg
  (per-fleet driver threads, no arrival gaps) measures aggregate peak
  decisions/s scaling vs the single loop — >= 1.5x at 2 fleets on a
  >= 4-core host, parity floor on the 1-core reference — and a final
  leg prices ``shard_map`` dispatch inside a latency window
  (``executor="sharded"`` SLO profile).

Run:  PYTHONPATH=src python -m benchmarks.service_bench
      PYTHONPATH=src python -m benchmarks.service_bench --smoke

With ``BENCH_OUT=<path>`` the results MERGE into the perf-trajectory
JSON as a ``"service"`` section (scripts/check.sh runs the batched bench
first, so the file already exists and this bench updates it in place,
AND-ing its pass flag).  ``scripts/bench_compare.py`` gates the
booleans unconditionally and the p99 latency / decisions/sec floors
under the usual topology-stamp skip rules; rebaseline per
CONTRIBUTING.md after an intentional service change.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import jax
import numpy as np

from repro.core.dlt import (DLTEngine, SystemSpec, enable_compile_cache,
                            solve)
from repro.core.dlt.executors import LANE_MICROBATCH
from repro.serve import (FleetRouter, RouterStats, RouterService,
                         ServiceConfig)
from repro.serve.engine import route_requests_batch
from .common import COMPILE_CACHE_DIR, check, table

#: The bench session — every service window and every one-shot reference
#: solve shares this engine's compiled-shape LRU, exactly as a deployed
#: router would share the process-wide default session.
enable_compile_cache(COMPILE_CACHE_DIR)
ENGINE = DLTEngine(executor=os.environ.get("ENGINE_EXECUTOR", "local"))

#: One fleet shape for the whole bench (2 frontends, 4 replicas): every
#: window lands in the same engine size bucket, so the SLO phase runs
#: entirely on executables compiled during the correctness phases.
FLEET_G = [0.001, 0.002]
FLEET_R = [0.0, 0.0]
FLEET_A = [0.05, 0.10, 0.20, 0.08]


def _topology() -> dict:
    return dict(
        backend=jax.default_backend(),
        device_count=jax.device_count(),
        executor=ENGINE.config.executor if isinstance(
            ENGINE.config.executor, str) else ENGINE.config.executor.name,
        precision=ENGINE._precision_policy(),
        cpu_count=os.cpu_count(),
    )


def _fleet() -> RouterStats:
    return RouterStats(FLEET_G, FLEET_R, FLEET_A)


def run_identity(r, out):
    """Batched admission window vs one-shot routing: bit-identity."""
    stats = _fleet()
    counts = [40, 17, 8, 3, 64]
    svc = RouterService(stats, ServiceConfig(admit_window_ms=1.0),
                       engine=ENGINE)
    futs = [svc.submit(c) for c in counts]
    svc.step()
    ones = [route_requests_batch(stats, [c], engine=ENGINE)[0]
            for c in counts]
    bit = all(
        np.array_equal(f.result().shares, o["shares"])
        and np.array_equal(f.result().schedule.beta, o["schedule"].beta)
        and f.result().makespan == o["makespan"]
        for f, o in zip(futs, ones))
    r.check("admission-window decisions bit-identical to one-shot "
            "route_requests", bool(bit), True, rtol=0)
    out["bit_identical_to_oneshot"] = bool(bit)


def run_drift(r, out):
    """Drift past the EWMA threshold -> warm re-solve + oracle parity."""
    stats = _fleet()
    svc = RouterService(
        stats, ServiceConfig(admit_window_ms=1.0, drift_threshold=0.15,
                             ewma_alpha=0.5), engine=ENGINE)
    f0 = svc.submit(40)
    svc.step()                                     # cold anchor window
    f0.result()
    drifted_A = np.asarray(FLEET_A) * 1.5
    for _ in range(4):
        svc.observe(drifted_A)                     # EWMA crosses 15%
    before = ENGINE.stats
    f1 = svc.submit(40)
    svc.step()                                     # warm drift window
    dec = f1.result()
    transferred = ENGINE.stats.transfer_lanes - before.transfer_lanes
    resolves = ENGINE.stats.resolve_lanes - before.resolve_lanes

    # oracle parity: the warm decision's makespan vs the scalar simplex
    # on the drifted fleet (the EWMA converged to exactly 1.5x A here)
    oracle = solve(SystemSpec(G=FLEET_G, R=FLEET_R, A=drifted_A, J=40.0),
                   frontend=True, solver="simplex")
    parity = abs(dec.makespan - oracle.finish_time) / max(
        1.0, oracle.finish_time)

    s = svc.stats
    table(["phase", "warm", "transfer", "resolves", "makespan", "parity"],
          [["drift re-solve", dec.warm, int(transferred), int(resolves),
            round(dec.makespan, 6), f"{parity:.1e}"]], fmt="{:>14}")
    r.check("drift window was warm-seeded (transfer_lanes > 0)",
            bool(dec.warm and transferred > 0), True, rtol=0)
    r.check("drift re-solve makespan parity vs scalar simplex oracle "
            "(rel err < 1e-6)", bool(parity < 1e-6), True, rtol=0)
    out["drift"] = dict(
        transfer_lanes=int(transferred), resolve_lanes=int(resolves),
        warm_windows=s.warm_windows, drift_events=s.drift_events,
        parity=float(parity))


def run_slo(r, smoke, out):
    """Poisson arrival load against the background-thread service."""
    if smoke:
        rate, duration, window_ms = 120.0, 2.0, 10.0
    else:
        rate, duration, window_ms = 250.0, 8.0, 5.0
    rng = np.random.default_rng(7)
    stats = _fleet()
    # max_window pins every solve to the LANE_MICROBATCH-lane executable
    # compiled during the correctness phases: a backlog drains as several
    # full windows instead of padding up the lane ladder and paying a
    # mid-run compile (the latency cliff this bench exists to catch)
    svc = RouterService(
        stats, ServiceConfig(admit_window_ms=window_ms, drift_threshold=0.2,
                             ewma_alpha=0.5, max_window=LANE_MICROBATCH),
        engine=ENGINE)
    futs = []
    drift_at = duration / 2.0
    drift_injected = threading.Event()
    t_start = time.perf_counter()
    with svc:
        # absolute-time Poisson schedule: each arrival targets
        # t_start + sum(exponential gaps), so Python submit overhead
        # shifts no later arrivals and the effective rate stays nominal
        t_next = 0.0
        while True:
            t_next += float(rng.exponential(1.0 / rate))
            if t_next >= duration:
                break
            now = time.perf_counter() - t_start
            if now >= drift_at and not drift_injected.is_set():
                # a fleet-wide 30% slowdown mid-run: the next window must
                # re-solve warm without stalling admission
                for _ in range(4):
                    svc.observe(np.asarray(FLEET_A) * 1.3)
                drift_injected.set()
            delay = t_next - (time.perf_counter() - t_start)
            if delay > 0:
                time.sleep(delay)
            futs.append(svc.submit(int(rng.integers(1, 48))))
    # context exit stops the loop and flushes the queue
    t_total = time.perf_counter() - t_start

    decs = [f.result(timeout=60) for f in futs]
    lat_ms = np.asarray([d.latency_seconds for d in decs]) * 1e3
    p50, p99, p999 = (float(np.quantile(lat_ms, q))
                      for q in (0.50, 0.99, 0.999))
    dps = len(decs) / t_total
    s = svc.stats
    mean_window = len(decs) / max(s.windows, 1)

    table(["arrivals/s", "decisions", "windows", "win size", "p50 ms",
           "p99 ms", "p999 ms", "dec/s"],
          [[round(rate, 1), len(decs), s.windows, round(mean_window, 1),
            round(p50, 2), round(p99, 2), round(p999, 2),
            round(dps, 1)]], fmt="{:>11}")
    r.check("all admitted queries decided (zero failed decisions)",
            bool(s.failed_decisions == 0 and s.queue_depth == 0), True,
            rtol=0)
    r.check("mid-run drift produced a warm window under load",
            bool(s.warm_windows >= 1 and s.drift_events >= 1), True, rtol=0)
    r.note("admission-to-decision latency",
           f"p50 {p50:.2f} ms / p99 {p99:.2f} ms / p999 {p999:.2f} ms "
           f"over {len(decs)} decisions")
    r.note("sustained decisions/sec",
           f"{dps:.1f} (arrival rate {rate:.0f}/s, window {window_ms} ms, "
           f"mean window size {mean_window:.1f})")
    r.note("service counters",
           f"windows {s.windows} (warm {s.warm_windows}) / transfer lanes "
           f"{s.transfer_lanes} / engine solve time "
           f"{s.solve_seconds_total:.2f}s")
    out["slo"] = dict(
        arrival_rate_per_s=rate, duration_s=duration,
        admit_window_ms=window_ms, decisions=len(decs),
        windows=s.windows, warm_windows=s.warm_windows,
        mean_window_size=mean_window,
        p50_ms=p50, p99_ms=p99, p999_ms=p999,
        decisions_per_s=dps, failed=s.failed_decisions,
        transfer_lanes=s.transfer_lanes,
        solve_seconds_total=s.solve_seconds_total)


#: Per-fleet A_j scale factors for the concurrency phase: distinct rates
#: per fleet (distinct LP data, same padded shape — every fleet shares
#: ONE compiled executable through the session LRU).
_FLEET_SCALES = (1.0, 1.25, 0.75, 1.5)


def _fleets(nf: int) -> dict:
    return {f"f{i}": RouterStats(
        FLEET_G, FLEET_R, [a * _FLEET_SCALES[i] for a in FLEET_A])
        for i in range(nf)}


def _poisson_leg(router, names, rate, duration, rng):
    """Fixed-aggregate Poisson arrivals round-robined over the fleets.

    Arrival-bound by design — it measures bit-identity and tail latency
    UNDER loop contention, not peak throughput (see ``_saturation_leg``
    for the scaling metric).  Returns ``{fleet: [(count, future), ...]}``.
    """
    futs = {name: [] for name in names}
    t_start = time.perf_counter()
    with router:
        t_next, k = 0.0, 0
        while True:
            t_next += float(rng.exponential(1.0 / rate))
            if t_next >= duration:
                break
            delay = t_next - (time.perf_counter() - t_start)
            if delay > 0:
                time.sleep(delay)
            name = names[k % len(names)]
            n = int(rng.integers(1, 48))
            futs[name].append((n, router.submit(name, n)))
            k += 1
    return futs, time.perf_counter() - t_start


def _saturation_leg(router, names, duration, rng):
    """Closed-loop peak throughput: one driver thread per fleet.

    Each driver submits a full micro-batch window then solves it with a
    synchronous ``step()`` (no daemon loop, no arrival gaps), so the
    aggregate decisions/s is compute-bound — the number that can
    actually scale past one loop when cores allow it.
    """
    counts = [0] * len(names)
    barrier = threading.Barrier(len(names) + 1)

    def drive(i, name):
        svc = router.service(name)
        lrng = np.random.default_rng(1000 + i)
        barrier.wait()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < duration:
            for _ in range(LANE_MICROBATCH):
                svc.submit(int(lrng.integers(1, 48)))
            counts[i] += svc.step()

    threads = [threading.Thread(target=drive, args=(i, name))
               for i, name in enumerate(names)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    router.flush()                       # resolve any tail admissions
    return sum(counts) / elapsed


def run_concurrency(r, smoke, out):
    """1/2/4 fleets over one shared session: identity, p99, scaling."""
    if smoke:
        rate, duration, sat_duration, window_ms = 120.0, 1.2, 1.0, 10.0
    else:
        rate, duration, sat_duration, window_ms = 250.0, 4.0, 3.0, 5.0
    cores = os.cpu_count() or 1
    cfg = ServiceConfig(admit_window_ms=window_ms,
                        max_window=LANE_MICROBATCH)
    rows, per_nf = [], {}
    bit_ok, failed_total = True, 0
    for nf in (1, 2, 4):
        rng = np.random.default_rng(11 + nf)
        fleets = _fleets(nf)
        router = FleetRouter(fleets, cfg, engine=ENGINE)
        router.prewarm()
        names = list(fleets)
        # -- Poisson leg: fixed AGGREGATE arrival rate split over fleets
        futs, t_total = _poisson_leg(router, names, rate, duration, rng)
        decs = [f.result(timeout=60) for per in futs.values()
                for _, f in per]
        lat_ms = np.asarray([d.latency_seconds for d in decs]) * 1e3
        p99 = float(np.quantile(lat_ms, 0.99)) if len(decs) else float("nan")
        agg = router.aggregate_stats()
        failed_total += int(agg["failed_decisions"])
        # -- bit-identity vs each fleet's one-shot baseline, under the
        #    contention the sibling loops just produced
        for name in names:
            oneshot = {n: route_requests_batch(
                fleets[name], [n], engine=ENGINE)[0]
                for n in sorted({n for n, _ in futs[name]})}
            for n, f in futs[name]:
                d = f.result(timeout=60)
                if not (np.array_equal(d.shares, oneshot[n]["shares"])
                        and d.makespan == oneshot[n]["makespan"]):
                    bit_ok = False
        # -- saturation leg: closed-loop peak decisions/s (the scaling
        #    metric; the Poisson leg is arrival-bound by construction)
        sat_router = FleetRouter(fleets, cfg, engine=ENGINE)
        sat_dps = _saturation_leg(sat_router, names, sat_duration, rng)
        per_nf[str(nf)] = dict(
            decisions=len(decs), p99_ms=p99,
            poisson_dps=len(decs) / t_total, saturated_dps=sat_dps,
            windows=int(agg["windows"]),
            failed=int(agg["failed_decisions"]))
        rows.append([nf, len(decs), int(agg["windows"]),
                     round(p99, 2), round(len(decs) / t_total, 1),
                     round(sat_dps, 1)])
    table(["fleets", "decisions", "windows", "p99 ms", "poisson dec/s",
           "saturated dec/s"], rows, fmt="{:>15}")

    scaling2 = per_nf["2"]["saturated_dps"] / per_nf["1"]["saturated_dps"]
    scaling4 = per_nf["4"]["saturated_dps"] / per_nf["1"]["saturated_dps"]
    r.check("per-window decisions bit-identical to one-shot under "
            "multi-fleet contention", bool(bit_ok), True, rtol=0)
    r.check("zero failed decisions across all fleet counts",
            bool(failed_total == 0), True, rtol=0)
    if cores >= 4:
        r.check("2-fleet aggregate decisions/s >= 1.5x single loop "
                f"({cores} cores)", bool(scaling2 >= 1.5), True, rtol=0)
    else:
        # 1-core reference topology: concurrency cannot add throughput,
        # it must only not destroy it (parity floor, not a speedup claim)
        r.check(f"2-fleet aggregate decisions/s parity on {cores} core(s) "
                "(>= 0.75x single loop)", bool(scaling2 >= 0.75), True,
                rtol=0)
    r.note("aggregate saturated scaling",
           f"2 fleets {scaling2:.2f}x / 4 fleets {scaling4:.2f}x vs one "
           f"loop ({cores} cores)")

    # -- sharded-executor SLO leg: price shard_map dispatch in-window
    sh_eng = ENGINE.configured(executor="sharded")
    sh_svc = RouterService(_fleet(), cfg, engine=sh_eng)
    sh_svc.prewarm()
    sh_rng = np.random.default_rng(23)
    sh_futs, sh_total = _poisson_leg(
        _SingleFleet(sh_svc), ["f0"],
        rate if not smoke else 60.0, duration, sh_rng)
    sh_decs = [f.result(timeout=60) for _, f in sh_futs["f0"]]
    sh_lat = np.asarray([d.latency_seconds for d in sh_decs]) * 1e3
    sh_p99 = (float(np.quantile(sh_lat, 0.99))
              if len(sh_decs) else float("nan"))
    sh_stats = sh_svc.stats
    r.check("sharded-executor SLO leg: zero failed decisions",
            bool(sh_stats.failed_decisions == 0), True, rtol=0)
    r.note("sharded SLO", f"p99 {sh_p99:.2f} ms over {len(sh_decs)} "
           f"decisions ({sh_eng._resolve_executor().device_count()} "
           "device(s))")
    out["concurrency"] = dict(
        fleets=per_nf, bit_identical=bool(bit_ok), failed=failed_total,
        scaling_2f=float(scaling2), scaling_4f=float(scaling4),
        cpu_count=cores,
        cache=dict((k, ENGINE.compile_cache_info()[k])
                   for k in ("hits", "misses", "lookups", "contention")),
        sharded_slo=dict(
            decisions=len(sh_decs), p99_ms=sh_p99,
            decisions_per_s=len(sh_decs) / sh_total,
            failed=int(sh_stats.failed_decisions)))


class _SingleFleet:
    """Adapter: drive one ``RouterService`` through the fleet-leg helpers."""

    def __init__(self, svc):
        self._svc = svc

    def submit(self, name, n):
        return self._svc.submit(n)

    def __enter__(self):
        self._svc.start()
        return self

    def __exit__(self, *exc):
        self._svc.stop()


def run(smoke=False):
    r = check("service_bench")
    out = {}
    run_identity(r, out)
    run_drift(r, out)
    run_slo(r, smoke, out)
    run_concurrency(r, smoke, out)

    bench_out = os.environ.get("BENCH_OUT")
    if bench_out:
        # merge into the batched bench's trajectory JSON (check.sh runs
        # that bench first); standalone runs start a fresh file
        try:
            with open(bench_out) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {"smoke": smoke, "topology": _topology(), "passed": True}
        data["service"] = out
        data["passed"] = bool(data.get("passed", True)) and r.passed
        with open(bench_out, "w") as f:
            json.dump(data, f, indent=2, default=float)
        r.note("service section merged into", bench_out)
    return r


if __name__ == "__main__":
    smoke = "--smoke" in sys.argv
    raise SystemExit(0 if run(smoke=smoke).passed else 1)
