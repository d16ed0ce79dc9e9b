"""End-to-end check of the DLT engine and the routing service on a TPU.

    python chip_smoke.py             # phases A and B, one chip
    python chip_smoke.py --chips 4   # phase C only, four chips

* **A. Planning families**, under the ``fp64`` and ``mixed`` precision
  policies: ``DLTEngine.solve_batch`` over 256 seeded scenarios of the
  paper's Sec 3.2 no-front-end family (N 1..5 sources, M 4..32
  processors) and ``DLTEngine.sweep`` over the Sec 6 processor prefixes
  of one system (m = 1..32).  The planning engines pad each family to
  one shape (``bucket="none"``): every compiled shape is a TPU compile
  of a minute or two, and a padded processor costs a lane only masked
  rows.
* **B. Router**, ``fp64``: a ``RouterService`` for one fleet of 4
  frontends x 128 replicas is prewarmed, takes 64 seeded bursts of 1-47
  requests on its background loop, then one drift re-solve.
* **C. Sharded executor** (``--chips 4``): phase A's family, padded to
  one shape of 16 micro-batches, through ``executor="sharded"`` over
  the four-chip lane mesh and through the local executor on chip 0.

Every answer is checked: each lane certified optimal, 16 lanes per
planning family within 1e-6 of the NumPy simplex oracle, one router
window bit for bit equal to one-shot ``route_requests_batch`` and the
drift window within 1e-6 of HiGHS (the NumPy simplex stops at its
iteration limit on a 4 x 128 fleet), sharded results bit for bit equal
to local ones.  Any oracle fallback, kernel downgrade, fp64 re-solve
of a mixed lane, failed future or exception fails the run, and so do
mixed lanes that left their fp32 phase early beyond
``MAX_HANDOVER_SHARE`` of the lanes solved (each is named).

Each phase prints one line of readings: per call its wall seconds and
the part of them the engine spent compiling, the kernel each compiled
bucket resolved to, and the engine's counters.  They record that the
system runs, not how fast: the calls of all phases run at once, so
they share the host's cores and the chip.

The script needs a TPU and exits non-zero, printing no result, when
JAX finds none.  A passing run's last line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Compiled executables persist in ``$JAX_COMPILATION_CACHE_DIR`` when it
is set, else in ``.jax_cache`` next to this file.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import os
import sys
import time
from collections import Counter
from pathlib import Path
from typing import Callable, NamedTuple

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.dlt import (  # noqa: E402
    STATUS_OPTIMAL,
    DLTEngine,
    SystemSpec,
    enable_compile_cache,
    solve,
)
from repro.core.dlt.executors import LANE_MICROBATCH  # noqa: E402
from repro.serve import (  # noqa: E402
    RouterService,
    RouterStats,
    ServiceConfig,
    route_requests_batch,
)

ORACLE_RTOL = 1e-6
ORACLE_LANES = 16
POLICIES = ("fp64", "mixed")
FUTURE_TIMEOUT_S = 600.0

#: Share of mixed lane solves that may leave the fp32 phase early.  A
#: handed-over lane certifies in the fp64 loop of the same executable,
#: so it costs fp64 iterations, not a result.  The bound tells a
#: working fp32 factor (the Pallas kernel on a v5e: one lane of the
#: 256-lane family) from a broken one (the XLA scans as the fp32 factor
#: on a v5e: every lane).
MAX_HANDOVER_SHARE = 1 / 64


class SmokeFailure(AssertionError):
    """A check of the smoke run did not hold."""


def _require(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _say(phase: str, **readings) -> None:
    print(f"[{phase}] {json.dumps(readings, default=str)}", flush=True)


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------

def planning_specs(rng, count: int, n_max: int = 5, m_lo: int = 4,
                   m_hi: int = 32) -> list:
    """Ragged Sec 3.2 family: N in 1..n_max, M in m_lo..m_hi (by default
    the batched bench's acceptance family)."""
    return [
        SystemSpec(G=rng.uniform(0.1, 1.0, n),
                   R=np.sort(rng.uniform(0.0, 2.0, n)),
                   A=rng.uniform(0.5, 4.0, m),
                   J=float(rng.uniform(50.0, 200.0)))
        for n, m in zip(rng.integers(1, n_max + 1, count),
                        rng.integers(m_lo, m_hi + 1, count))
    ]


def sweep_spec(rng, n: int = 3, m: int = 32) -> SystemSpec:
    """One system whose processor prefixes form the Sec 6 sweep."""
    return SystemSpec(G=rng.uniform(0.1, 1.0, n),
                      R=np.sort(rng.uniform(0.0, 2.0, n)),
                      A=rng.uniform(0.5, 4.0, m), J=100.0)


def fleet_stats(rng, frontends: int = 4, replicas: int = 128) -> RouterStats:
    """A measured fleet: per-request seconds of each frontend and replica.

    Every frontend holds its burst when the window opens (R = 0): with
    staggered releases the release chaining (Eq 3) would ask more of
    each frontend's first replica than a one-request burst can carry.
    """
    return RouterStats(
        frontend_seconds_per_request=rng.uniform(0.0005, 0.002, frontends),
        frontend_release=np.zeros(frontends),
        replica_seconds_per_request=rng.uniform(0.02, 0.2, replicas))


# ---------------------------------------------------------------------------
# running the phases
# ---------------------------------------------------------------------------

#: Host memory to set aside per concurrent compile: about 2.5 GiB at
#: the peak of one mixed-policy interior-point compile for a v5e, plus
#: what the compiled executables keep (0.3-0.8 GiB each: the
#: fp64-emulating programs run to ~60k HLO instructions).
COMPILE_BYTES = 6 << 30


def compile_workers() -> int:
    """Concurrent calls: one per core, as far as host memory allows."""
    with open("/proc/meminfo") as f:
        avail_kib = next(int(line.split()[1]) for line in f
                         if line.startswith("MemAvailable:"))
    return max(1, min(len(os.sched_getaffinity(0)),
                      (avail_kib << 10) // COMPILE_BYTES))


class Timed(NamedTuple):
    """What one call returned, its wall seconds, and the seconds of
    them this thread spent compiling executables."""

    value: object
    seconds: float
    compile_s: float


def timed(eng: DLTEngine, fn: Callable) -> Callable[[], Timed]:
    """``fn`` as a call that reports its seconds (see :class:`Timed`)."""
    def call() -> Timed:
        with eng.counter_scope() as delta:
            t0 = time.perf_counter()
            value = fn()
            seconds = time.perf_counter() - t0
        return Timed(value, seconds, delta["compile_ms"] / 1e3)
    return call


class Phase(NamedTuple):
    """One phase of the run: ``calls`` (name -> a :func:`timed` call)
    and ``check``, which gets their :class:`Timed` results by name."""

    calls: dict
    check: Callable[[dict], None]


def run_phases(phases: list) -> None:
    """Run every phase's calls at once, then check the phases in order.

    TPU compiles of the fp64-emulating interior-point programs take a
    minute or more each, some calls compile several programs one after
    another (a sweep's cold anchors, then its warm pass), and compiles
    release the interpreter lock, so the calls run on
    :func:`compile_workers` threads.  Each phase is checked as soon as
    its own calls have returned.
    """
    with cf.ThreadPoolExecutor(compile_workers()) as pool:
        futures = [{name: pool.submit(call)
                    for name, call in ph.calls.items()} for ph in phases]
        for ph, futs in zip(phases, futures):
            ph.check({name: f.result() for name, f in futs.items()})


# ---------------------------------------------------------------------------
# readings
# ---------------------------------------------------------------------------

def kernels_of(eng: DLTEngine, precision: str) -> dict:
    """Kernel each compiled bucket of one policy resolved to.

    Read from the engine's compile-cache keys: the kind leads every key
    and the precision token sits second to last.  Fails if any Pallas
    executable was compiled in interpret mode.
    """
    kinds: Counter = Counter()
    for key in eng.compile_cache_info()["keys"]:
        ptok = key[-2]
        if (ptok if isinstance(ptok, str) else ptok[0]) != precision:
            continue
        kinds[key[0]] += 1
        if key[0] == "pallas_banded":
            _require(key[12] is False, f"interpret-mode Pallas key {key}")
    return dict(kinds)


def handovers(sol) -> list:
    """(lane, N, M, reason) of each lane of a batch that left the fp32
    phase early (``precision.HANDOVER_*`` codes; none under fp64)."""
    if sol.phase1_handover is None:
        return []
    return [(int(k), int(sol.spec.n_sources[k]), int(sol.spec.n_procs[k]),
             int(sol.phase1_handover[k]))
            for k in np.flatnonzero(sol.phase1_handover)]


def _rel_err(got: float, want: float) -> float:
    return abs(got - want) / max(1.0, abs(want))


def _counters(eng: DLTEngine) -> dict:
    st = eng.stats
    return dict(oracle_fallback_lanes=st.fallback_lanes,
                kernel_fallbacks=st.kernel_fallbacks,
                precision_fallback_lanes=st.precision_fallback_lanes,
                phase1_handover_lanes=st.phase1_handover_lanes,
                banded_lanes=st.banded_lanes, pallas_lanes=st.pallas_lanes,
                compiles=st.cache_misses, compile_ms=st.compile_ms)


def _seconds(**calls: Timed) -> dict:
    """Wall and compile seconds of each named call."""
    out = {}
    for name, t in calls.items():
        out[f"{name}_s"] = t.seconds
        out[f"{name}_compile_s"] = t.compile_s
    return out


def _require_no_fallbacks(eng: DLTEngine, phase: str) -> None:
    st = eng.stats
    _require(st.fallback_lanes == 0,
             f"{phase}: {st.fallback_lanes} lanes fell back to the oracle")
    _require(st.kernel_fallbacks == 0,
             f"{phase}: {st.kernel_fallbacks} kernel downgrades")
    _require(st.precision_fallback_lanes == 0,
             f"{phase}: {st.precision_fallback_lanes} mixed lanes "
             "re-solved in fp64")
    _require(st.phase1_handover_lanes <= MAX_HANDOVER_SHARE * st.lanes,
             f"{phase}: {st.phase1_handover_lanes} of {st.lanes} mixed "
             "lane solves left the fp32 phase on a step that did not "
             "lower mu")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def planning_phase(seed: int, policy: str, count: int = 256,
                   n_max: int = 5, m_lo: int = 4, m_hi: int = 32) -> Phase:
    """A: the Sec 3.2 family and the Sec 6 sweep under one policy."""
    rng = np.random.default_rng(seed)
    specs = planning_specs(rng, count, n_max, m_lo, m_hi)
    system = sweep_spec(rng, m=m_hi)
    eng = DLTEngine(precision=policy, bucket="none")

    def check(res: dict) -> None:
        sol, sw = res["solve_batch"].value, res["sweep"].value
        errs = [_rel_err(sol.finish_time[k],
                         solve(specs[k], frontend=False,
                               solver="simplex").finish_time)
                for k in np.linspace(0, count - 1, ORACLE_LANES, dtype=int)]
        cspec = system.canonical()[0]
        errs_sw = [_rel_err(sw.finish_time[m - 1],
                            solve(cspec.subset_processors(int(m)),
                                  frontend=False, solver="simplex",
                                  presorted=True).finish_time)
                   for m in np.linspace(1, m_hi, ORACLE_LANES, dtype=int)]
        worst = max(errs + errs_sw)
        # readings first, so a failing phase still shows them
        _say(f"A {policy}", **_seconds(**res), lanes=count,
             sweep_prefixes=int(sw.m.size), oracle_max_rel_err=worst,
             handover_lanes=handovers(sol),
             kernels=kernels_of(eng, policy), **_counters(eng))
        _require(np.all(sol.status == STATUS_OPTIMAL),
                 f"A {policy}: lane statuses {np.bincount(sol.status)}")
        _require(sol.fallback_count == 0,
                 f"A {policy}: fallback_count {sol.fallback_count}")
        _require(np.array_equal(sw.m, np.arange(1, m_hi + 1)),
                 f"A {policy}: sweep certified prefixes {sw.m}")
        _require_no_fallbacks(eng, f"A {policy}")
        _require(worst <= ORACLE_RTOL,
                 f"A {policy}: oracle rel err {worst:.3e} > {ORACLE_RTOL}")

    return Phase(calls={
        "solve_batch": timed(eng, lambda: eng.solve_batch(
            specs, frontend=False)),
        "sweep": timed(eng, lambda: eng.sweep(
            system, frontend=False, m_max=m_hi)),
    }, check=check)


def router_phase(seed: int, frontends: int = 4, replicas: int = 128,
                 bursts: int = 64, max_burst: int = 47) -> Phase:
    """B: the routing service's loop, window identity and drift re-solve."""
    rng = np.random.default_rng(seed)
    stats = fleet_stats(rng, frontends, replicas)
    counts = [int(c) for c in rng.integers(1, max_burst + 1, bursts)]
    eng = DLTEngine(precision="fp64")
    # windows of at most one micro-batch all run the prewarmed shape
    svc = RouterService(stats, ServiceConfig(max_window=LANE_MICROBATCH),
                        engine=eng)

    def check(res: dict) -> None:
        misses = eng.stats.cache_misses
        t0 = time.perf_counter()
        with svc:
            futures = [svc.submit(c) for c in counts]
            decisions = [f.result(timeout=FUTURE_TIMEOUT_S) for f in futures]
        loop_s = time.perf_counter() - t0
        compiles_in_loop = eng.stats.cache_misses - misses
        _require(compiles_in_loop == 0,
                 f"B: {compiles_in_loop} compiles after prewarm()")

        # the first window, bit for bit, against its bursts routed one-shot
        size = decisions[0].window_size
        ref = route_requests_batch(stats, counts[:size], engine=eng)
        for d, r in zip(decisions[:size], ref):
            _require(np.array_equal(d.shares, r["shares"])
                     and d.makespan == r["makespan"],
                     "B: the first window differs from one-shot routing")

        drifted = np.asarray(stats.replica_seconds_per_request) * 1.5
        svc.observe(drifted)
        future = svc.submit(counts[0])
        svc.step()
        dec = future.result(timeout=0)
        oracle = solve(SystemSpec(G=stats.frontend_seconds_per_request,
                                  R=stats.frontend_release, A=drifted,
                                  J=float(counts[0])),
                       frontend=True, solver="highs").finish_time
        s = svc.stats
        _require(dec.warm and s.transfer_lanes > 0,
                 f"B: drift window warm={dec.warm} "
                 f"transfer_lanes={s.transfer_lanes}")
        _require(_rel_err(dec.makespan, oracle) <= ORACLE_RTOL,
                 f"B: drift makespan {dec.makespan} vs oracle {oracle}")
        _require(s.failed_decisions == 0,
                 f"B: {s.failed_decisions} failed decisions")
        _require(s.fallback_lanes == 0, f"B: {s.fallback_lanes} oracle lanes")
        _require_no_fallbacks(eng, "B")
        _say("B fp64", **_seconds(**res), loop_s=loop_s,
             decisions=s.decisions, windows=s.windows,
             identity_window=size, warm_windows=s.warm_windows,
             transfer_lanes=s.transfer_lanes, resolve_lanes=s.resolve_lanes,
             solve_seconds_total=s.solve_seconds_total,
             compiles_in_loop=compiles_in_loop,
             drift_rel_err=_rel_err(dec.makespan, oracle),
             kernels=kernels_of(eng, "fp64"), **_counters(eng))

    return Phase(calls={"prewarm": timed(eng, svc.prewarm)}, check=check)


def sharded_phase(seed: int, count: int = 256, n_max: int = 5,
                  m_lo: int = 4, m_hi: int = 32) -> Phase:
    """C: sharded over every visible device == local on device 0."""
    specs = planning_specs(np.random.default_rng(seed), count, n_max,
                           m_lo, m_hi)
    base = DLTEngine(precision="fp64", bucket="none")
    engines = {"local": base.configured(executor="local"),
               "sharded": base.configured(executor="sharded")}
    ndev = engines["sharded"]._resolve_executor().device_count()

    def check(res: dict) -> None:
        a, b = res["local"].value, res["sharded"].value
        _require(np.all(a.status == STATUS_OPTIMAL),
                 f"C: lane statuses {np.bincount(a.status)}")
        for field in ("finish_time", "beta", "status", "iterations",
                      "TS", "TF"):
            _require(np.array_equal(getattr(a, field), getattr(b, field),
                                    equal_nan=field != "status"),
                     f"C: sharded {field} differs from local")
        _require_no_fallbacks(base, "C")
        _say("C fp64", **_seconds(**res), devices=ndev, lanes=count,
             bit_identical=True, kernels=kernels_of(base, "fp64"),
             **_counters(base))

    return Phase(calls={
        name: timed(e, lambda e=e: e.solve_batch(specs, frontend=False))
        for name, e in engines.items()}, check=check)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A and B; 4: phase C only")
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} device(s)")
    cache_dir = enable_compile_cache(ROOT / ".jax_cache")
    rows, source = DLTEngine().banded_min_rows()
    _say("setup", jax=jax.__version__, kind=dev.device_kind,
         devices=len(devices), cpus=len(os.sched_getaffinity(0)),
         compile_workers=compile_workers(), compile_cache=cache_dir,
         banded_min_rows=rows, banded_min_rows_source=source)

    t0 = time.perf_counter()
    if args.chips == 4:
        run_phases([sharded_phase(args.seed)])
    else:
        run_phases([planning_phase(args.seed, p) for p in POLICIES]
                   + [router_phase(args.seed)])
    _say("done", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
