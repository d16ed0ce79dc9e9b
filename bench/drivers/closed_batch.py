"""Closed loop, one caller: back-to-back ``DLTEngine.solve_batch`` calls.

Each call is one ragged family of ``lanes_per_call`` scenarios of the
Sec 3.2 no-front-end LP, solved cold: the cell's fixed pool of families
in turn, with the lanes of few sources drawn anew from the run's seed
(``bench.traffic.planning_calls``).  The caller sends the
next call when the last returns, and the window closes with the first
call that returns ``seconds`` or more after it opened, so the rate is
over whole calls: every lane and every second of the window.

Set-up builds the engine the configuration states and runs one call of
the cell's one padded shape, so the window finds the executable loaded.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from bench import check as chk
from bench import reference as ref
from bench import traffic, work

WARMUP_CALLS = 1
STATUS_OPTIMAL = 0


def _specs(raw):
    from repro.core.dlt import SystemSpec
    return [SystemSpec(G=g, R=r, A=a, J=j) for g, r, a, j in raw]


def _counters(eng) -> dict:
    st = eng.stats
    return {k: getattr(st, k) for k in (
        "lanes", "cold_iterations", "warm_iterations", "cache_misses",
        "compile_ms", "fallback_lanes")}


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def run(cell, seed: int, seconds: float, rt):
    from repro.core.dlt import DLTEngine
    cfg = cell["config_data"]
    lanes = cfg["lanes_per_call"]
    t = time.perf_counter()
    eng = DLTEngine(**cfg["engine"])
    warm_rng = traffic.rng_for(seed, 1)
    for _ in range(WARMUP_CALLS):
        eng.solve_batch(_specs(traffic.planning_family(warm_rng, cfg, lanes)),
                        frontend=False)
    setup = _counters(eng)
    rt.say("setup.warmup", seconds=time.perf_counter() - t,
           calls=WARMUP_CALLS, compiles=setup["cache_misses"],
           compile_s=setup["compile_ms"] / 1e3)

    families = traffic.planning_calls(seed, cfg, cell["traffic"])
    calls, solve_s = [], []
    gen_s = 0.0
    t0 = rt.window_start()
    while True:
        ta = time.perf_counter()
        with rt.span("generator"):
            raw, fresh = next(families)
            specs = _specs(raw)
        tb = time.perf_counter()
        gen_s += tb - ta
        with rt.span("planning_call"):
            sol = eng.solve_batch(specs, frontend=False)
        t1 = time.perf_counter()
        solve_s.append(t1 - tb)
        calls.append((raw, sol, fresh))
        if t1 - t0 >= seconds:
            break
    rt.window_end()
    elapsed = t1 - t0
    solve_ms = np.array(solve_s) * 1e3
    c = _delta(_counters(eng), setup)

    status = np.concatenate([s.status for _, s, _ in calls])
    certified = int(np.count_nonzero(status == STATUS_OPTIMAL))
    flops = nbytes = 0.0
    for raw, sol, _ in calls:
        for (g, _, a, _), it in zip(raw, sol.iterations):
            f, b = work.ipm_iteration(len(g), len(a))
            flops += f * int(it)
            nbytes += b * int(it)
    rt.say("window", seconds=elapsed, calls=len(calls),
           lanes=int(status.size), certified=certified,
           compiles_in_window=c["cache_misses"],
           oracle_fallback_lanes=c["fallback_lanes"],
           ipm_iterations=c["cold_iterations"] + c["warm_iterations"],
           call_seconds_mean=elapsed / len(calls),
           solve_ms_quartiles=np.percentile(solve_ms, [25, 50, 75]),
           solve_ms_max=solve_ms.max(), generator_seconds=gen_s,
           call_iterations_max=[int(s.iterations.max()) for _, s, _ in calls],
           ipm_flops=flops, ipm_bytes=nbytes)
    return SimpleNamespace(
        e2e={"scenarios_per_s": certified / elapsed},
        attempted=status.size, failed=status.size - certified,
        layer=c, calls=calls)


def check_lanes(calls, seed: int, lim: dict) -> list:
    """The lanes the check compares, as ``(lane, call, index)``: every
    lane drawn from the seed, then a seeded sample of the pool's lanes
    (the ``largest`` by N x M among them)."""
    lanes = [(lane, c, k, new) for c, (fam, _, fresh) in enumerate(calls)
             for k, (lane, new) in enumerate(zip(fam, fresh))]
    fresh = [(lane, c, k) for lane, c, k, new in lanes if new]
    pool = [(lane, c, k) for lane, c, k, new in lanes if not new]
    sizes = [len(lane[0]) * len(lane[2]) for lane, _, _ in pool]
    pick = chk.sample(traffic.rng_for(seed, 3), sizes, lim["sample"],
                      lim["largest"]) if pool else []
    return fresh + [pool[i] for i in pick]


def check(window, cell, seed: int) -> dict:
    """Lanes certified, and every lane drawn from the seed with a seeded
    sample of the pool's lanes against the reference."""
    pairs = []
    for (g, r, a, j), c, k in check_lanes(window.calls, seed, cell["check"]):
        sol = window.calls[c][1]
        n, m = len(g), len(a)
        lp = ref.nofrontend_lp(g, r, a, j)
        x = np.concatenate([sol.beta[k, :n, :m].ravel(),
                            sol.TS[k, :n, :m].ravel(),
                            sol.TF[k, :n, :m].ravel(), [sol.finish_time[k]]])
        pairs.append(chk.compare(lp, x, ref.solve_highs(lp)))
    out = chk.worst(pairs)
    out["uncertified_lanes"] = float(window.failed)
    return out
