#!/usr/bin/env bash
# Tier-1 verification + fast batched-engine smoke + perf-regression gate.
#
# Usage:  bash scripts/check.sh
#
# 0. static analysis: ruff (when installed) and the dltlint graph gate
#    (scripts/lint_graphs.py — every formulation x kernel x executor
#    x precision combo traced and checked against rules DL001-DL007),
# 1. the full offline test suite (works without hypothesis/scipy — the
#    property tests fall back to tests/_hyp.py, scipy cross-checks skip),
# 2. a fast batched-vs-scalar parity + throughput smoke, including a
#    mixed-size ragged no-front-end family exercising size-bucketed
#    batching, a banded-vs-structured kernel pass, a warm-vs-cold
#    Sec 6 prefix sweep, and the registered scenario families beyond
#    the paper's LPs (resource-sharing, multi-installment) on both the
#    fp64 and mixed precision legs
#    (benchmarks/batched_solve_bench.py --smoke).
#    The smoke writes a perf-trajectory JSON (scenarios/sec, warm vs
#    cold IPM iterations, compile-cache hit/miss counters) to
#    $BENCH_OUT — CI uploads it as a workflow artifact so the numbers
#    are tracked per commit.  Compiled executables persist across
#    processes in .jax_cache, or in $JAX_COMPILATION_CACHE_DIR when set
#    (CI caches it between workflow runs).
#    benchmarks/service_bench.py --smoke then drives the always-on
#    routing service (async admission queue + deadline batching + drift
#    re-solves) under a Poisson arrival load, checks window/one-shot
#    bit-identity and warm-transfer oracle parity, and merges a
#    "service" section (p50/p99/p999 admission-to-decision latency,
#    decisions/sec) into the same $BENCH_OUT JSON.
# 3. scripts/bench_compare.py diffs $BENCH_OUT against the committed
#    BENCH_baseline.json: >30% machine-normalized scenarios/sec
#    regression, any fallback-count increase, or a warm sweep slower
#    than cold fails the build.  Skip with PERF_GATE=0; rebaseline with
#    `python scripts/bench_compare.py --write-baseline` (CONTRIBUTING.md).
#
# With ENGINE_EXECUTOR=sharded every bench pass runs through the
# sharded executor (CI's multi-device job pairs it with
# XLA_FLAGS=--xla_force_host_platform_device_count=8 so the lane mesh
# has 8 virtual host devices to span).
#
# CI (.github/workflows/check.yml) runs this script on a bare profile
# (numpy+jax+pytest only), a full-extras profile (+hypothesis +scipy),
# a multi-device profile (8 virtual devices + sharded executor), and a
# minimum-supported-versions profile (oldest tested jax/numpy).

set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export BENCH_OUT="${BENCH_OUT:-BENCH_engine.json}"

if command -v ruff >/dev/null 2>&1; then
  echo "== lint: ruff =="
  ruff check .
else
  echo "ruff not installed — style lint skipped (CI's lint job runs it)"
fi

echo
echo "== lint: dltlint graph gate (DL001-DL007 over the registry) =="
python scripts/lint_graphs.py

echo
echo "== tier-1: pytest =="
python -m pytest -x -q

echo
echo "== batched engine smoke (parity + speedup + banded + warm sweep) =="
python -m benchmarks.batched_solve_bench --smoke

echo
echo "== routing service smoke (SLO latency under Poisson load) =="
python -m benchmarks.service_bench --smoke

echo
echo "perf trajectory written to ${BENCH_OUT}"

if [[ "${PERF_GATE:-1}" == "1" ]]; then
  echo
  echo "== perf-regression gate (vs BENCH_baseline.json) =="
  python scripts/bench_compare.py --current "${BENCH_OUT}"
else
  echo "perf-regression gate skipped (PERF_GATE=0)"
fi

echo "ALL CHECKS PASSED"
