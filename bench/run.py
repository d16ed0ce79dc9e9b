#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload plan-nofe.ragged --seed 7 --seconds 30 --trace 0

Everything is found by name: the cell in ``bench/workloads/<name>.json``
(its configuration, driver, traffic and the limits of its check), the
configuration in ``bench/configs/<config>.json``, the traffic driver in
``bench/drivers/<driver>.py`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``; ``BENCHMARK.json`` says which metrics a
cell reports and how many chips it needs.

A run sets up (runtime, compile-cache loads, the cell's warm-up),
measures for ``--seconds``, then checks its answers against the plain
reference.  With ``--trace 1`` it measures a window of at most
``TRACE_SECONDS`` under the profiler, records the program's spans over
it, and reports the per-layer metrics in place of the end-to-end ones,
with the device's busy time over the part of the window the trace
holds (``bench/trace.py``).  The last line of standard output is one
JSON object; the numbers compared, each beside its limit, come last in
it and as the last lines of standard error.

Needs an accelerator: where JAX finds none, or fewer chips than the
cell asks for, it exits non-zero and prints no result.  Run-time files
(the persistent compile cache, the trace, the TPU runtime's logs) go
under ``.bench/`` in the checkout.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
STATE = ROOT / ".bench"
TRACE_SECONDS = 20

for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class Runtime:
    """What a driver needs of the harness: earlier lines, host spans,
    and the start and end of the measured window.

    A traced window also records the program's spans (``repro.tracing``),
    kept in :attr:`spans` when it ends.
    """

    def __init__(self, trace_dir=None):
        self.trace_dir = trace_dir
        self.setup_s = None
        self.spans = None
        self._stack = None
        self._recorder = None

    def say(self, tag: str, **readings) -> None:
        print(f"[{tag}] {json.dumps(readings, default=_plain)}", flush=True)

    def span(self, name: str):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def window_start(self) -> float:
        """Set-up ends here; the traced window, if any, begins."""
        self._stack = stack = contextlib.ExitStack()
        if self.trace_dir is not None:
            import jax
            from repro import tracing
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # the host spans suffice
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
            stack.callback(jax.profiler.stop_trace)
            self._recorder = stack.enter_context(tracing.recording())
            stack.enter_context(self.span("window"))
        t0 = time.perf_counter()
        self.setup_s = t0 - T_PROCESS
        return t0

    def window_end(self) -> None:
        self._stack.close()
        if self._recorder is not None:
            self.spans = self._recorder.spans


def _plain(v):
    if hasattr(v, "tolist"):
        return v.tolist()
    return str(v)


def _num(v):
    """A finite float, or None (JSON has no infinities)."""
    v = float(v)
    return v if math.isfinite(v) else None


def load_cell(name: str) -> dict:
    cell = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    cell["name"] = name
    cell["config_data"] = json.loads(
        (BENCH / "configs" / f"{cell['config']}.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == name), {})
    cell["chips"] = entry.get("chips", 1)
    cell["end_to_end"] = [m for m in bench["end_to_end"]
                          if name in m.get("workloads", [name])]
    reported = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if name in m.get("workloads", [name])
                         and m["moves"] in reported]
    return cell


def read_metric(name: str, run: dict):
    """The value of per-layer metric ``name`` from its reader, or None."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def devices_for(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator")
    if require_chip and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def run(args, require_chip: bool = True, cell: dict = None) -> dict:
    """One run of one cell; returns the result line's object.

    ``cell`` stands in for the cell's files (the tests run tiny cells
    on the CPU with ``require_chip=False``)."""
    cell = load_cell(args.workload) if cell is None else cell
    devs = devices_for(cell["chips"], require_chip)
    from repro.core.dlt import enable_compile_cache
    enable_compile_cache(STATE / "jax_cache")
    import jax.numpy as jnp
    jnp.zeros(()).block_until_ready()
    seconds = float(args.seconds)
    trace_dir = None
    if args.trace:
        seconds = min(seconds, TRACE_SECONDS)
        trace_dir = STATE / "trace" / cell["name"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    rt = Runtime(trace_dir)
    rt.say("setup.runtime", seconds=time.perf_counter() - T_PROCESS,
           kind=devs[0].device_kind, devices=len(devs))
    driver = importlib.import_module(f"bench.drivers.{cell['driver']}")
    window = driver.run(cell, args.seed, seconds, rt)

    stats = devs[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell["chips"]])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak),
              "memory_bytes_limit": int(stats.get("bytes_limit", 0))}

    checks = driver.check(window, cell, args.seed)
    limits = cell["limits"]
    correct = all(checks[k] is not None and math.isfinite(checks[k])
                  and checks[k] <= limits[k] for k in limits)

    result = {"correct": bool(correct), "attempted": int(window.attempted),
              "failed": int(window.failed)}
    if args.trace:
        from bench import spans as sp
        from bench import trace as tr
        rt.say("spans", ms_per_call=sp.per_name(rt.spans))
        reduced = tr.reduce_dir(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"layer": window.layer, "trace": reduced, "spans": rt.spans,
               "device_kind": devs[0].device_kind}
        metrics = {}
        for m in cell["per_layer"]:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["metrics"] = metrics
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            rt.say("trace", window_s=reduced["window_s"], busy_s=reduced["busy_s"],
                   cut_where_buffers_ran_out=reduced["cut"])
        result["device"] = device
        if reduced is not None:
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    else:
        values = dict(window.e2e, setup_s=rt.setup_s)
        result["metrics"] = {m["name"]: {"value": _num(values[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
        result["device"] = device
    result["checks"] = {k: {"value": _num(checks[k]), "limit": limits[k]}
                        for k in limits}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # before JAX starts: the persistent compile cache at a fixed path in
    # the checkout, which the program takes from the environment
    # (enable_compile_cache), and the TPU runtime's logs beside it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(STATE / "jax_cache")
    os.environ["TPU_LOG_DIR"] = str(STATE / "tpu_logs")
    try:
        result = run(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    line = json.dumps(result)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
