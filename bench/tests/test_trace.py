"""The trace-to-metrics reduction, on a trace recorded on the CPU.

The host spans come from a real profiler trace; the CPU has no device
plane, so one with known operations is laid over the recorded window.
The program's spans reach the trace as ``repro.tracing`` writes them.
"""

import time
from types import SimpleNamespace as NS

import pytest

from bench import trace


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x).sum())
    f(jnp.ones(8)).block_until_ready()
    d = tmp_path_factory.mktemp("trace")
    from repro import tracing
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("bench.window"), tracing.recording():
        with jax.profiler.TraceAnnotation("bench.generator"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.planning_call"):
            with tracing.span("dlt.solve_batch"):
                f(jnp.ones(8)).block_until_ready()
                with tracing.span("dlt.assemble"):
                    time.sleep(0.06)
        with jax.profiler.TraceAnnotation("bench.wait_futures"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = trace.xplane_file(str(d))
    assert path is not None
    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    spans = {ev.name: (ev.start_ns, ev.end_ns)
             for p in planes if p.name.startswith("/host")
             for line in p.lines for ev in line.events
             if ev.name.startswith(("bench.", "dlt."))}
    return planes, spans


def _device(events):
    ops = NS(name="XLA Ops", events=[NS(name=n, start_ns=a, end_ns=b)
                                     for n, a, b in events])
    return NS(name="/device:TPU:0", lines=[NS(name="Steps", events=[]), ops])


def test_reduce_busy_idle_ops_and_gaps(recorded):
    planes, spans = recorded
    w0, w1 = spans["bench.window"]
    c0, c1 = spans["bench.planning_call"]
    third = (c1 - c0) / 3
    # two overlapping ops in the call's first third, one in its last
    # third, and one op sticking out past the window's end (clipped)
    ops = [("fusion.1", c0, c0 + third), ("fusion.1", c0 + third / 2, c0 + third),
           ("while.2", c1 - third, c1), ("copy", w1 - 10, w1 + 1e9)]
    got = trace.reduce(planes + [_device(ops)])
    window = (w1 - w0) * 1e-9
    busy = (2 * third + 10) * 1e-9
    assert got["window_s"] == pytest.approx(window)
    assert got["busy_s"] == pytest.approx(busy)
    assert got["devices"] == 1
    names = dict(got["device_ops"])
    assert names["fusion.1"] == pytest.approx(1.5 * third * 1e-9)
    assert names["copy"] == pytest.approx(10e-9)
    assert [n for n, _ in got["device_ops"]][0] == "fusion.1"
    gaps = got["idle_gaps"]
    assert sum(s for _, s in gaps) == pytest.approx(window - busy)
    # three gaps, longest first, each named for the innermost host span
    # around its middle: the harness's or the program's
    want = sorted([("generator", (c0 - w0) * 1e-9),
                   ("dlt.assemble", third * 1e-9),
                   ("wait_futures", (w1 - 10 - c1) * 1e-9)],
                  key=lambda g: -g[1])
    assert [n for n, _ in gaps] == [n for n, _ in want]
    assert [s for _, s in gaps] == pytest.approx([s for _, s in want])


def test_gap_inside_a_program_span_is_named_for_it(recorded):
    planes, spans = recorded
    w0, w1 = spans["bench.window"]
    a0, a1 = spans["dlt.assemble"]
    got = trace.reduce(planes + [_device([("op", w0, a0), ("op", a1, w1)])])
    assert got["idle_gaps"] == [["dlt.assemble", pytest.approx((a1 - a0) * 1e-9)]]


def test_window_is_cut_where_trace_buffers_ran_out(recorded):
    planes, spans = recorded
    w0, w1 = spans["bench.window"]
    cut = w0 + (w1 - w0) / 4
    dev = _device([("while.1", w0, w1)])
    dev.lines.append(NS(name="XLA TraceMe", events=[
        NS(name=trace.DROPPED, start_ns=cut, end_ns=w1 + 1e9)]))
    got = trace.reduce(planes + [dev])
    assert got["cut"] is True
    assert got["window_s"] == pytest.approx((cut - w0) * 1e-9)
    assert got["busy_s"] == pytest.approx(got["window_s"])
    assert got["idle_gaps"] == []


def test_reduce_without_window_or_device_is_none(recorded):
    planes, _ = recorded
    assert trace.reduce(planes) is None           # no device plane
    assert trace.reduce([_device([("op", 0, 1)])]) is None   # no window
