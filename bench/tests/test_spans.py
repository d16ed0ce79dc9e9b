"""The span readers, on spans recorded by ``repro.tracing``."""

import time

import pytest

from bench import spans

LAYERS = {
    "assembly_ms_per_call.plan": ["dlt.assemble"],
    "transfer_ms_per_call.plan": ["dlt.to_device", "dlt.from_device"],
    "ipm_ms_per_call.plan": ["dlt.ipm"],
    "verify_ms_per_call.plan": ["dlt.verify", "dlt.unpack"],
}


def _call(tracing, children):
    with tracing.span("dlt.solve_batch"):
        for name in children:
            with tracing.span(name):
                time.sleep(0.002)


@pytest.fixture(scope="module")
def recorded():
    """Two engine calls as the engine records them, one without verify."""
    from repro import tracing
    with tracing.recording() as rec:
        _call(tracing, ["dlt.assemble", "dlt.assemble", "dlt.to_device",
                        "dlt.ipm", "dlt.from_device", "dlt.unpack",
                        "dlt.verify", "dlt.unpack"])
        _call(tracing, ["dlt.assemble", "dlt.to_device", "dlt.ipm",
                        "dlt.from_device", "dlt.unpack"])
    return rec.spans


def _ms(spans_, names):
    return sum(s.end_ns - s.start_ns for s in spans_ if s.name in names) * 1e-6


@pytest.mark.parametrize("metric", sorted(LAYERS))
def test_reader_is_span_time_per_call(harness, recorded, metric):
    got = harness.read_metric(metric, {"spans": recorded})
    assert got == pytest.approx(_ms(recorded, LAYERS[metric]) / 2)
    assert got >= 2.0           # each layer has a 2 ms span in each call


def test_layers_cover_the_call(harness, recorded):
    layers = sum(harness.read_metric(m, {"spans": recorded}) for m in LAYERS)
    call = spans.ms_per_call(recorded, ["dlt.solve_batch"])
    assert 0.9 * call < layers < call


@pytest.mark.parametrize("metric", sorted(LAYERS))
def test_reader_without_its_spans_returns_none(harness, recorded, metric):
    assert harness.read_metric(metric, {"spans": None}) is None
    assert harness.read_metric(metric, {}) is None
    others = [s for s in recorded if s.name not in LAYERS[metric]]
    assert harness.read_metric(metric, {"spans": others}) is None


def test_no_call_recorded_returns_none(recorded):
    children = [s for s in recorded if s.name != "dlt.solve_batch"]
    assert spans.ms_per_call(children, ["dlt.ipm"]) is None


def test_per_name_gives_each_name(recorded):
    got = spans.per_name(recorded)
    assert set(got) == {s.name for s in recorded}
    assert got["dlt.verify"] == pytest.approx(_ms(recorded, ["dlt.verify"]) / 2)
