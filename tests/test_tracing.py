"""Host spans (repro.tracing) and the engine's lane-waste counters.

Spans: a no-op with no recorder; with one, a ``solve_batch`` is one
``dlt.solve_batch`` root whose children are the engine's layers, and
threads keep their own parent chains.  Counters: ``ipm_lane_slots``
counts the lane-iterations the micro-batched executables issue, and
``lp_cells``/``lp_cell_slots`` the real and padded LP cells.
"""

import threading

import numpy as np
import pytest

from repro import tracing
from repro.core.dlt import DLTEngine, SystemSpec
from repro.core.dlt.batched import build_family_lp
from repro.core.dlt.executors import LANE_MICROBATCH, microbatch_slots
from repro.core.dlt.stacking import BatchedSystemSpec

#: every span the engine opens under its root on the cold path
ENGINE_SPANS = {"dlt.assemble", "dlt.compile", "dlt.to_device", "dlt.ipm",
                "dlt.from_device", "dlt.unpack", "dlt.verify", "dlt.oracle"}


def _family(lanes, seed=0, n_max=2, m_max=6):
    """A ragged no-front-end family: N 1..n_max x M 1..m_max."""
    rng = np.random.default_rng(seed)
    specs = []
    for k in range(lanes):
        n = 1 + k % n_max
        m = int(rng.integers(1, m_max + 1))
        specs.append(SystemSpec(G=rng.uniform(0.5, 0.7, n),
                                R=np.sort(rng.uniform(2.0, 4.0, n)),
                                A=rng.uniform(1.1, 3.0, m),
                                J=float(rng.uniform(100, 500))))
    return specs


def _engine():
    return DLTEngine(precision="fp64", bucket="none")


def _covered(spans):
    return tracing._covered((s.start_ns, s.end_ns) for s in spans)


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

def test_span_without_recorder_is_shared_null_context():
    assert not tracing.active()
    a = tracing.span("dlt.assemble")
    b = tracing.span("dlt.ipm", lanes=3)
    assert a is b
    with a as sp:
        sp.set(groups=1)            # attributes are accepted and dropped
    with tracing.recording() as rec:
        pass
    with tracing.span("after"):
        pass
    assert rec.spans == [] and rec.summary() == {}
    assert not tracing.active()


def test_summary_self_time_on_a_hand_built_tree():
    rec = tracing.Recorder()
    S = tracing.Span
    # root 0..100; children 10..40 and 30..60 overlap (union 50) and one
    # reaches past the root (clipped); a grandchild under the first child
    for s in (S(1, "child", 10, 40, 0, 0, 1, {}),
              S(2, "child", 30, 60, 0, 0, 1, {}),
              S(3, "late", 90, 130, 0, 0, 1, {}),
              S(4, "leaf", 15, 20, 1, 0, 1, {}),
              S(0, "root", 0, 100, None, 0, 1, {})):
        rec._add(s)
    out = rec.summary()
    assert out["root"]["count"] == 1
    assert out["root"]["total_s"] == pytest.approx(100e-9)
    assert out["root"]["self_s"] == pytest.approx((100 - 50 - 10) * 1e-9)
    assert out["child"]["count"] == 2
    assert out["child"]["total_s"] == pytest.approx(60e-9)
    assert out["child"]["self_s"] == pytest.approx((30 - 5 + 30) * 1e-9)
    assert out["late"]["self_s"] == pytest.approx(40e-9)
    assert out["leaf"]["self_s"] == pytest.approx(5e-9)


def test_spans_nest_per_thread_with_one_call_id_per_root():
    with tracing.recording() as rec:
        with tracing.span("outer", k=1) as sp:
            sp.set(j=2)
            with tracing.span("inner"):
                pass
        with tracing.span("second"):
            pass
    by = {s.name: s for s in rec.spans}
    assert by["outer"].parent is None and by["second"].parent is None
    assert by["inner"].parent == by["outer"].id
    assert by["inner"].call == by["outer"].call == by["outer"].id
    assert by["second"].call != by["outer"].call
    assert by["outer"].attrs == {"k": 1, "j": 2}
    assert by["outer"].start_ns <= by["inner"].start_ns
    assert by["inner"].end_ns <= by["outer"].end_ns


# ---------------------------------------------------------------------------
# spans of one solve_batch
# ---------------------------------------------------------------------------

def test_solve_batch_is_one_root_over_the_engine_layers():
    eng = _engine()
    specs = _family(LANE_MICROBATCH, n_max=3, m_max=20)
    eng.solve_batch(specs, frontend=False)      # compile outside the record
    with tracing.recording() as rec:
        eng.solve_batch(specs, frontend=False)
    spans = rec.spans
    roots = [s for s in spans if s.parent is None]
    assert len(roots) == 1
    root = roots[0]
    assert root.name == "dlt.solve_batch"
    assert root.attrs == {"lanes": LANE_MICROBATCH, "groups": 1}
    assert all(s.call == root.id for s in spans)
    kids = [s for s in spans if s.parent == root.id]
    assert len(kids) == len(spans) - 1          # the layers are flat
    names = {s.name for s in kids}
    assert names <= ENGINE_SPANS
    assert {"dlt.assemble", "dlt.to_device", "dlt.ipm", "dlt.from_device",
            "dlt.unpack", "dlt.verify"} <= names
    assert "dlt.compile" not in names           # an LRU hit compiles nothing
    for s in kids:
        assert root.start_ns <= s.start_ns <= s.end_ns <= root.end_ns
    assert _covered(kids) >= 0.9 * (root.end_ns - root.start_ns)
    summary = rec.summary()
    assert summary["dlt.solve_batch"]["count"] == 1
    assert summary["dlt.ipm"]["count"] == 1


def test_compile_span_marks_an_lru_miss_and_names_the_executable():
    eng = _engine()
    with tracing.recording() as rec:
        eng.solve_batch(_family(3, seed=1), frontend=False)
    compiles = [s for s in rec.spans if s.name == "dlt.compile"]
    assert len(compiles) == 1
    attrs = compiles[0].attrs
    assert attrs["precision"] == "fp64" and attrs["warm"] is False
    assert attrs["B"] == 4                      # 3 lanes pad to 4
    (exe,) = eng._state.compiled.values()
    head = exe.as_text().split("\n", 1)[0]
    assert head.startswith(f"HloModule jit_dlt_ipm.{attrs['kernel']}.fp64.cold")


def test_ipm_program_carries_named_scopes():
    eng = _engine()
    specs = _family(4, seed=2, n_max=3, m_max=12)
    bs = BatchedSystemSpec.from_specs(specs)
    fm = eng._formulation(False, None)
    plan = eng._kernel_plan(fm, bs, build_family_lp(bs, fm))
    assert plan.kind == "banded"
    _, lowered, _ = eng.trace_plan(plan, lower=True)
    text = lowered.as_text(debug_info=True)
    for scope in ("ipm.step", "ipm.normal", "ipm.factor", "ipm.solve"):
        assert scope in text, scope


def test_oracle_span_only_when_lanes_fall_back():
    eng = DLTEngine(precision="fp64", bucket="none", max_iter=1)
    with tracing.recording() as rec:
        sol = eng.solve_batch(_family(3, seed=7), frontend=False)
    assert sol.fallback_mask.all()              # one iteration certifies none
    (root,) = [s for s in rec.spans if s.parent is None]
    (oracle,) = [s for s in rec.spans if s.name == "dlt.oracle"]
    assert oracle.parent == root.id
    with tracing.recording() as rec:
        _engine().solve_batch(_family(3, seed=7), frontend=False)
    assert "dlt.oracle" not in rec.summary()


def test_threads_keep_separate_parent_chains():
    eng = _engine()
    specs = {"a": _family(5, seed=3), "b": _family(5, seed=4)}
    eng.solve_batch(specs["a"], frontend=False)  # compile outside the record
    start = threading.Barrier(2, timeout=60)
    errors = []

    def worker(name):
        try:
            start.wait()
            eng.solve_batch(specs[name], frontend=False)
        except Exception as e:                  # surfaced below
            errors.append(e)

    with tracing.recording() as rec:
        threads = [threading.Thread(target=worker, args=(n,)) for n in specs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    assert not errors
    assert not any(t.is_alive() for t in threads)
    spans = rec.spans
    by_id = {s.id: s for s in spans}
    roots = [s for s in spans if s.parent is None]
    assert len(roots) == 2
    assert {r.name for r in roots} == {"dlt.solve_batch"}
    assert roots[0].thread != roots[1].thread
    for s in spans:
        if s.parent is not None:
            assert by_id[s.parent].thread == s.thread
            assert by_id[s.call].thread == s.thread


# ---------------------------------------------------------------------------
# waste counters
# ---------------------------------------------------------------------------

def test_microbatch_slots_is_width_times_slowest_lane():
    assert microbatch_slots(np.array([3, 5, 4])) == 3 * 5
    iters = np.arange(2 * LANE_MICROBATCH)
    assert microbatch_slots(iters) == LANE_MICROBATCH * (
        (LANE_MICROBATCH - 1) + (2 * LANE_MICROBATCH - 1))
    assert microbatch_slots(np.array([], dtype=int)) == 0


def test_lane_slots_and_cells_match_a_hand_count():
    eng = _engine()
    lanes = LANE_MICROBATCH + 4     # pads to 32: two micro-batches
    specs = _family(lanes, seed=5, n_max=3, m_max=8)
    before = eng.stats
    sol = eng.solve_batch(specs, frontend=False)
    after = eng.stats
    it = sol.iterations
    # pad lanes repeat the last lane, so they take its iterations
    padded = np.concatenate([it, np.full(2 * LANE_MICROBATCH - lanes, it[-1])])
    slots = sum(LANE_MICROBATCH * padded[k:k + LANE_MICROBATCH].max()
                for k in range(0, padded.size, LANE_MICROBATCH))
    assert after.ipm_lane_slots - before.ipm_lane_slots == slots
    cells = sum(len(s.G) * len(s.A) for s in specs)
    n_pad = max(len(s.G) for s in specs)
    m_pad = max(len(s.A) for s in specs)
    assert after.lp_cells - before.lp_cells == cells
    assert after.lp_cell_slots - before.lp_cell_slots == lanes * n_pad * m_pad
    assert after.ipm_iterations - before.ipm_iterations == it.sum()


@pytest.mark.parametrize("warm", [False, True])
def test_lane_occupancy_is_at_most_one(warm):
    eng = DLTEngine(precision="fp64")
    spec = SystemSpec(G=[0.5, 0.6], R=[2.0, 3.0],
                      A=np.linspace(1.1, 3.0, 20), J=300.0)
    if warm:
        eng.sweep(spec, frontend=False)
    else:
        eng.solve_batch(_family(11, seed=6, n_max=3, m_max=20),
                        frontend=False)
    st = eng.stats
    assert 0 < st.ipm_iterations <= st.ipm_lane_slots
    assert 0 < st.lp_cells <= st.lp_cell_slots
