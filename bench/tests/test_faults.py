"""A run whose timed path is broken underneath must read not correct.

Each test drives the rest of a run (on the CPU, past the harness's look
for a chip) with one fault planted where the program produces its
answers: an answer altered, or half of a batch left out.  The cell
runs on one chip, so no exchange between chips can be left out, and
it trains nothing, so no state can be returned unchanged.
"""

import numpy as np
import pytest

from cells import args, tiny


def _run(harness, name):
    return harness.run(args(name), require_chip=False, cell=tiny(harness, name))


def _broken_solve_batch(monkeypatch, fault):
    from repro.core.dlt import DLTEngine
    real = DLTEngine.solve_batch

    def solve_batch(self, *a, **kw):
        sol = real(self, *a, **kw)
        fault(sol)
        return sol

    monkeypatch.setattr(DLTEngine, "solve_batch", solve_batch)


def _finish_altered(sol):
    sol.finish_time[:] *= 1 + 1e-4


def _half_left_out(sol):
    half = sol.status.size // 2
    sol.status[half:] = 1               # STATUS_MAXITER: no answer
    sol.finish_time[half:] = np.nan


@pytest.mark.parametrize("fault, number", [
    (_finish_altered, "finish_rel_err"),
    (_half_left_out, "uncertified_lanes"),
])
def test_planning_fault_reads_not_correct(harness, monkeypatch, fault, number):
    _broken_solve_batch(monkeypatch, fault)
    res = _run(harness, "plan-nofe.ragged")
    assert res["correct"] is False
    c = res["checks"][number]
    assert c["value"] is None or c["value"] > c["limit"]
