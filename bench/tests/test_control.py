"""The control: the reference LP solved in float32 in the program's
place must fail the check, and the same solver in float64 pass it."""

import numpy as np
import pytest

from bench import control
from cells import CELLS, SEED, tiny


def _fails(numbers, limits):
    return [k for k in limits if not numbers[k] <= limits[k]]


@pytest.mark.parametrize("name", CELLS)
def test_float32_control_fails_and_float64_passes(harness, name, monkeypatch):
    monkeypatch.setattr(control, "PLAN_CALLS", 2)
    cell = tiny(harness, name)
    f32 = control.numbers(cell, SEED, dtype=np.float32)
    f64 = control.numbers(cell, SEED, dtype=np.float64)
    assert _fails(f32, cell["limits"]), f32
    assert not _fails(f64, cell["limits"]), f64


def test_plain_ipm_agrees_with_highs():
    from bench import reference as ref, traffic
    rng = traffic.rng_for(SEED, 9)
    G, R, A, J = rng.uniform(0.1, 1, 3), np.sort(rng.uniform(0, 2, 3)), \
        rng.uniform(0.5, 4, 12), 120.0
    lp = ref.nofrontend_lp(G, R, A, J)
    t_ref = ref.solve_highs(lp)[-1]
    assert ref.solve_ipm(lp, np.float64)[-1] == pytest.approx(t_ref, rel=1e-7)
