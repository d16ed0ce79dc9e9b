"""``chip_smoke.py`` rehearsed on the CPU at small sizes.

The script itself refuses to run off a TPU; its phases are plain
functions, so the checks they make (statuses, fallback counters, oracle
parity, window bit-identity, sharded == local) run here at sizes that
compile in seconds.
"""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402


def test_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_planning_phase_small():
    chip_smoke.run_phases([
        chip_smoke.planning_phase(1, policy, count=24, n_max=2, m_lo=4,
                                  m_hi=8)
        for policy in chip_smoke.POLICIES])


def test_router_phase_small():
    chip_smoke.run_phases([chip_smoke.router_phase(
        seed=1, frontends=2, replicas=8, bursts=24)])


def test_sharded_phase_small():
    chip_smoke.run_phases([chip_smoke.sharded_phase(
        seed=1, count=24, n_max=2, m_lo=4, m_hi=8)])


@pytest.mark.parametrize("handovers,ok", [(0, True), (4, True), (5, False),
                                          (288, False)])
def test_handover_bound(handovers, ok):
    """At most MAX_HANDOVER_SHARE of the lanes solved may leave the fp32
    phase early: 4 of 288 pass, 5 and every lane do not."""
    eng = SimpleNamespace(stats=SimpleNamespace(
        fallback_lanes=0, kernel_fallbacks=0, precision_fallback_lanes=0,
        phase1_handover_lanes=handovers, lanes=288))
    if ok:
        chip_smoke._require_no_fallbacks(eng, "A mixed")
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match="fp32 phase"):
            chip_smoke._require_no_fallbacks(eng, "A mixed")
