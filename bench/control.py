#!/usr/bin/env python3
"""The control of the check: the reference put in the program's place,
computed in float32, one precision below the float64 the configurations
state.

    python3 bench/control.py --workload plan-nofe.ragged --seeds 11 12 13

For each seed it draws the cell's traffic as a run does, picks the
answers a run's check would pick, answers them with the float32 interior point of
:mod:`bench.reference`, and compares those answers with the float64
reference by the cell's own comparison.  Each number should land above
the cell's limit: a check that passed float32 answers could not tell a
precision drop from the float64 program.  The benchmark's own runs do
not run it.  It runs on the host alone, so it needs no chip.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check as chk  # noqa: E402
from bench import reference as ref  # noqa: E402
from bench import traffic  # noqa: E402
from bench.drivers.closed_batch import check_lanes  # noqa: E402

#: planning calls a window completes, whose lanes the check is drawn from
PLAN_CALLS = 10


def numbers(cell: dict, seed: int, dtype=np.float32) -> dict:
    families = traffic.planning_calls(seed, cell["config_data"], cell["traffic"])
    calls = [(fam, None, fresh) for (fam, fresh), _ in zip(families, range(PLAN_CALLS))]
    pairs = []
    for lane, _, _ in check_lanes(calls, seed, cell["check"]):
        lp = ref.nofrontend_lp(*lane)
        pairs.append(chk.compare(lp, ref.solve_ipm(lp, dtype), ref.solve_highs(lp)))
    out = chk.worst(pairs)
    out["uncertified_lanes"] = 0.0
    return out


def main(argv=None) -> int:
    sys.path.insert(0, str(ROOT / "bench"))
    from run import load_cell
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    for seed in args.seeds:
        got = numbers(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "float32", "numbers": got,
                          "limits": cell["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
