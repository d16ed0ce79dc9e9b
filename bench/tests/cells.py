"""Tiny versions of the benchmark's cells, for runs on the CPU."""

import argparse

SEED = 2**31 + 12345      # above 32 signed bits: seeds may be that large


def tiny(harness, name: str) -> dict:
    cell = harness.load_cell(name)
    cell["check"] = {"sample": 6, "largest": 2}
    return cell


def args(name: str, seconds: float = 2.0, trace: int = 0, seed: int = SEED):
    return argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=trace)


CELLS = ["plan-nofe.ragged"]
