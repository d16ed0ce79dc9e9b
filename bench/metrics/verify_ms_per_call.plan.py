"""Verify and unpack per engine call, in ms: the program spans
``dlt.verify`` and ``dlt.unpack`` (answers out of the LP layout, cleaned
and checked against the paper's constraints) over ``dlt.solve_batch``."""

from bench import spans


def read(run):
    return spans.ms_per_call(run.get("spans"), ["dlt.verify", "dlt.unpack"])
