"""Interior point per engine call, in ms: the program span ``dlt.ipm``
(the compiled IPM from its call until its outputs are ready) over
``dlt.solve_batch``."""

from bench import spans


def read(run):
    return spans.ms_per_call(run.get("spans"), ["dlt.ipm"])
