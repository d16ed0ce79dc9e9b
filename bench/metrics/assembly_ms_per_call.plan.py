"""Host assembly per engine call, in ms: the program spans
``dlt.assemble`` (stacking the specs, grouping lanes, building the
family LP and its banded parts, padding) over ``dlt.solve_batch``."""

from bench import spans


def read(run):
    return spans.ms_per_call(run.get("spans"), ["dlt.assemble"])
