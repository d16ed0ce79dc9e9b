"""Transfer per engine call, in ms: the program spans
``dlt.to_device`` (the parts to the device, waited for while spans are
recorded) and ``dlt.from_device`` (the outputs back) over
``dlt.solve_batch``."""

from bench import spans


def read(run):
    return spans.ms_per_call(run.get("spans"), ["dlt.to_device", "dlt.from_device"])
