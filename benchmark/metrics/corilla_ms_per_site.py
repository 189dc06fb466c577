"""plate steps: the ``corilla`` step span (illumination statistics: five
channels read, scanned on the device, finalized, written), over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    steps = spans.select(run.events(), "corilla", "step")
    if not steps:
        return None
    return 1e3 * sum(float(e["elapsed"]) for e in steps) / run.sites
