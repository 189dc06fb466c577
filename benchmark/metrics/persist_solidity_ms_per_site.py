"""pipelined executor: jterator's ``solidity`` spans under ``persist`` (the
convex hull of every object, on the host, on the one persist worker: once
per object type that measured morphology), over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    if not spans.select(run.events(), "jterator", "solidity", "persist"):
        return None
    return spans.ms_per_site(run, "jterator", "solidity", parent="persist")
