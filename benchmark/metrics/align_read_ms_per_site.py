"""align step: its ``read`` spans — the reference cycle's and a later
cycle's uint16 stacks of one launch out of the store's memory maps, on
the engine thread — over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    if not spans.select(run.events(), "align", "read"):
        return None
    return spans.ms_per_site(run, "align", "read")
