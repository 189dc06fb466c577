"""pipelined executor: jterator's ``fetch`` spans — counts, label planes
and measurements copied from the device to the host — over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    return spans.ms_per_site(run, "jterator", "fetch", parent="persist")
