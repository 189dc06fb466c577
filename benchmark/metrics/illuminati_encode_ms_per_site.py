"""plate steps: illuminati's ``encode`` spans — tiles cut and PNG-encoded
on the thread pool — over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    return spans.ms_per_site(run, "illuminati", "encode")
