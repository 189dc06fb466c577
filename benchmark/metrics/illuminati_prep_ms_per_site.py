"""plate steps: illuminati's ``prep`` spans — upload of a channel's planes,
the (re-traced) correction program and the fetch of its result — over
sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    return spans.ms_per_site(run, "illuminati", "prep")
