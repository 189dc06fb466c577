"""capacity router: jterator's ``escalate`` spans — one per rung climbed on
the persist worker: planes re-read, re-uploaded, the program re-launched
and waited for — over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    return spans.ms_per_site(run, "jterator", "escalate", parent="persist")
