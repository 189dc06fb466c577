"""spatial layout: jterator's ``device_wait`` spans — the host blocked on
the sharded label images after the dispatch (``segment``, which already
waits for the root table's overflow check: this is the watershed's rest) — over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    return spans.ms_per_site(run, "jterator", "device_wait")
