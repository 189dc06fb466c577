"""capacity router: ``batch_done.result.bucket_escalations`` summed, over
sites — re-launches of a field one rung up.  A count: it repeats exactly
for a seed."""

from benchmark import ledger

UNIT = "count/site"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    return ledger.escalations(run.events()) / run.sites
