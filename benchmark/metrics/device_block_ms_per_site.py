"""pipelined executor: the jterator step's ``pipeline_stats`` total of the
``device_block`` phase, over sites.  The host waiting for the device's result."""

from benchmark import ledger

UNIT = "ms/site"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    return 1e3 * ledger.phase_seconds(run.events(), "jterator")["device_block"] \
        / run.sites
