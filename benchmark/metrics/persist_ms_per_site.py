"""pipelined executor: the jterator step's ``pipeline_stats`` total of the
``persist`` phase, over sites.  Label stacks, feature tables and polygons written (on worker threads, so the
total can exceed the step's wall-clock)."""

from benchmark import ledger

UNIT = "ms/site"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    return 1e3 * ledger.phase_seconds(run.events(), "jterator")["persist"] \
        / run.sites
