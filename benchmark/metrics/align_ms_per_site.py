"""align step: its ``step_done.elapsed`` (every later cycle's fields read,
registered on the first cycle's, the shift tables and the window written),
over sites."""

from benchmark import ledger

UNIT = "ms/site"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    seconds = ledger.step_seconds(run.events())
    if "align" not in seconds:
        return None
    return 1e3 * seconds["align"] / run.sites
