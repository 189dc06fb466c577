"""jterator step: its ``step_done.elapsed`` (launches, re-launches, persist,
collect), over sites."""

from benchmark import ledger

UNIT = "ms/site"
STEPS = ("jterator",)


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    seconds = ledger.step_seconds(run.events())
    return 1e3 * sum(seconds.get(s, 0.0) for s in STEPS) / run.sites
