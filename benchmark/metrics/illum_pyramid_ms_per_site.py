"""plate steps: ``step_done.elapsed`` of corilla (illumination statistics)
and illuminati (pyramids), over sites."""

from benchmark import ledger

UNIT = "ms/site"
STEPS = ("corilla", "illuminati")


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    seconds = ledger.step_seconds(run.events())
    return 1e3 * sum(seconds.get(s, 0.0) for s in STEPS) / run.sites
