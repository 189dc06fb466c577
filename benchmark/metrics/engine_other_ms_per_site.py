"""entry and engine: a unit's wall-clock (``tmx create``, the description,
``tmx workflow submit``) less the sum of its steps' ``step_done.elapsed``,
over sites — what ``cli.py`` and ``workflow/engine.py`` spend around the
steps."""

from benchmark import ledger

UNIT = "ms/site"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    other = sum(u.seconds - sum(ledger.step_seconds(u.events).values())
                for u in run.units)
    return 1e3 * other / run.sites
