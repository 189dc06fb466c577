"""batch program: summed device time of the executions of the jterator
batch program inside the traced unit (the XLA module the configuration
names, every rung, re-launches included), over the unit's sites."""

from benchmark import xplane

UNIT = "ms/site"


def read(run):
    if run.kind != "plate" or run.trace is None or not run.traced_units:
        return None
    seconds, calls = xplane.module_seconds(
        run.trace, run.config["batch_program_module"])
    if not calls:
        return None
    return 1e3 * seconds / sum(u.sites for u in run.traced_units)
