"""align step: fields whose shift the step zeroed (over ``max_shift`` or
under its quality floor), ``step_done.collected.failed_sites`` summed over
the window's units; a count, exact for a seed."""

from benchmark import roofline_align

UNIT = "count"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    said = [c for c in roofline_align.collected(run.events())
            if "failed_sites" in c]
    if not said:
        return None
    return sum(int(c["failed_sites"]) for c in said)
