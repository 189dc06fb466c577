"""align step: percent of a field's pixels outside the window the step
stored (``step_done.collected.window``: what every channel is cropped by
and no object is measured in); exact for a seed."""

from benchmark import roofline_align

UNIT = "%"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    said = [c["window"] for c in roofline_align.collected(run.events())
            if c.get("window")]
    if not said:
        return None
    w, side = said[0], run.field_size
    kept = (side - w["top"] - w["bottom"]) * (side - w["left"] - w["right"])
    return 100.0 * (1.0 - kept / (side * side))
