"""device: 100 x (1 - union of the device's operation intervals over the
traced window), for serve cells."""

from benchmark import xplane

UNIT = "%"


def read(run):
    if run.kind != "serve" or run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.trace_window
    return xplane.idle_share(run.busy_s, hi - lo)
