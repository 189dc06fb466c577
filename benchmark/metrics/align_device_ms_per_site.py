"""align step: device time of every execution of the registration program
(the XLA module the configuration names as ``align_program_module``; its
operations carry the scope ``phase_correlation``) that starts inside the
traced unit's ``align`` step span, over the unit's sites."""

from benchmark import roofline_align

UNIT = "ms/site"


def read(run):
    runs = roofline_align.executions(run)
    if not runs:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1 in runs) \
        / sum(u.sites for u in run.traced_units)
