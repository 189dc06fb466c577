"""batch program: device time of Otsu's histogram, argmax and compare (scope
``otsu``) inside the executions of the batch program in the traced unit,
over the unit's sites.  By the innermost ``jax.named_scope`` in each
instruction's ``tf_op`` (``benchmark/stages.py``); the seven ``stage_*``
sum to ``program_ms_per_site``."""

from benchmark import stages

UNIT = "ms/site"


def read(run):
    return stages.stage_ms_per_site(run, "threshold")
