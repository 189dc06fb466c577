"""spatial layout: rounds of the connected components' outer loop (seam
join, local fixpoint, global ``psum``) a unit ran —
``batch_done.result.seam_rounds``; a count, exact for a seed."""

from benchmark import roofline_mosaic

UNIT = "count"


def read(run):
    return roofline_mosaic.counter_per_unit(run, "seam_rounds")
