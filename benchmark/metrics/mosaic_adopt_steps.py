"""spatial layout: synchronous adopt steps of the watershed a unit ran, each
a halo exchange and a ``psum`` — ``batch_done.result.adopt_steps``; a
count, exact for a seed."""

from benchmark import roofline_mosaic

UNIT = "count"


def read(run):
    return roofline_mosaic.counter_per_unit(run, "adopt_steps")
