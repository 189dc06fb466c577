"""spatial layout: components in the fullest shard's root table, against
the bound ``max_objects`` gives it —
``batch_done.result.roots_max_per_shard``, the most over the window's units."""

from benchmark import roofline_mosaic

UNIT = "count"


def read(run):
    return roofline_mosaic.counter_per_unit(run, "roots_max_per_shard", max)
