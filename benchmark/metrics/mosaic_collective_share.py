"""spatial layout: percent of the segmentation's device time spent inside
``collective-permute``, ``all-reduce`` and ``all-gather`` operations (halo
rows, the seam join, the per-step ``psum``, the root table): what only
several chips show."""

from benchmark import roofline_mosaic

UNIT = "%"


def read(run):
    return roofline_mosaic.collective_share(run)
