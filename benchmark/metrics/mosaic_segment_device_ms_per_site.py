"""spatial layout: device time a chip spent in the sharded segmentation of
the traced unit — every operation under a ``mosaic_*`` scope, self times,
the mean over the device planes — over the unit's sites."""

from benchmark import roofline_mosaic

UNIT = "ms/site"


def read(run):
    return roofline_mosaic.ms_per_site(run)
