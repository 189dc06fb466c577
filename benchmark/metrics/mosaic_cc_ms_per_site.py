"""spatial layout: device time of the operations traced under the scope
``mosaic_cc`` in the traced unit, the mean over the device planes, over
the unit's sites (``benchmark/roofline_mosaic.py``); the four parts sum to
``mosaic_segment_device_ms_per_site``."""

from benchmark import roofline_mosaic

UNIT = "ms/site"


def read(run):
    return roofline_mosaic.ms_per_site(run, "mosaic_cc")
