"""kernels: the compulsory HBM traffic of one unit's segmentation (two
float32 planes read, two int32 label planes written:
``roofline_mosaic.segment_compulsory_bytes``) over the chips' peak bytes/s
together, over the device seconds a chip spent in it (the chips work side
by side).  Memory bound by construction and a function of shapes alone."""

from benchmark import roofline, roofline_mosaic

UNIT = "%"


def read(run):
    seconds = roofline_mosaic.segment_device_s(run)
    if not seconds:
        return None
    side = run.config["sites_per_well_x"] * run.field_size
    peak = dict(roofline.peaks(run.device["kind"]))
    peak["bytes_per_s"] *= run.config["chips"]
    share, _ = roofline.roofline_share(
        roofline_mosaic.segment_compulsory_bytes(side, side)
        * len(run.traced_units), 0.0, seconds, peak)
    return share
