"""kernels: the compulsory HBM traffic of one ``measure_texture`` call (one
int32 label plane and one float32 intensity plane read once, 13 x capacity
floats written: ``roofline_measure.texture_compulsory_bytes``) over the
chip's peak bytes/s, over the module's device time in one call at the
slowest rung (the median over that rung's executions).

Memory bound by construction, and a function of shapes alone: the GLCM
contraction's 9.8 TFLOP at rung 1024 are how this implementation counts
pairs, not work the features need, so the share reads the same whatever
builds the GLCM and says how far the module is from one pass over its
inputs."""

from benchmark import roofline, roofline_measure

UNIT = "%"


def read(run):
    call_s = roofline_measure.slowest_rung_call_seconds(
        run, "measure_texture")
    if not call_s:
        return None
    moved = roofline_measure.texture_compulsory_bytes(
        run.field_size, run.field_size, run.capacity)
    share, _ = roofline.roofline_share(
        moved, 0.0, call_s, roofline.peaks(run.device["kind"]))
    return share
