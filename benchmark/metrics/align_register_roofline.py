"""kernels: the least one registration can take — the larger of its
compulsory bytes (two uint16 planes read once, 12 bytes written) over the
chip's peak bytes/s and its three real H x W transforms at 2.5 N log2 N
operations over the chip's peak FLOP/s (``roofline_align``) — over the
median device time of a pair in the traced unit.  A function of shapes,
so it reads the same work whatever computes the transforms."""

from benchmark import roofline_align

UNIT = "%"


def read(run):
    share = roofline_align.register_share(run)
    return None if share is None else share[0]
