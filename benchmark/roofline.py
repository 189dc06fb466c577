"""The table of peaks and the compulsory traffic of one call of the
jterator batch program — functions of shapes, kept with the benchmark."""

import os

from benchmark.harness import HERE, load_json


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s, bytes/s and HBM bytes of ``device_kind``.  A device
    that is not in ``peaks.json`` is an error, not a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (has: {sorted(table)})")
    return table[device_kind]


def compulsory_bytes(batch: int, height: int, width: int,
                     channels_read: int, label_planes: int,
                     capacity: int, features_per_object: int,
                     pixel_bytes: int = 2, label_bytes: int = 4,
                     feature_bytes: int = 4) -> int:
    """Bytes one program call cannot avoid moving to or from HBM: every
    input pixel read once, every label plane written once, every feature
    row written once.  Intermediates are the implementation's choice and
    count for nothing, so the share this gives is a lower bound's."""
    pixels = batch * height * width
    return (pixels * channels_read * pixel_bytes
            + pixels * label_planes * label_bytes
            + batch * label_planes * capacity * features_per_object
            * feature_bytes)


def roofline_share(bytes_moved: float, flops: float, seconds: float,
                   peak: dict) -> tuple:
    """``(percent of the roofline, which bound)``: the least time the chip
    could take — the larger of bytes over peak bytes/s and operations over
    peak FLOP/s — over the time it took."""
    by_bytes = bytes_moved / peak["bytes_per_s"]
    by_flops = flops / peak["flops_per_s"]
    bound = "memory" if by_bytes >= by_flops else "compute"
    return 100.0 * max(by_bytes, by_flops) / seconds, bound
