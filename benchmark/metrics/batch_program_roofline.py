"""kernels: the compulsory HBM traffic of one call of the batch program
(inputs read once, label planes and feature rows written once) over the
chip's peak bytes/s, over that call's device time at the routed rung.

Memory-bound by construction: the operations of the XLA twins are integer
compares and selects, which the chip's FLOP/s peak does not describe, so
only the bytes bound is taken.  The routed rung is the module fingerprint
whose executions are slowest (the ladder's top: capacity adds one-hot
work); the call's time is that fingerprint's median."""

import statistics

from benchmark import roofline

UNIT = "%"


def read(run):
    if run.kind != "plate" or run.trace is None:
        return None
    by_rung: dict = {}
    for events in run.trace.modules.values():
        for t0, t1, name in events:
            if name.startswith(run.config["batch_program_module"]):
                by_rung.setdefault(name, []).append(t1 - t0)
    if not by_rung:
        return None
    call_s = max(statistics.median(d) for d in by_rung.values())
    config = run.config
    objects = config["pipeline"]["output"]["objects"]
    moved = roofline.compulsory_bytes(
        batch=1, height=run.field_size, width=run.field_size,
        channels_read=len(config["channels_read_by_pipeline"]),
        label_planes=len(objects), capacity=run.capacity,
        features_per_object=config["features_per_object"])
    share, _ = roofline.roofline_share(
        moved, 0.0, call_s, roofline.peaks(run.device["kind"]))
    return share
