"""The align step in a trace and in the ledger, and the least one
registration can cost — functions of the trace, the ledger and of shapes,
kept with the benchmark (new in PR 36; ``roofline.py``, ``xplane.py`` and
``ledger.py`` are read, not changed).

A registration is one (reference, target) pair of fields: two uint16
planes read once, a (dy, dx) and a quality written, and three real
transforms of an H x W plane (two forward, one inverse) at the textbook
``2.5 N log2 N`` operations of a real FFT of N points.  That is a function
of shapes alone, so the share reads the same work whether an FFT, a
matmul DFT or a kernel does it."""

import math
import statistics

from benchmark import ledger, roofline


def pair_bytes(height: int, width: int, pixel_bytes: int = 2,
               written: int = 12) -> int:
    """Two planes read once; two int32 and one float32 written."""
    return 2 * height * width * pixel_bytes + written


def pair_flops(height: int, width: int, transforms: int = 3) -> float:
    n = height * width
    return transforms * 2.5 * n * math.log2(n)


def collected(events: list) -> list:
    """``step_done.collected`` of every align step in ``events``."""
    return [e.get("collected") or {} for e in events
            if e.get("event") == "step_done" and e.get("step") == "align"]


def step_spans(events: list) -> list:
    """``(t0, t1)`` of the align step spans, wall-clock seconds."""
    return [(t0, t1) for name, t0, t1 in ledger.spans(events)
            if name == "align"]


def executions(run) -> list:
    """``(t0, t1)`` on the trace's clock of every execution of the
    registration program that starts inside the traced unit's align step
    span; empty without a trace, a traced unit or such a program."""
    if run.kind != "plate" or run.trace is None or not run.traced_units \
            or run.trace.anchor_s is None:
        return []
    module = run.config.get("align_program_module")
    steps = step_spans(run.traced_units[0].events)
    if not module or not steps:
        return []
    shift = run.trace.anchor_s - run.tracer.anchor_wall   # wall -> trace
    return sorted(
        (m0, m1) for events in run.trace.modules.values()
        for m0, m1, name in events
        if name.startswith(module)
        and any(t0 + shift <= m0 < t1 + shift for t0, t1 in steps))


def pairs_per_execution(run, n_executions: int) -> list:
    """Pairs each execution registered, from the traced unit's
    ``register`` spans in time order (``pairs``); None where the ledger
    does not say so for every execution."""
    spans = sorted(
        (float(e["t0"]), int(e["pairs"]))
        for e in run.traced_units[0].events
        if e.get("event") == "span" and e.get("step") == "align"
        and e.get("span") == "register" and "pairs" in e and "t0" in e)
    if len(spans) != n_executions:
        return None
    return [pairs for _, pairs in spans]


def pair_seconds(run):
    """Median device seconds of one registration in the traced unit."""
    runs = executions(run)
    if not runs:
        return None
    pairs = pairs_per_execution(run, len(runs))
    if not pairs:
        return None
    return statistics.median((t1 - t0) / n
                             for (t0, t1), n in zip(runs, pairs))


def register_share(run):
    """``(percent of the roofline, which bound)`` of one registration."""
    seconds = pair_seconds(run)
    if not seconds:
        return None
    return roofline.roofline_share(
        pair_bytes(run.field_size, run.field_size),
        pair_flops(run.field_size, run.field_size), seconds,
        roofline.peaks(run.device["kind"]))
