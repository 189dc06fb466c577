"""Config 4's measure modules in a trace, and the compulsory traffic of
one ``measure_texture`` call — functions of the trace and of shapes, kept
with the benchmark (new in PR 27; ``stages.py`` and ``roofline.py`` are
read, not changed).

A pipeline module's operations carry its name as the first scope after
the jitted function's own (``jit(one_site)/vmap(measure_texture)/glcm/…``;
``stages.module_and_stage``).  ``module_call_seconds`` gives, per rung (an
XLA module name with its fingerprint), the module's device seconds in
each execution; the readers under ``metrics/`` reduce that."""

import bisect

from benchmark import stages


def texture_compulsory_bytes(height: int, width: int, capacity: int,
                             features: int = 13, label_bytes: int = 4,
                             pixel_bytes: int = 4,
                             feature_bytes: int = 4) -> int:
    """Bytes one ``measure_texture`` call cannot avoid moving to or from
    HBM: one int32 label plane and one float32 intensity plane read once,
    ``features`` floats an object slot written once.  The GLCMs, the
    one-hots and the quantized plane are the implementation's choice and
    count for nothing, so the same work is read whatever builds the
    GLCM."""
    pixels = height * width
    return (pixels * (label_bytes + pixel_bytes)
            + features * capacity * feature_bytes)


def module_call_seconds(path: str, program_prefix: str) -> dict:
    """``{pipeline module: {rung: [seconds, one per execution]}}`` of the
    executions of the XLA modules whose name starts with
    ``program_prefix``: the self time of every operation inside an
    execution, by the pipeline module in its ``tf_op``.  An execution in
    which a module ran nothing counts 0 for it; a program built without
    scope names gives every operation to the module ``""``."""
    out: dict = {}
    for plane in stages.device_planes(path):
        runs = sorted((t0, t0 + d, plane.names.get(mid, ""))
                      for t0, d, mid in plane.lines.get(
                          stages.MODULES_LINE, [])
                      if plane.names.get(mid, "").startswith(program_prefix))
        if not runs:
            continue
        starts = [t0 for t0, _, _ in runs]
        per_run = [dict() for _ in runs]
        inside = [e for e in plane.lines.get(stages.OPS_LINE, [])
                  if (i := bisect.bisect_right(starts, e[0])) and
                  e[0] < runs[i - 1][1]]
        ordered = sorted(inside, key=lambda e: (e[0], -e[1]))
        for (start, _, _), (self_ns, mid) in zip(
                ordered, stages.self_times(inside)):
            module, _ = stages.module_and_stage(
                plane.stats.get(mid, {}).get("tf_op", ""))
            slot = per_run[bisect.bisect_right(starts, start) - 1]
            slot[module] = slot.get(module, 0.0) + self_ns * 1e-9
        modules = {m for slot in per_run for m in slot}
        for (_, _, rung), slot in zip(runs, per_run):
            for module in modules:
                out.setdefault(module, {}).setdefault(rung, []).append(
                    slot.get(module, 0.0))
    return out


def _calls(run) -> dict:
    """``module_call_seconds`` of a traced plate run, read once."""
    if run.kind != "plate" or run.tracer is None or not run.traced_units:
        return {}
    table = getattr(run, "_module_calls", None)
    if table is None:
        table = run._module_calls = module_call_seconds(
            run.tracer.file(), run.config["batch_program_module"])
    return table


def module_ms_per_site(run, module: str):
    """The pipeline module's device milliseconds in the traced unit's
    batch-program executions, over the unit's sites; None without a
    trace, or where no operation carries the module's name."""
    rungs = _calls(run).get(module)
    if not rungs:
        return None
    seconds = sum(sum(calls) for calls in rungs.values())
    return 1e3 * seconds / sum(u.sites for u in run.traced_units)


def slowest_rung_call_seconds(run, module: str):
    """The module's median seconds a call at the rung where that median
    is largest (the ladder's top); None as above."""
    import statistics

    rungs = _calls(run).get(module)
    if not rungs:
        return None
    return max(statistics.median(calls) for calls in rungs.values())
