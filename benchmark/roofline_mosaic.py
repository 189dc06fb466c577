"""The spatial layout's segmentation in a trace, and the compulsory
traffic of one unit's segmentation — functions of the trace and of
shapes, kept with the benchmark (new in PR 33; ``stages.py`` and
``roofline.py`` are read, not changed).

The sharded programs trace their operations under ``jax.named_scope``s
``mosaic_smooth``, ``mosaic_otsu``, ``mosaic_cc`` (the seam join inside
it under ``mosaic_seam``) and ``mosaic_watershed``.  ``segment_seconds``
gives an operation's *self* time (``stages.self_times``: a ``while`` holds
its body's operations) to the outermost of those scopes in its ``tf_op``,
per device plane, and returns the mean over the planes: the seconds one
chip spent.  The parts sum to the whole by construction; a program built
without those scopes gives nothing."""

from benchmark import stages, xplane

SCOPES = ("mosaic_smooth", "mosaic_otsu", "mosaic_cc", "mosaic_watershed")

#: HLO operations that move data between chips (their ``-start`` /
#: ``-done`` halves included)
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather")


def segment_compulsory_bytes(height: int, width: int, planes_read: int = 2,
                             label_planes: int = 2, pixel_bytes: int = 4,
                             label_bytes: int = 4) -> int:
    """Bytes one unit's segmentation cannot avoid moving to or from HBM,
    all chips together: each stain's float32 plane read once, each object
    type's int32 label plane written once.  The smoothed plane, masks,
    halos, the sort and every adopt step's copy are the implementation's
    choice and count for nothing, so the share reads the same work
    whatever builds the labels."""
    pixels = height * width
    return pixels * (planes_read * pixel_bytes + label_planes * label_bytes)


def scope_of(tf_op: str):
    """The outermost ``mosaic_*`` scope of an instruction's op name."""
    for name in stages.scopes(tf_op or ""):
        if name in SCOPES:
            return name
    return None


def is_collective(hlo_text: str) -> bool:
    return xplane.op_label(hlo_text).startswith(COLLECTIVES)


def segment_seconds(planes: list) -> dict:
    """``{scope: seconds, ..., "collective": seconds}`` of the device
    planes (``stages.Plane``), the mean over them; {} where no operation
    carries a scope."""
    totals: dict = {}
    devices = 0
    for plane in planes:
        events = plane.lines.get(stages.OPS_LINE, [])
        if not events:
            continue
        devices += 1
        for self_ns, mid in stages.self_times(events):
            scope = scope_of(plane.stats.get(mid, {}).get("tf_op", ""))
            if scope is None:
                continue
            totals[scope] = totals.get(scope, 0.0) + self_ns * 1e-9
            if is_collective(plane.names.get(mid, "")):
                totals["collective"] = \
                    totals.get("collective", 0.0) + self_ns * 1e-9
    if not any(s in totals for s in SCOPES):
        return {}
    totals.setdefault("collective", 0.0)
    return {k: v / devices for k, v in totals.items()}


def _table(run) -> dict:
    """``segment_seconds`` of a traced plate run, read once."""
    if run.kind != "plate" or run.tracer is None or not run.traced_units:
        return {}
    table = getattr(run, "_mosaic_segment", None)
    if table is None:
        table = run._mosaic_segment = segment_seconds(
            stages.device_planes(run.tracer.file()))
    return table


def segment_device_s(run):
    """Device seconds a chip spent in the segmentation of the traced
    unit(s); None without a trace or without scopes."""
    table = _table(run)
    if not table:
        return None
    return sum(table.get(s, 0.0) for s in SCOPES)


def ms_per_site(run, scope=None):
    """Device milliseconds of the segmentation (or of one scope of it)
    over the traced units' sites."""
    whole = segment_device_s(run)
    if whole is None:
        return None
    seconds = whole if scope is None else _table(run).get(scope, 0.0)
    return 1e3 * seconds / sum(u.sites for u in run.traced_units)


def counter_per_unit(run, key: str, reduce=None):
    """``batch_done.result[key]`` of the jterator step: summed over a
    unit's batches and averaged over the window's units (exact for a
    seed: every unit is the same well), or ``reduce``d over all of them;
    None where no batch carries the key."""
    from benchmark import ledger

    if run.kind != "plate" or not run.units:
        return None
    values = [int(r[key]) for r in
              ledger.batch_results(run.events(), "jterator") if key in r]
    if not values:
        return None
    return reduce(values) if reduce else sum(values) / len(run.units)


def collective_share(run):
    """Percent of the segmentation's device time inside collectives."""
    whole = segment_device_s(run)
    if not whole:
        return None
    return 100.0 * _table(run)["collective"] / whole
