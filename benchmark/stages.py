"""Device time by stage: whose an HLO instruction's time was.

``jax.profiler.ProfileData`` shows an event's name, start, duration and
its own three stats.  What says where an instruction came from sits one
level up, in the TPU plane's *event metadata* (looked at by hand on a v5e
trace, PR 25): per HLO instruction the stats ``tf_op`` — JAX's op-name
path, e.g. ``jit(one_site)/vmap(segment_primary)/label/while/body/min:``,
which is where a ``jax.named_scope`` lands — ``source``, ``hlo_category``
and ``program_id``.  Copies and iotas carry no ``tf_op``.  So this module
walks the protobuf wire format itself (no new dependency):

    XSpace.planes=1
    XPlane.name=2 .lines=3 .event_metadata=4 .stat_metadata=5
    XLine.name=2 .timestamp_ns=3 .events=4
    XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3
    XEventMetadata.id=1 .name=2 .stats=5
    XStatMetadata.id=1 .name=2
    XStat.metadata_id=1 .str_value=5 .ref_value=7

and reduces the ``XLA Ops`` events inside the executions of one XLA
module to seconds per stage.  An event's *self* time (its duration less
the events nested in it: a ``while`` holds its body's operations) goes to
the innermost stage name in its own ``tf_op``; what the module's
executions spend in no operation at all goes to ``other``.  So the stages
sum to the module's device time by construction.

    python -m benchmark.stages <trace.xplane.pb> [--module jit_one_site]

prints that table and, from the same file, the longest idle gaps of the
device split among the program's spans (``idle_gap_table``).
"""

import bisect
import re

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

#: ``jax.named_scope`` name (``tmlibrary_tpu/ops/*``) -> the stage whose
#: metric ``stage_<stage>_ms_per_site`` reports it.  A name that is not
#: here — a module's own (``segment_primary``), ``preprocess``,
#: ``filter_area``, no name at all — is ``other``.
STAGE_OF = {
    "smooth": "smooth",
    "otsu": "threshold",
    "threshold_adaptive": "threshold",
    "fill_holes": "fill",
    "label": "label",
    "watershed": "watershed",
    "measure_intensity": "measure",
    "morphology": "measure",
    "glcm": "measure",
    "zernike": "measure",
}
STAGES = ("smooth", "threshold", "fill", "label", "watershed", "measure",
          "other")

_WRAPPED = re.compile(r"^[a-z_]+\((.*)\)$")


# ---------------------------------------------------------------- wire walk
def _varint(buf: bytes, pos: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf: bytes):
    """``(field number, wire type, value)`` of every field of one message:
    an int for a varint, bytes for a length-delimited or fixed field."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield number, wire, value


def _first(buf: bytes, number: int, default=None):
    for n, _, value in fields(buf):
        if n == number:
            return value
    return default


def _map_entry(buf: bytes) -> tuple:
    """A protobuf map entry: key=1, value=2."""
    key, value = 0, b""
    for n, _, v in fields(buf):
        if n == 1:
            key = v
        elif n == 2:
            value = v
    return key, value


class Plane:
    """One plane — a device's, or ``/host:CPU`` — : per line it keeps
    (a device's two XLA lines, the host's ``python`` lines, where a
    ``TraceAnnotation`` lands) the events ``(start ns, duration ns,
    metadata id)``, and per metadata id the event's name and its string
    stats (an instruction's ``tf_op``, ``source``, ``hlo_category``, …)."""

    def __init__(self, buf: bytes):
        self.name = ""
        self.lines: dict = {}
        self.names: dict = {}
        self.stats: dict = {}
        stat_names, raw_meta, raw_lines = {}, [], []
        for n, _, v in fields(buf):
            if n == 2:
                self.name = v.decode()
            elif n == 3:
                raw_lines.append(v)
            elif n == 4:
                raw_meta.append(_map_entry(v)[1])
            elif n == 5:
                entry = _map_entry(v)[1]
                stat_names[_first(entry, 1, 0)] = \
                    (_first(entry, 2, b"") or b"").decode()
        device = self.name.startswith(DEVICE_PLANE)
        if not device and self.name != HOST_PLANE:
            return
        for meta in raw_meta:
            mid, stats = _first(meta, 1, 0), {}
            for n, _, v in fields(meta):
                if n == 2:
                    self.names[mid] = v.decode()
                elif n == 5:
                    key = stat_names.get(_first(v, 1, 0), "")
                    text = _first(v, 5)
                    if text is None and _first(v, 7) is not None:
                        text = stat_names.get(_first(v, 7), "").encode()
                    if text is not None:
                        stats[key] = text.decode(errors="replace")
            self.stats[mid] = stats
        for line in raw_lines:
            name, t_line, events = "", 0, []
            for n, _, v in fields(line):
                if n == 2:
                    name = v.decode()
                elif n == 3:
                    t_line = v
                elif n == 4:
                    events.append(v)
            if not (name in (OPS_LINE, MODULES_LINE) if device
                    else name.startswith("python")):
                continue
            out = []
            for event in events:
                mid = offset_ps = duration_ps = 0
                for n, _, v in fields(event):
                    if n == 1:
                        mid = v
                    elif n == 2:
                        offset_ps = v
                    elif n == 3:
                        duration_ps = v
                out.append((t_line + offset_ps / 1e3, duration_ps / 1e3, mid))
            # host threads can share a line name: keep every one
            self.lines[name if name not in self.lines
                       else f"{name}#{len(self.lines)}"] = out


def planes(path: str) -> list:
    with open(path, "rb") as f:
        buf = f.read()
    return [Plane(v) for n, _, v in fields(buf) if n == 1]


def device_planes(path: str) -> list:
    return [p for p in planes(path) if p.name.startswith(DEVICE_PLANE)]


# ------------------------------------------------------------ op -> stage
def scopes(tf_op: str) -> list:
    """``jit(one_site)/vmap(segment_primary)/label/while/body/min:`` ->
    ``['one_site', 'segment_primary', 'label', 'while', 'body', 'min']``:
    the path's components with the transformations' wrappers taken off."""
    out = []
    for part in tf_op.rstrip(":").split("/"):
        match = _WRAPPED.match(part)
        while match:
            part = match.group(1)
            match = _WRAPPED.match(part)
        if part:
            out.append(part)
    return out


def module_and_stage(tf_op: str) -> tuple:
    """``(pipeline module, stage)`` of an instruction: the first scope
    after the jitted function's own, and the innermost scope that
    ``STAGE_OF`` knows (``other`` where none is)."""
    path = scopes(tf_op or "")
    module = path[1] if len(path) > 2 else ""
    for name in reversed(path[1:]):
        if name in STAGE_OF:
            return module, STAGE_OF[name]
    return module, "other"


# -------------------------------------------------------------- reduction
def self_times(events: list) -> list:
    """``(self ns, metadata id)`` per event of one line: the duration less
    the events nested inside it."""
    out, stack = [], []   # stack of [end, index into out]
    for start, duration, mid in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][0] -= duration
        out.append([duration, mid])
        stack.append((start + duration, len(out) - 1))
    return out


def stage_table(path: str, module_prefix: str) -> dict:
    """Of the executions of the XLA modules whose name starts with
    ``module_prefix``, summed over devices: ``module_s`` and
    ``executions``, ``stages`` (seconds per stage; they sum to
    ``module_s``), ``by_module`` (seconds per (pipeline module, stage)),
    and ``named``: whether any instruction carried a known stage name at
    all (a program built before the scopes existed carries none)."""
    stages = dict.fromkeys(STAGES, 0.0)
    by_module: dict = {}
    module_ns, executions, named = 0.0, 0, False
    for plane in device_planes(path):
        runs = sorted((t0, t0 + d) for t0, d, mid in plane.lines.get(
            MODULES_LINE, []) if plane.names.get(mid, "").startswith(
                module_prefix))
        if not runs:
            continue
        executions += len(runs)
        module_ns += sum(t1 - t0 for t0, t1 in runs)
        starts = [t0 for t0, _ in runs]
        # an operation belongs to the execution that last started before it
        inside = [e for e in plane.lines.get(OPS_LINE, [])
                  if (i := bisect.bisect_right(starts, e[0])) and
                  e[0] < runs[i - 1][1]]
        for self_ns, mid in self_times(inside):
            module, stage = module_and_stage(
                plane.stats.get(mid, {}).get("tf_op", ""))
            named |= stage != "other"
            stages[stage] += self_ns * 1e-9
            key = (module, stage)
            by_module[key] = by_module.get(key, 0.0) + self_ns * 1e-9
    # what the executions spent in no operation at all is ``other``
    stages["other"] = module_ns * 1e-9 - sum(
        v for k, v in stages.items() if k != "other")
    return {"module_s": module_ns * 1e-9, "executions": executions,
            "stages": stages, "by_module": by_module, "named": named}


def idle_gap_table(path: str, n: int = 5) -> list:
    """The ``n`` longest stretches in which no device ran an operation,
    between the trace's first and last one, each with what the host was
    in: ``[{"seconds", "start_s", "inside": [[annotation, seconds], …]}]``.
    The program's spans are ``TraceAnnotation``s named ``<step>/<span>``,
    on the device's clock; a stretch is split among the *innermost*
    annotations that overlap it (an annotation's share is its overlap less
    its children's), per host thread, so a gap that crosses several spans
    shows every one of them."""
    from benchmark import xplane

    every = planes(path)
    busy = xplane.union([(t0, t0 + d) for p in every
                         if p.name.startswith(DEVICE_PLANE)
                         for t0, d, _ in p.lines.get(OPS_LINE, [])])
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(busy, busy[1:])), reverse=True)[:n]
    out = []
    for seconds, lo, hi in gaps:
        inside: dict = {}
        for plane in every:
            if plane.name != HOST_PLANE:
                continue
            for events in plane.lines.values():
                clipped = [(max(t0, lo), min(t0 + d, hi) - max(t0, lo), mid)
                           for t0, d, mid in events
                           if t0 < hi and t0 + d > lo]
                for self_ns, mid in self_times(clipped):
                    name = plane.names.get(mid, "")
                    if self_ns > 0 and name != "bench_anchor":
                        inside[name] = inside.get(name, 0.0) + self_ns * 1e-9
        out.append({"seconds": seconds * 1e-9, "start_s": lo * 1e-9,
                    "inside": sorted(([k, v] for k, v in inside.items()),
                                     key=lambda kv: -kv[1])})
    return out


def stage_ms_per_site(run, stage: str):
    """What ``metrics/stage_<stage>_ms_per_site.py`` reports: the stage's
    device milliseconds in the traced unit's batch-program executions,
    over the unit's sites.  None without a trace, without an execution,
    or for a program that carries no stage names."""
    if run.kind != "plate" or run.tracer is None or not run.traced_units:
        return None
    table = getattr(run, "_stage_table", None)
    if table is None:
        table = run._stage_table = stage_table(
            run.tracer.file(), run.config["batch_program_module"])
    if not table["executions"] or not table["named"]:
        return None
    return 1e3 * table["stages"][stage] \
        / sum(u.sites for u in run.traced_units)


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("trace")
    parser.add_argument("--module", default="jit_one_site")
    parser.add_argument("--gaps", type=int, default=5,
                        help="how many of the longest idle gaps to split "
                             "among the host's spans")
    args = parser.parse_args(argv)
    table = stage_table(args.trace, args.module)
    total = table["module_s"] or 1.0
    print(f"{args.module}: {table['executions']} executions, "
          f"{table['module_s']:.6f} s on the device")
    for stage in STAGES:
        seconds = table["stages"][stage]
        print(f"  {stage:<10} {seconds:10.6f} s  {100 * seconds / total:5.1f} %")
    print("by pipeline module and stage:")
    for (module, stage), seconds in sorted(table["by_module"].items(),
                                           key=lambda kv: -kv[1]):
        print(f"  {module or '-':<20} {stage:<10} {seconds:10.6f} s")
    print(f"the {args.gaps} longest idle gaps, by the innermost span the "
          "host was in:")
    for gap in idle_gap_table(args.trace, args.gaps):
        parts = ", ".join(f"{name} {seconds:.3f}"
                          for name, seconds in gap["inside"][:6])
        print(f"  {gap['seconds']:8.4f} s at {gap['start_s']:9.4f}  {parts}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
