"""From a profiler trace (``.xplane.pb``) to numbers: device busy time as
the union of the intervals in which an operation ran, the idle share, the
device time of one XLA module, the operations that took most time, and
the longest idle gaps by what the host was doing.

What a TPU v5e trace holds (looked at by hand, PR 23): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per program execution, named ``jit_<function>(<fingerprint>)`` — an
executable imported from the store keeps that name), ``XLA Ops`` (one
event per HLO operation executed, named by its HLO text) and ``Async XLA
Ops``; host threads under ``/host:CPU``, where a
``jax.profiler.TraceAnnotation`` lands on a ``python`` line.  All
timestamps are nanoseconds since the trace started, device and host on
one clock; the anchor annotation, written at a known wall-clock instant,
ties that clock to the ledgers' ``t0``."""

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Trace:
    """The events of one trace the reductions need, in seconds since the
    trace started: per device the (start, end, name) of every operation
    and of every module execution, and the anchor's start."""

    def __init__(self, ops: dict, modules: dict, anchor_s):
        self.ops = ops            # device plane name -> [(t0, t1, name)]
        self.modules = modules
        self.anchor_s = anchor_s

    @classmethod
    def from_file(cls, path: str, anchor: str = "bench_anchor"):
        from jax.profiler import ProfileData

        ops, modules, anchor_s = {}, {}, None
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith(DEVICE_PLANE):
                for line in plane.lines:
                    if line.name in (OPS_LINE, MODULES_LINE):
                        target = ops if line.name == OPS_LINE else modules
                        target[plane.name] = [
                            (e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9, e.name)
                            for e in line.events]
            elif plane.name == "/host:CPU" and anchor_s is None:
                for line in plane.lines:
                    if not line.name.startswith("python"):
                        continue
                    for e in line.events:
                        if e.name == anchor:
                            anchor_s = e.start_ns * 1e-9
                            break
        return cls(ops, modules, anchor_s)

    @property
    def devices(self) -> list:
        return sorted(self.ops)


def union(intervals: list) -> list:
    """Sorted, disjoint ``[(t0, t1)]`` covering the same instants."""
    merged: list = []
    for t0, t1 in sorted(intervals):
        if merged and t0 <= merged[-1][1]:
            if t1 > merged[-1][1]:
                merged[-1] = (merged[-1][0], t1)
        else:
            merged.append((t0, t1))
    return merged


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(t0, lo), min(t1, hi)) for t0, t1 in intervals
            if t1 > lo and t0 < hi]


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which an operation ran on the device,
    averaged over the devices the trace holds."""
    if not trace.ops:
        return 0.0
    total = 0.0
    for events in trace.ops.values():
        total += sum(t1 - t0 for t0, t1 in union(clip(
            [(t0, t1) for t0, t1, _ in events], lo, hi)))
    return total / len(trace.ops)


def idle_share(busy_s: float, window_s: float) -> float:
    """Percent of the window in which nothing ran on the device."""
    return 100.0 * (1.0 - busy_s / window_s)


def module_seconds(trace: Trace, prefix: str) -> tuple:
    """``(device seconds, executions)`` of the modules whose name starts
    with ``prefix``, summed over devices."""
    total, count = 0.0, 0
    for events in trace.modules.values():
        for t0, t1, name in events:
            if name.startswith(prefix):
                total += t1 - t0
                count += 1
    return total, count


def op_label(hlo_text: str) -> str:
    """``%fusion.2 = s32[…] fusion(…)`` -> ``fusion.2``: the operation's
    own name, which is all the trace knows of it until the program sets
    ``jax.named_scope``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")[:64]


def top_operations(trace: Trace, n: int = 10) -> list:
    """``[[name, seconds]]``: the ``n`` operations with most device time,
    ``while`` bodies counted in their children and not twice."""
    totals: dict = {}
    for events in trace.ops.values():
        for t0, t1, name in events:
            label = op_label(name)
            if label.startswith(("while", "conditional", "call")):
                continue   # a container: its children carry the time
            totals[label] = totals.get(label, 0.0) + (t1 - t0)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]


def idle_gaps(trace: Trace, lo: float, hi: float) -> list:
    """``[(t0, t1)]`` of ``[lo, hi]`` in which no device ran anything,
    longest first."""
    busy = union(clip([(t0, t1) for events in trace.ops.values()
                       for t0, t1, _ in events], lo, hi))
    gaps, cursor = [], lo
    for t0, t1 in busy:
        if t0 > cursor:
            gaps.append((cursor, t0))
        cursor = max(cursor, t1)
    if hi > cursor:
        gaps.append((cursor, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def attribute(gap: tuple, spans: list) -> str:
    """The name of the span that covers most of ``gap``; among spans that
    cover it equally the shortest, which is the innermost (a phase inside
    its step).  ``spans`` are ``(name, t0, t1)`` on the gap's clock."""
    best, best_key = "outside_spans", (0.0, 0.0)
    for name, t0, t1 in spans:
        overlap = min(gap[1], t1) - max(gap[0], t0)
        if overlap <= 0:
            continue
        key = (round(overlap, 4), -(t1 - t0))
        if key > best_key:
            best, best_key = name, key
    return best


def gap_breakdown(trace: Trace, lo: float, hi: float, spans: list,
                  n: int = 5) -> list:
    """``[[name, seconds]]`` of the ``n`` longest idle gaps."""
    return [[attribute(gap, spans), gap[1] - gap[0]]
            for gap in idle_gaps(trace, lo, hi)[:n]]
