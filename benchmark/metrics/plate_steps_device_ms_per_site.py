"""plate steps: device time of every XLA module execution that starts
inside a ``corilla`` or ``illuminati`` step span of the traced unit (the
Welford scans, ``prep``, the pyramid chain, ``to_uint8``), over the unit's
sites."""

from benchmark import ledger

STEPS = ("corilla", "illuminati")

UNIT = "ms/site"


def read(run):
    if run.kind != "plate" or run.trace is None or not run.traced_units:
        return None
    unit, tracer = run.traced_units[0], run.tracer
    steps = [(t0, t1) for name, t0, t1 in ledger.spans(unit.events)
             if name in STEPS]
    if not steps or run.trace.anchor_s is None:
        return None
    shift = run.trace.anchor_s - tracer.anchor_wall   # wall -> trace clock
    seconds = sum(
        m1 - m0 for events in run.trace.modules.values()
        for m0, m1, _ in events
        if any(t0 + shift <= m0 < t1 + shift for t0, t1 in steps))
    return 1e3 * seconds / unit.sites
