"""Reductions over the spans the program records *inside* its steps
(``telemetry.span`` since PR 25): ledger ``span`` events with ``step``,
``parent``, ``t0``, ``elapsed`` and numeric attributes.  The readers under
``metrics/`` stay a few lines each.  A ledger written by a program that
has no such spans (every span event lacks ``parent``) reads as None, not
as zero."""

from benchmark import xplane

#: JAX's compile path as spans: tracing, lowering, the backend compile
#: (a persistent-cache read sits inside it), the cache read alone
COMPILE_SPANS = ("jit_trace", "jit_lower", "jit_compile", "cache_load")


def inner_spans_recorded(events: list) -> bool:
    return any(e.get("event") == "span" and "parent" in e for e in events)


def select(events: list, step: str, names, parent=None) -> list:
    """The span events of ``step`` named one of ``names`` (and under
    ``parent``, where given)."""
    names = (names,) if isinstance(names, str) else names
    return [e for e in events
            if e.get("event") == "span" and e.get("step") == step
            and e.get("span") in names
            and (parent is None or e.get("parent") == parent)]


def ms_per_site(run, step: str, names, parent=None):
    """Summed ``elapsed`` of the window's units' spans, over sites."""
    if run.kind != "plate" or not run.units:
        return None
    events = run.events()
    if not inner_spans_recorded(events):
        return None
    seconds = sum(float(e.get("elapsed", 0.0))
                  for e in select(events, step, names, parent))
    return 1e3 * seconds / run.sites


def union_seconds(events: list, names) -> float:
    """Seconds covered by at least one span named in ``names``: nested or
    concurrent ones (a cache read inside its compile, a function traced
    inside its caller) are counted once."""
    return sum(t1 - t0 for t0, t1 in xplane.union(
        [(float(e["t0"]), float(e["t0"]) + float(e.get("elapsed", 0.0)))
         for e in events
         if e.get("event") == "span" and e.get("span") in names
         and "t0" in e]))
