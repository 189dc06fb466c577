"""caches: seconds of the window's units covered by a ``jit_trace``,
``jit_lower``, ``jit_compile`` or ``cache_load`` span of any step (nested
ones counted once), over sites.  Compile-path work the warm-up did not
remove: a program re-traced, an executable re-read from JAX's cache."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    if not spans.inner_spans_recorded(run.events()):
        return None
    seconds = sum(spans.union_seconds(u.events, spans.COMPILE_SPANS)
                  for u in run.units)
    return 1e3 * seconds / run.sites
