"""serve daemon: median of the serve ledger's ``spool_pickup`` spans in the
window (reading one spec from ``incoming/``)."""

from benchmark import ledger
from benchmark import stats

UNIT = "ms"


def read(run):
    if run.kind != "serve":
        return None
    spans = ledger.span_durations(run.window_events(), "spool_pickup")
    return 1e3 * stats.percentile(spans, 50.0) if spans else None
