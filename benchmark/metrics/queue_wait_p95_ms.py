"""serve daemon: 95th percentile of the serve ledger's ``queue_wait`` spans
in the window (enqueue to admission)."""

from benchmark import ledger
from benchmark import stats

UNIT = "ms"


def read(run):
    if run.kind != "serve":
        return None
    spans = ledger.span_durations(run.window_events(), "queue_wait")
    return 1e3 * stats.percentile(spans, 95.0) if spans else None
