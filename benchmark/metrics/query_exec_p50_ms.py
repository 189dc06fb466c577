"""query execution: median of the serve ledger's ``job`` spans in the window
(store open, feature store, the tool or the cached result)."""

from benchmark import ledger
from benchmark import stats

UNIT = "ms"


def read(run):
    if run.kind != "serve":
        return None
    spans = ledger.span_durations(run.window_events(), "job")
    return 1e3 * stats.percentile(spans, 50.0) if spans else None
