"""ingest steps: ``step_done.elapsed`` of metaconfig and imextract (file
parsing, TIFF decode, store write), over sites."""

from benchmark import ledger

UNIT = "ms/site"
STEPS = ("metaconfig", "imextract")


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    seconds = ledger.step_seconds(run.events())
    return 1e3 * sum(seconds.get(s, 0.0) for s in STEPS) / run.sites
