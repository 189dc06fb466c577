"""ingest steps: pixels decoded over the seconds the imextract step waited
for its decode pool (``imextract``'s ``decode`` spans: ``pixels`` over
``elapsed``), in megapixels a second."""

from benchmark import spans

UNIT = "Mpix/s"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    decodes = spans.select(run.events(), "imextract", "decode")
    seconds = sum(float(e.get("elapsed", 0.0)) for e in decodes)
    if not decodes or seconds <= 0:
        return None
    return sum(int(e.get("pixels", 0)) for e in decodes) / 1e6 / seconds
