"""pipelined executor: feature values written, ``rows`` x ``columns`` of
jterator's ``write_features`` spans (one per object type and batch: object
rows, feature columns without the seven site and label keys), summed over
the window's units, over sites.  A count, exact for a seed.  A program
whose spans carry no such attributes reads as nothing."""

from benchmark import spans

UNIT = "values/site"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    written = [e for e in spans.select(run.events(), "jterator",
                                       "write_features", "persist")
               if "rows" in e and "columns" in e]
    if not written:
        return None
    return sum(int(e["rows"]) * int(e["columns"]) for e in written) \
        / run.sites
