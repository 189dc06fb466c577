"""pipelined executor: jterator's ``write_features`` spans (feature table
built and its Parquet shard written), over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    return spans.ms_per_site(run, "jterator", "write_features",
                             parent="persist")
