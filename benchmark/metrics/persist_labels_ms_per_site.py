"""pipelined executor: jterator's ``write_labels`` spans (the label stacks'
PNGs), over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    return spans.ms_per_site(run, "jterator", "write_labels",
                             parent="persist")
