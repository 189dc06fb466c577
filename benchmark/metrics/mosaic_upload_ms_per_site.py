"""spatial layout: jterator's ``upload`` spans — every mosaic plane the
segmentation reads handed from host memory to its shards — over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    return spans.ms_per_site(run, "jterator", "upload")
