"""spatial layout: jterator's ``stitch`` spans — one per stain: the well's
fields read from the store, illumination-corrected and laid into one
float32 mosaic on the host — over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    return spans.ms_per_site(run, "jterator", "stitch")
