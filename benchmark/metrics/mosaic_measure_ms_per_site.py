"""spatial layout: jterator's ``morph``, ``intensity`` and ``solidity`` spans
— area, centroid and box, the five statistics of every stain, and the
convex hull of every object of both types, measured on the host over the
whole mosaic — over sites.  ``morph`` is the spatial layout's own span: a
sites-layout ledger reads as nothing."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    if run.kind != "plate" or not run.units or not spans.select(
            run.events(), "jterator", "morph"):
        return None
    return spans.ms_per_site(run, "jterator",
                             ("morph", "intensity", "solidity"))
