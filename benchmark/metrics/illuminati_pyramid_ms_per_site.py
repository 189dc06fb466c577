"""plate steps: illuminati's ``pyramid`` (the mosaic's upload and the
downsample chain's dispatch) and ``level_fetch`` (``to_uint8`` and the
fetch of each level, where the device is waited for) spans, over sites."""

from benchmark import spans

UNIT = "ms/site"


def read(run):
    return spans.ms_per_site(run, "illuminati", ("pyramid", "level_fetch"))
