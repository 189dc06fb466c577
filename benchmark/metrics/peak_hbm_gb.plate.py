"""device: ``memory_stats()["peak_bytes_in_use"]`` after the window, in
GB (1e9 bytes), on the fullest device."""

UNIT = "GB"


def read(run):
    if run.kind != "plate" or not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 1e9
