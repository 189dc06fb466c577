"""Seeded synthetic Cell Painting fields, the plate on disk, and the
workflow description the engine reads.  Copied from ``chip_smoke.py``
(``synth_field`` / ``write_plate`` / ``write_description``, proven on the
chip in PR 21) and widened to the channels a configuration lists; it is
the benchmark's own, so no later PR can move the inputs."""

import os

import numpy as np

PIPE = "pipeline.pipe.yaml"

#: (amplitude, radius as a multiple of the nucleus radius) of the Gaussian
#: each cell leaves in a channel.  DAPI and Actin are the smoke's recipe;
#: the other three stains are cell-body stains of differing extent.
STAINS = {
    "DAPI": (4000.0, 1.0),
    "Actin": (1500.0, None),      # the cell body's own drawn radius
    "Tubulin": (1200.0, None),
    "ER": (900.0, 0.8),           # fraction of the body radius
    "Mito": (700.0, 0.6),
}


def parse_range(text: str) -> tuple[int, int]:
    """``"350-650"`` -> (350, 650), the half-open range ``rng.integers``
    takes."""
    lo, hi = text.split("-")
    return int(lo), int(hi)


def synth_field(rng, size: int, n_cells: int, channels) -> dict:
    """One seeded field, uint16, every channel of ``channels``: a noise
    floor, Gaussian nuclei in DAPI and wider Gaussian bodies in the other
    stains, each cell stamped into a local window so a 2160x2160 field
    with hundreds of cells takes milliseconds."""
    planes = {c: rng.normal(300.0, 25.0, (size, size)).astype(np.float32)
              for c in channels}
    margin = max(4, size // 20)
    ys = rng.integers(margin, size - margin, n_cells)
    xs = rng.integers(margin, size - margin, n_cells)
    for y, x in zip(ys, xs):
        r_n = rng.uniform(3.5, 5.5)
        r_c = r_n * rng.uniform(2.0, 3.0)
        half = int(4 * r_c) + 1
        y0, y1 = max(0, y - half), min(size, y + half + 1)
        x0, x1 = max(0, x - half), min(size, x + half + 1)
        yy, xx = np.mgrid[y0:y1, x0:x1].astype(np.float32)
        d2 = (yy - y) ** 2 + (xx - x) ** 2
        for c in channels:
            amp, rel = STAINS[c]
            r = r_n if c == "DAPI" else r_c * (rel or 1.0)
            planes[c][y0:y1, x0:x1] += amp * np.exp(-d2 / (2 * r ** 2))
    return {c: np.clip(p, 0, 65535).astype(np.uint16)
            for c, p in planes.items()}


def write_plate(src: str, wells, fields: int, size: int, cells, channels,
                seed: int) -> int:
    """``<well>_s<field>_<channel>.tif`` files, 16-bit, so metaconfig's
    default handler parses them and imextract's native TIFF decoder runs.
    Returns the number of sites."""
    import cv2

    rng = np.random.default_rng(seed)
    os.makedirs(src)
    for well in wells:
        for field in range(fields):
            planes = synth_field(rng, size, int(rng.integers(*cells)),
                                 channels)
            for chan, img in planes.items():
                path = os.path.join(src, f"{well}_s{field}_{chan}.tif")
                if not cv2.imwrite(path, img):
                    raise RuntimeError(f"could not write {path}")
    return len(wells) * fields


def well_names(n: int) -> list:
    """The first ``n`` wells of a 384-well plate, row-major (A01 … P24)."""
    return [f"{chr(ord('A') + i // 24)}{i % 24 + 1:02d}" for i in range(n)]


def write_description(root: str, src: str, config: dict,
                      max_objects: int) -> str:
    """``workflow.yaml`` (the serialized form ``tmx workflow submit``
    reads) and the jterator pipeline file, both from the configuration's
    file.  Batch size, depth and strategy stay the engine's to resolve."""
    import yaml

    from tmlibrary_tpu.workflow.engine import WorkflowDescription

    with open(os.path.join(root, PIPE), "w") as f:
        yaml.safe_dump(config["pipeline"], f)
    step_args = {
        "metaconfig": {"source_dir": src,
                       "sites_per_well_x": config["sites_per_well_x"]},
        "imextract": {},
        "corilla": {"n_devices": config["chips"]},
        "illuminati": {},
        "jterator": {"pipe": PIPE, "max_objects": max_objects,
                     "n_devices": config["chips"]},
    }
    path = os.path.join(root, "workflow.yaml")
    WorkflowDescription.canonical(
        {s: step_args[s] for s in config["steps"]}).save(path)
    return path
