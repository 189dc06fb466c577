"""A multiplexed (4i) plate's wells on disk, and the ``multiplexing`` workflow
description that analyses them: every field imaged once a cycle, each later
cycle displaced by the stage's repositioning error, DAPI in every cycle
for registration and two antibody stains of its own.

A field's cells are drawn ONCE, on a canvas wider than the field by the
largest drift on every side; a cycle's field is the canvas cropped at that
cycle's own offset, under noise drawn anew.  So a drifted cycle shows new
cells at the edge it moved towards and loses those at the other: nothing
wraps around, as nothing does under a microscope.  ``plate.STAINS``'
recipe (imported, not copied) with two more rows."""

import os

import numpy as np

from benchmark import plate

PIPE = plate.PIPE

#: ``plate.STAINS`` and the two stains of the third cycle: (amplitude,
#: radius as a fraction of the cell body's drawn radius)
STAINS = {**plate.STAINS, "Golgi": (800.0, 0.5), "Nucleolin": (1000.0, 0.35)}

#: how much dimmer, at most, DAPI is in a later cycle (elution takes some)
DAPI_FADE = 0.2

#: chromatin: a nucleus's DAPI varies by this share from pixel to pixel,
#: the same in every cycle.  It is what a registration locks on to: plain
#: Gaussians hold nothing above the noise beyond 0.1 cycles a pixel, and
#: the field's own border (at shift 0 in every cycle) then outweighs a few
#: nuclei (a 64 x 64 rehearsal registered nothing; 2160 x 2160 did)
CHROMATIN = 0.1


def ref_channel_index(config: dict) -> int:
    """metaconfig numbers the channels in the order of their sorted names."""
    names = sorted({s for stains in config["cycles"] for s in stains})
    return names.index(config["ref_channel"])


def draw_canvas(rng, side: int, n_cells: int, stains) -> dict:
    """The noise-free signal of every stain on a ``side`` x ``side``
    canvas, float32: ``plate.synth_field``'s Gaussians (nuclei in DAPI,
    wider bodies in the others), each stamped into a local window."""
    planes = {c: np.zeros((side, side), np.float32) for c in stains}
    margin = max(4, side // 20)
    ys = rng.integers(margin, side - margin, n_cells)
    xs = rng.integers(margin, side - margin, n_cells)
    for y, x in zip(ys, xs):
        r_n = rng.uniform(3.5, 5.5)
        r_c = r_n * rng.uniform(2.0, 3.0)
        half = int(4 * r_c) + 1
        y0, y1 = max(0, y - half), min(side, y + half + 1)
        x0, x1 = max(0, x - half), min(side, x + half + 1)
        yy, xx = np.mgrid[y0:y1, x0:x1].astype(np.float32)
        d2 = (yy - y) ** 2 + (xx - x) ** 2
        for c in stains:
            amp, rel = STAINS[c]
            r = r_n if c == "DAPI" else r_c * (rel or 1.0)
            planes[c][y0:y1, x0:x1] += amp * np.exp(-d2 / (2 * r ** 2))
    if "DAPI" in planes:
        planes["DAPI"] *= rng.uniform(
            1.0 - CHROMATIN, 1.0 + CHROMATIN, (side, side)).astype(np.float32)
    return planes


def write_wells(src: str, wells: list, config: dict, size: int, cells,
                drift: int, seed: int) -> tuple:
    """``<well>_s<field>_c<cycle>_<stain>.tif`` (metaconfig's default
    pattern), 16-bit, for every field of ``wells``.  Returns ``(sites,
    planted)``: ``planted[cycle]`` is the ``(sites, 2)`` int32 table of
    corrections the align step has to store for that cycle, a row a site
    in the store's order (well by well, field by field) — the offset
    (dy, dx) its field was cropped at, drawn uniformly from
    ``-drift..drift`` on each axis; the first cycle's is zero."""
    import cv2

    rng = np.random.default_rng(seed)
    os.makedirs(src)
    fields, cycles = config["fields_per_well"], config["cycles"]
    stains = sorted({s for c in cycles for s in c})
    sites = len(wells) * fields
    planted = {c: np.zeros((sites, 2), np.int32) for c in range(len(cycles))}
    for site in range(sites):
        well, field = wells[site // fields], site % fields
        canvas = draw_canvas(rng, size + 2 * drift,
                             int(rng.integers(*cells)), stains)
        for cycle, imaged in enumerate(cycles):
            if cycle:
                planted[cycle][site] = rng.integers(-drift, drift + 1, 2)
            oy, ox = drift + planted[cycle][site]
            for stain in imaged:
                gain = 1.0 - (rng.uniform(0.0, DAPI_FADE)
                              if cycle and stain == "DAPI" else 0.0)
                img = (gain * canvas[stain][oy:oy + size, ox:ox + size]
                       + rng.normal(300.0, 25.0, (size, size))
                       .astype(np.float32))
                path = os.path.join(
                    src, f"{well}_s{field}_c{cycle}_{stain}.tif")
                if not cv2.imwrite(
                        path, np.clip(img, 0, 65535).astype(np.uint16)):
                    raise RuntimeError(f"could not write {path}")
    return sites, planted


def write_description(root: str, src: str, config: dict,
                      max_objects: int) -> str:
    """``workflow.yaml`` (the serialized form ``tmx workflow submit``
    reads) of the six steps and the jterator pipeline file, both from the
    configuration's file.  ``align`` among the steps makes it the
    ``multiplexing`` type.  Launch sizes, depth and strategy stay the
    engine's to resolve."""
    import yaml

    from tmlibrary_tpu.workflow.engine import WorkflowDescription

    with open(os.path.join(root, PIPE), "w") as f:
        yaml.safe_dump(config["pipeline"], f)
    step_args = {
        "metaconfig": {"source_dir": src,
                       "sites_per_well_x": config["sites_per_well_x"]},
        "imextract": {},
        "corilla": {"n_devices": config["chips"]},
        "align": {"ref_cycle": config["ref_cycle"],
                  "ref_channel": ref_channel_index(config),
                  "max_shift": config["max_shift"]},
        "illuminati": {},
        "jterator": {"pipe": PIPE, "max_objects": max_objects,
                     "n_devices": config["chips"]},
    }
    path = os.path.join(root, "workflow.yaml")
    WorkflowDescription.canonical(
        {s: step_args[s] for s in config["steps"]}).save(path)
    return path
