"""One well drawn as ONE mosaic and cut into the fields a microscope
would have written, and the workflow description that analyses it with
``--layout spatial``.  The cells are placed at mosaic coordinates
(``plate.synth_field`` at three fields' width, imported and not copied),
so some lie across a field border and some across a mesh seam: the
objects that per-site analysis splits and the spatial layout does not."""

import os

import numpy as np

from benchmark import plate


def draw_well(seed: int, size: int, fields_x: int, fields: int, cells,
              channels) -> tuple:
    """``(planes, n_cells)``: every channel's uint16 mosaic of
    ``fields // fields_x`` x ``fields_x`` fields of ``size`` pixels, with
    as many cells as ``fields`` draws from the range ``cells`` sum to."""
    if fields != fields_x * fields_x:
        raise ValueError("the mosaic generator draws square wells: "
                         f"{fields} fields, {fields_x} across")
    rng = np.random.default_rng(seed)
    n_cells = sum(int(rng.integers(*cells)) for _ in range(fields))
    return (plate.synth_field(rng, fields_x * size, n_cells, channels),
            n_cells)


def write_well(src: str, well: str, planes: dict, size: int,
               fields_x: int) -> int:
    """The mosaic's fields as ``<well>_s<field>_<channel>.tif`` — the
    names ``plate.write_plate`` uses — row-major, field ``f`` at row
    ``f // fields_x``, column ``f % fields_x``.  Returns the fields."""
    import cv2

    os.makedirs(src)
    fields_y = next(iter(planes.values())).shape[0] // size
    for field in range(fields_y * fields_x):
        y, x = divmod(field, fields_x)
        for chan, img in planes.items():
            path = os.path.join(src, f"{well}_s{field}_{chan}.tif")
            tile = np.ascontiguousarray(
                img[y * size:(y + 1) * size, x * size:(x + 1) * size])
            if not cv2.imwrite(path, tile):
                raise RuntimeError(f"could not write {path}")
    return fields_y * fields_x


def write_description(root: str, src: str, config: dict,
                      max_objects: int) -> str:
    """``workflow.yaml`` for the five steps, jterator with the spatial
    step arguments the configuration states.  corilla gets ``n_devices:
    chips`` as ``plate.write_description`` gives it."""
    from tmlibrary_tpu.workflow.engine import WorkflowDescription

    step_args = {
        "metaconfig": {"source_dir": src,
                       "sites_per_well_x": config["sites_per_well_x"]},
        "imextract": {},
        "corilla": {"n_devices": config["chips"]},
        "illuminati": {},
        "jterator": {"max_objects": max_objects,
                     "n_devices": config["chips"], **config["jterator"]},
    }
    path = os.path.join(root, "workflow.yaml")
    WorkflowDescription.canonical(
        {s: step_args[s] for s in config["steps"]}).save(path)
    return path
