#!/usr/bin/env python3
"""The control of the ``cp3-mosaic.x4`` cell: the seed's well through the
same unit once on the configuration's mesh (four devices, 2 x 2) and once
with ``n_devices: 1`` on one of them; the label stacks of both object
types have to be bit-identical, and every nucleus id scipy's.

    chiprun --chips 4 -- python scripts/mosaic_control.py <seed>

On a platform without a ``tpu`` it runs at the rehearsal size (give it
four host devices) and exits 1."""

import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    seed = int((argv or sys.argv[1:] or ["3000000940"])[0])
    import numpy as np

    from benchmark import harness, ledger, mosaic, plate

    harness.prepare_environment()
    harness.take_stdout()
    config = harness.load_json(harness.HERE, "configs", "cp3-mosaic.json")
    traffic = harness.load_json(harness.HERE, "traffic", "x4.json")
    device = harness.device_record()
    on_chip = device["platform"] == "tpu"
    sized = harness.at_size(config, on_chip)
    mix = harness.at_size(traffic, on_chip)
    from benchmark.drivers.mosaic import submit
    from tmlibrary_tpu.models.store import ExperimentStore

    work = tempfile.mkdtemp(prefix="tmcontrol_")
    out = {"seed": seed, "device": device}
    try:
        size, fields_x = sized["field_size"], config["sites_per_well_x"]
        planes, out["cells_drawn"] = mosaic.draw_well(
            seed, size, fields_x, config["fields_per_well"],
            plate.parse_range(mix["cells_per_field"]), config["channels"])
        src = os.path.join(work, "src")
        sites = mosaic.write_well(src, "A01", planes, size, fields_x)
        del planes
        stores = {}
        for index, chips in enumerate((config["chips"], 1)):
            t0 = time.time()
            # steps 1-4 as the cell runs them both times (corilla's
            # statistics depend on its mesh in their last bits): only the
            # jterator step is told another mesh
            unit = submit(work, index, src, sites, dict(
                config, jterator=dict(config["jterator"], n_devices=chips)),
                sized["max_objects"])
            (result,) = ledger.batch_results(
                ledger.run_ledger(unit.root), "jterator")
            out[f"unit_{chips}dev"] = {"seconds": time.time() - t0,
                                       "jterator": result}
            stores[chips] = ExperimentStore.open(Path(unit.root))
        many, one = stores[config["chips"]], stores[1]
        out["labels_bit_identical"] = {
            name: bool(np.array_equal(many.read_labels(None, name),
                                      one.read_labels(None, name)))
            for name in ("nuclei", "cells")}
        out["objects"] = int(many.read_labels(None, "nuclei").max())
    finally:
        shutil.rmtree(work, ignore_errors=True)
    ok = all(out["labels_bit_identical"].values()) and out["objects"] > 0
    harness.emit(dict(out, ok=bool(ok and on_chip)))
    return 0 if ok and on_chip else 1


if __name__ == "__main__":
    sys.exit(main())
