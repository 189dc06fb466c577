#!/usr/bin/env python3
"""What ``cp3-mosaic``'s one cut does to the pixels: the seed's well
through the cell's unit as the cell runs it (corilla's statistics of nine
fields, every plane corrected with them) and once with corilla left out
of the description (no statistics, so the spatial chain corrects
nothing), on the devices that are there.  A z-score of nine samples tops
out at 2.67, so corrected nuclei are flat-topped and the Actin mask is
speckle; a plate's statistics over 3,456 fields would not do that.  The
objects found, the watershed's adopt steps, each step's seconds and the
host's seconds of hulls, intensity passes and stitches (sums over spans
that may be open at once) of both units are printed, so that
a later claim in the cell is not tuned to the artifact (PERF.md section
7).  On fewer devices than the configuration's the step shrinks its
mesh: counts and adopt steps are the mesh's to leave alone, host seconds
are this host's.

    chiprun -- python scripts/mosaic_uncorrected.py <seed>

Without a ``tpu`` platform it runs at the rehearsal size and exits 1."""

import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

SPANS = ("stitch", "segment", "device_wait", "solidity", "intensity",
         "morph")


def main(argv=None) -> int:
    seed = int((argv or sys.argv[1:] or ["3000000941"])[0])
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

    work = tempfile.mkdtemp(prefix="tmuncorrected_")
    out = {"seed": seed, "device": device}
    try:
        size, fields_x = sized["field_size"], config["sites_per_well_x"]
        planes, out["cells_drawn"] = mosaic.draw_well(
            seed, size, fields_x, config["fields_per_well"],
            plate.parse_range(mix["cells_per_field"]), config["channels"])
        src = os.path.join(work, "src")
        sites = mosaic.write_well(src, "A01", planes, size, fields_x)
        del planes
        for index, name in enumerate(("corrected", "uncorrected")):
            steps = [s for s in config["steps"]
                     if name == "corrected" or s != "corilla"]
            unit = submit(work, index, src, sites,
                          dict(config, steps=steps), sized["max_objects"])
            events = ledger.run_ledger(unit.root)
            (result,) = ledger.batch_results(events, "jterator")
            seconds = {span: 0.0 for span in SPANS}
            for e in events:
                if (e.get("event") == "span" and e.get("step") == "jterator"
                        and e.get("span") in seconds):
                    seconds[e["span"]] += float(e["elapsed"])
            out[name] = {"unit_s": unit.seconds, "steps": steps,
                         "step_s": ledger.step_seconds(events),
                         "jterator": result, "span_s": seconds}
    finally:
        shutil.rmtree(work, ignore_errors=True)
    harness.emit(out)
    return 0 if on_chip else 1


if __name__ == "__main__":
    sys.exit(main())
