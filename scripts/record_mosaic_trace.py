#!/usr/bin/env python3
"""Record the fixtures of ``tests/benchmark/test_mosaic_cell.py`` on four
chips: one unit of the ``cp3-mosaic`` configuration at its rehearsal size
(3 x 3 fields of 64 x 64: a 192 x 192 mosaic on the 2 x 2 mesh) through
``tmx create`` + ``tmx workflow submit`` under a profiler trace, after a
warm-up unit.

    chiprun --chips 4 -- python scripts/record_mosaic_trace.py chiprun_out/mosaic

writes ``tiny_mosaic_tpu_v5e_x4.xplane.pb`` (slimmed as
``record_stage_trace.slim_trace`` slims: the four device planes' two XLA
lines, the host's ``python`` lines), ``mosaic_run_ledger.jsonl`` (the
traced unit's run ledger) and ``mosaic_unit.json``.  Start from an empty
``.cache/xla``: a cache hit serves an executable without the scope names.
"""

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

SEED = 25


def main(argv=None) -> int:
    argv = list(argv or sys.argv[1:] or ["chiprun_out/mosaic"])
    out_dir = os.path.abspath(argv[0])
    os.makedirs(out_dir, exist_ok=True)
    from benchmark import harness, ledger, mosaic, plate
    from record_stage_trace import slim_trace

    harness.prepare_environment()
    config = harness.load_json(harness.HERE, "configs", "cp3-mosaic.json")
    traffic = harness.load_json(harness.HERE, "traffic", "x4.json")
    sized = harness.at_size(config, on_chip=False)
    device = harness.device_record()
    work = tempfile.mkdtemp(prefix="tmmosaic_")
    try:
        size, fields_x = sized["field_size"], config["sites_per_well_x"]
        planes, n_cells = mosaic.draw_well(
            SEED, size, fields_x, config["fields_per_well"],
            plate.parse_range(traffic["rehearsal"]["cells_per_field"]),
            config["channels"])
        src = os.path.join(work, "src")
        sites = mosaic.write_well(src, "A01", planes, size, fields_x)
        from benchmark.drivers.mosaic import submit

        submit(work, 0, src, sites, config, sized["max_objects"])  # warm-up
        tracer = harness.TraceWindow(os.path.join(work, "trace"))
        tracer.start()
        unit = submit(work, 1, src, sites, config, sized["max_objects"])
        tracer.stop()
        if device["platform"] == "tpu":
            slim_trace(tracer.file(), os.path.join(
                out_dir, "tiny_mosaic_tpu_v5e_x4.xplane.pb"))
        shutil.copy(os.path.join(unit.root, "workflow", "ledger.jsonl"),
                    os.path.join(out_dir, "mosaic_run_ledger.jsonl"))
        results = ledger.batch_results(ledger.run_ledger(unit.root),
                                       "jterator")
        with open(os.path.join(out_dir, "mosaic_unit.json"), "w") as f:
            json.dump({"device": device, "sites": sites, "t0": unit.t0,
                       "t1": unit.t1, "anchor_wall": tracer.anchor_wall,
                       "field_size": size, "cells_drawn": n_cells,
                       "jterator_batches": results}, f, indent=1)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"out": out_dir, "device": device,
                      "bytes": {n: os.path.getsize(os.path.join(out_dir, n))
                                for n in sorted(os.listdir(out_dir))}}))
    return 0 if device["platform"] == "tpu" else 1


if __name__ == "__main__":
    sys.exit(main())
