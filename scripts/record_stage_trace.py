#!/usr/bin/env python3
"""Record the fixtures of ``tests/benchmark/test_inside_spans.py`` on the
chip: one 64x64 unit of the ``cp3-plate`` configuration (config 3, five
channels on disk) through ``tmx create`` + ``tmx workflow submit`` under a
profiler trace, after a warm-up unit.

    chiprun -- python scripts/record_stage_trace.py chiprun_out/stages [config]

writes ``tiny_stages_tpu_v5e.xplane.pb`` (the device plane's ``XLA Ops``
and ``XLA Modules`` lines and the host's ``python`` lines, which hold the
anchor annotation and the program's spans; every other plane, line and
statistic dropped and the instructions' HLO text cut short, so it stays
under 200 KB), ``stages_run_ledger.jsonl`` (the
traced unit's run ledger) and ``stages_unit.json`` (the unit's and the
anchor's wall-clock times, the device).  The fields are a jittered grid
of twelve small nuclei, so rung 8 of the capacity ladder saturates and the
ledger holds ``escalate`` spans.  Start from an empty ``.cache/xla``:
JAX's cache key leaves metadata out, so a hit would serve an executable
without the stage names.
"""

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

SIZE, CAPACITY, FIELDS = 64, 16, 9


def grid_field(rng, size: int, channels) -> dict:
    """One uint16 field per channel: a 4x3 jittered grid of nuclei (sigma
    1.6 px) in DAPI, wider bodies (sigma 3.2 px) in the other stains."""
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    planes = {c: rng.normal(300.0, 20.0, (size, size)).astype(np.float32)
              for c in channels}
    for gy in range(4):
        for gx in range(3):
            y = 8 + gy * 16 + rng.uniform(-2, 2)
            x = 11 + gx * 21 + rng.uniform(-2, 2)
            d2 = (yy - y) ** 2 + (xx - x) ** 2
            for c in channels:
                amp, sigma = (4000.0, 1.6) if c == "DAPI" else (1500.0, 3.2)
                planes[c] += amp * np.exp(-d2 / (2 * sigma ** 2))
    return {c: np.clip(p, 0, 65535).astype(np.uint16)
            for c, p in planes.items()}


def write_grid_plate(src: str, fields: int, size: int, channels,
                     seed: int) -> int:
    """``A01_s<field>_<channel>.tif`` files metaconfig's default handler
    parses; returns the number of sites."""
    import cv2

    rng = np.random.default_rng(seed)
    os.makedirs(src)
    for field in range(fields):
        for chan, img in grid_field(rng, size, channels).items():
            if not cv2.imwrite(os.path.join(
                    src, f"A01_s{field}_{chan}.tif"), img):
                raise RuntimeError(f"could not write field {field} {chan}")
    return fields


#: of an instruction's metadata the readers use its name (to 96 bytes:
#: the HLO text runs to kilobytes) and these stats
KEPT_STATS = ("tf_op", "hlo_category")


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, wire: int, value) -> bytes:
    if wire == 2:
        return _varint(number << 3 | 2) + _varint(len(value)) + value
    return _varint(number << 3 | wire) + (
        _varint(value) if wire == 0 else value)


def _rebuild(buf: bytes, edit) -> bytes:
    """The message with each field passed through ``edit(number, wire,
    value)``, which returns the value to keep or None to drop it."""
    from benchmark import stages

    out = bytearray()
    for number, wire, value in stages.fields(buf):
        value = edit(number, wire, value)
        if value is not None:
            out += _field(number, wire, value)
    return bytes(out)


def slim_trace(src: str, dst: str) -> None:
    """Copy a trace keeping what the readers read.  Planes: the devices'
    and ``/host:CPU``.  Lines: the two XLA lines; the host's ``python``
    lines (the anchor, the program's spans).  Events: without their own
    stats (start and duration are fields of the event).  Instruction
    metadata: the name cut to 96 bytes, the stats of ``KEPT_STATS``."""
    from benchmark import stages, xplane

    with open(src, "rb") as f:
        space = f.read()

    def plane(number, wire, value):
        if number != 1:
            return None
        name = (stages._first(value, 2, b"") or b"").decode()
        device = name.startswith(xplane.DEVICE_PLANE)
        if not device and name != "/host:CPU":
            return None
        lines = (xplane.OPS_LINE, xplane.MODULES_LINE) if device \
            else ("python",)
        kept_ids = {stages._first(stages._map_entry(v)[1], 1, 0)
                    for n, _, v in stages.fields(value) if n == 5
                    and (stages._first(stages._map_entry(v)[1], 2, b"")
                         or b"").decode() in KEPT_STATS}

        def event(n, w, v):
            return None if n == 4 else v

        def line(n, w, v):
            return _rebuild(v, event) if n == 4 else v

        def metadata(n, w, v):
            if n == 2:
                return v[:96]
            if n == 5 and stages._first(v, 1, 0) not in kept_ids:
                return None
            return v

        def entry(n, w, v):      # a map entry: key=1, value=2
            return _rebuild(v, metadata) if n == 2 else v

        def part(n, w, v):
            if n == 3:
                label = (stages._first(v, 2, b"") or b"").decode()
                return _rebuild(v, line) if label.startswith(lines) \
                    else None
            if n == 4 and device:
                return _rebuild(v, entry)
            return None if n == 6 else v

        return _rebuild(value, part)

    with open(dst, "wb") as f:
        f.write(_rebuild(space, plane))


def main(argv=None) -> int:
    argv = list(argv or sys.argv[1:] or ["chiprun_out/stages"])
    out_dir = os.path.abspath(argv[0])
    # a second argument names another plate configuration (``cp4-plate``,
    # PR 27): its files carry the name, ``tiny_cp4-plate_stages_…``
    name = argv[1] if len(argv) > 1 else "cp3-plate"
    tag = "" if name == "cp3-plate" else name + "_"
    os.makedirs(out_dir, exist_ok=True)
    from benchmark import harness, ledger, plate

    harness.prepare_environment()
    config = harness.load_json(harness.HERE, "configs", name + ".json")
    device = harness.device_record()
    work = tempfile.mkdtemp(prefix="tmstages_")
    try:
        src = os.path.join(work, "src")
        sites = write_grid_plate(src, FIELDS, SIZE, config["channels"], 25)
        from benchmark.drivers.plate import join_speculation, submit

        submit(work, 0, src, sites, config, CAPACITY)   # warm-up
        join_speculation()
        tracer = harness.TraceWindow(os.path.join(work, "trace"))
        tracer.start()
        unit = submit(work, 1, src, sites, config, CAPACITY)
        tracer.stop()
        slim_trace(tracer.file(), os.path.join(
            out_dir, f"tiny_{tag}stages_tpu_v5e.xplane.pb"))
        shutil.copy(os.path.join(unit.root, "workflow", "ledger.jsonl"),
                    os.path.join(out_dir, f"{tag}stages_run_ledger.jsonl"))
        events = ledger.run_ledger(unit.root)
        with open(os.path.join(out_dir, f"{tag}stages_unit.json"), "w") as f:
            json.dump({"device": device, "sites": sites, "t0": unit.t0,
                       "t1": unit.t1, "anchor_wall": tracer.anchor_wall,
                       "field_size": SIZE, "capacity": CAPACITY,
                       "escalations": ledger.escalations(events)}, f,
                      indent=1)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"out": out_dir, "device": device,
                      "bytes": {n: os.path.getsize(os.path.join(out_dir, n))
                                for n in sorted(os.listdir(out_dir))}}))
    return 0 if device["platform"] == "tpu" else 1


if __name__ == "__main__":
    sys.exit(main())
