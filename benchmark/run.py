#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``: a
configuration (``configs/<config>.json``, with its plain reference beside
it), a traffic mix (``traffic/<traffic>.json``) and the metrics that list
it; the configuration's ``driver`` key picks the loop (``drivers/``).  One
process holds the chip and every call into the program goes through
``tmlibrary_tpu.cli.main`` in it.  The last line of standard output is the
result; earlier lines are context.  Without a ``tpu`` platform the same
code runs at the configuration's rehearsal size, names the device it ran
on, and exits non-zero: a CPU number is never a result."""

import time

T_PROCESS = time.time()

import argparse
import importlib
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
# the checkout, not this directory, leads the path: ``benchmark`` is a
# package, and nothing of it may shadow a module of the same name
sys.path[0] = os.path.dirname(HERE)

from benchmark import harness, ledger, xplane  # noqa: E402


def metric_names(bench: dict, group: str, workload: str) -> list:
    """The metrics of ``group`` that this cell reports: those that list it
    under ``workloads``, and those that list none."""
    return [m["name"] for m in bench[group]
            if workload in m.get("workloads", [workload])]


def device_fields(run) -> dict:
    """``busy_s`` and ``window_s`` of the traced window, and the
    breakdown, from the trace and the ledgers' spans on one clock."""
    tracer = run.tracer
    tr = run.trace = xplane.Trace.from_file(tracer.file())
    if tr.anchor_s is None:
        raise RuntimeError("the trace holds no anchor annotation")

    def on_trace_clock(wall: float) -> float:
        return tr.anchor_s + (wall - tracer.anchor_wall)

    if run.kind == "plate":
        unit = run.traced_units[0]
        lo, hi = on_trace_clock(unit.t0), on_trace_clock(unit.t1)
        spans = ledger.spans(unit.events)
    else:
        lo, hi = tr.anchor_s, on_trace_clock(tracer.wall_stop)
        # the daemon's own work; queue_wait and sched_delay are waits
        # that overlap everything and would swallow every gap
        spans = [s for s in ledger.spans(run.serve_events)
                 if s[0] not in ("queue_wait", "sched_delay")]
    spans = [(name, on_trace_clock(t0), on_trace_clock(t1))
             for name, t0, t1 in spans]
    run.trace_window = (lo, hi)
    run.busy_s = xplane.busy_seconds(tr, lo, hi)
    return {
        "busy_s": run.busy_s, "window_s": hi - lo,
        "breakdown": {
            "device_ops": xplane.top_operations(tr, 10),
            "idle_gaps": xplane.gap_breakdown(tr, lo, hi, spans, 5),
        },
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    bench = harness.load_json(harness.CHECKOUT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; BENCHMARK.json has "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    workload = cells[args.workload]
    entry = {c["name"]: c for c in bench["configs"]}[workload["config"]]
    config = harness.load_json(harness.CHECKOUT, entry["file"])
    traffic = harness.load_json(HERE, "traffic",
                                workload["traffic"] + ".json")

    cache = harness.prepare_environment()
    harness.take_stdout()
    try:
        import tmlibrary_tpu
    except ImportError:
        tmlibrary_tpu = None
    if tmlibrary_tpu is None or not os.path.abspath(
            tmlibrary_tpu.__file__).startswith(harness.CHECKOUT + os.sep):
        print("this checkout holds no tmlibrary_tpu: nothing to measure",
              file=sys.stderr)
        return 2
    device = harness.device_record()
    on_chip = device["platform"] == "tpu"
    harness.emit({"line": "start", "workload": args.workload,
                  "seed": args.seed, "seconds": args.seconds,
                  "trace": args.trace, "device": device,
                  "rehearsal": not on_chip, "compile_cache_dir": cache})
    if device["count"] < workload["chips"]:
        print(f"{args.workload} needs {workload['chips']} chip(s); JAX "
              f"found {device}", file=sys.stderr)
        return 3
    if on_chip:
        from benchmark import roofline

        roofline.peaks(device["kind"])   # an unknown chip is an error

    meter = harness.CompileMeter()
    driver = importlib.import_module("benchmark.drivers." + config["driver"])
    # plates, experiment roots and the spool: under TMPDIR, gone at the end
    work = tempfile.mkdtemp(prefix="tmbench_")
    try:
        out = driver.run(args, config, traffic, device, meter, work,
                         T_PROCESS)
        run = out["run"]
        run.memory_peak_bytes = harness.memory_peak_bytes()
        result_device = dict(device,
                             memory_peak_bytes=run.memory_peak_bytes)
        result = {"correct": out["correct"], "attempted": out["attempted"],
                  "failed": out["failed"]}
        if args.trace:
            traced = device_fields(run)
            result["breakdown"] = traced.pop("breakdown")
            result_device.update(traced)
            result["metrics"] = harness.read_metrics(
                metric_names(bench, "per_layer", args.workload), run)
        else:
            wanted = metric_names(bench, "end_to_end", args.workload)
            result["metrics"] = {k: v for k, v in out["metrics"].items()
                                 if k in wanted}
        result["device"] = result_device
    finally:
        shutil.rmtree(work, ignore_errors=True)

    if not on_chip:
        harness.emit({"line": "rehearsal", "would_have_printed": result})
        print("not a tpu platform: this was a rehearsal, not a result",
              file=sys.stderr)
        return 1
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
