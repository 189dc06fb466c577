"""The ``serve`` driver: the ``tmx serve`` daemon in the main thread over
a spool, closed-loop client threads that ``tmx enqueue`` query jobs and
watch ``done/``.  The clients never touch JAX; the daemon holds the
chip."""

import json
import os
import threading
import time
from pathlib import Path

import numpy as np

from benchmark import ledger, stats
from benchmark.drivers import plate as plate_driver
from benchmark.harness import (HERE, Run, TraceWindow, at_size, emit,
                               load_json, load_module, real_compiles, tmx)

TERMINAL = ("done", "failed", "rejected", "expired")

#: seconds of a serve window that a ``--trace 1`` run traces
TRACED_S = 5.0


class ServeRun(Run):
    """A serve cell's run: the serve ledger and the window it covers."""

    def __init__(self, config: dict, device: dict):
        super().__init__("serve", config, device)
        self.serve_events: list = []
        self.window = (0.0, 0.0)      # wall clock

    def window_events(self) -> list:
        lo, hi = self.window
        return [e for e in self.serve_events if lo <= e.get("ts", 0) <= hi]


def payloads(traffic: dict, columns: dict, rng, on_chip: bool) -> list:
    """The enumerated payloads: objects x k x feature subsets, in a
    seeded order.  ``columns`` gives each object type's feature names."""
    mix = at_size(traffic, on_chip)
    out = []
    for objects in mix["objects"]:
        names = sorted(c for c in columns[objects]
                       if c.startswith(mix["feature_prefix"]))
        subsets = set()
        while len(subsets) < mix["feature_subsets"]:
            pick = rng.choice(len(names), size=mix["features_per_job"],
                              replace=False)
            subsets.add(tuple(sorted(int(i) for i in pick)))
        for subset in sorted(subsets):
            for k in mix["k"]:
                out.append({**mix["job"]["payload"], "k": int(k),
                            "objects_name": objects,
                            "features": [names[i] for i in subset]})
    order = rng.permutation(len(out))
    return [out[i] for i in order]


def enqueue(sroot: str, experiment: str, traffic: dict, tenant: str,
            job_id: str, payload: dict) -> None:
    """``tmx enqueue --kind query --tool knn``: one job of the mix."""
    tmx(["enqueue", "--root", sroot, "--experiment", experiment,
         "--tenant", tenant, "--job-id", job_id,
         "--kind", traffic["job"]["kind"], "--tool", traffic["job"]["tool"],
         "--payload", json.dumps(payload)])


def outcome(spool: Path, job_id: str, give_up_at: float) -> str:
    """Where the job ended: polls ``done/`` every 4 ms and the rare
    outcomes every 50th poll; ``open`` if nothing by ``give_up_at``."""
    polls = 0
    while time.time() < give_up_at:
        for state in TERMINAL if polls % 50 == 49 else TERMINAL[:1]:
            if (spool / state / f"{job_id}.json").exists():
                return state
        polls += 1
        time.sleep(0.004)
    return "open"


class Stream:
    """The whole query stream of a run, drawn from the seed alone: job
    ``n`` is a repeat of an earlier job with probability ``repeat_share``
    and otherwise a payload no earlier job had — the next enumerated one,
    and once those are used up an enumerated one made new by the free
    key, which the tool ignores and which changes no shape."""

    def __init__(self, enumerated: list, traffic: dict, rng):
        self.enumerated, self.traffic = enumerated, traffic
        self.rng = rng
        self.issued: list = []
        self.fresh = 0
        self.lock = threading.Lock()

    def next(self) -> tuple:
        """``(n, payload, is a repeat)`` of the run's ``n``-th job."""
        with self.lock:
            n = len(self.issued)
            repeat = bool(self.issued) and \
                self.rng.random() < self.traffic["repeat_share"]
            if repeat:
                payload = self.issued[int(self.rng.integers(n))]
            else:
                payload = dict(
                    self.enumerated[self.fresh % len(self.enumerated)])
                if self.fresh >= len(self.enumerated):
                    payload[self.traffic["free_key"]] = self.fresh
                self.fresh += 1
            self.issued.append(payload)
            return n, payload, repeat


def client(tenant: str, number: int, stream: Stream, sroot: str,
           experiment: str, stop_at: float, out: list, errors: list) -> None:
    """One analyst: enqueue, wait for the answer, enqueue the next."""
    spool = Path(sroot) / "spool"
    try:
        while time.time() < stop_at:
            n, payload, repeat = stream.next()
            job_id = f"{tenant}-{number}-{n:06d}"
            due = time.time()
            enqueue(sroot, experiment, stream.traffic, tenant, job_id,
                    payload)
            state = outcome(spool, job_id, stop_at + 60.0)
            out.append({"job": job_id, "due": due, "end": time.time(),
                        "state": state, "repeat": repeat,
                        "payload": payload})
    except Exception as exc:  # a client thread must report, not vanish
        errors.append(f"{tenant}-{number}: {type(exc).__name__}: {exc}")


def daemon_argv(sroot: str, config: dict) -> list:
    s = config["serve"]
    return ["serve", "run", "--root", sroot, "--poll", s["poll_s"],
            "--max-queue", s["max_queue"], "--tenant-quota",
            s["tenant_quota"], "--lease", s["lease_s"]]


def run(args, config, traffic, device, meter, work, t_process) -> dict:
    from tmlibrary_tpu.analytics.store import FeatureStore
    from tmlibrary_tpu.models.store import ExperimentStore
    from tmlibrary_tpu.tools.base import ToolResult

    on_chip = device["platform"] == "tpu"
    rng = np.random.default_rng(args.seed)
    run_ = ServeRun(config, device)

    # ---- set-up 1: the experiment, through the normal path
    plate_config = load_json(HERE, "configs", config["store_from"] + ".json")
    plate_traffic = load_json(HERE, "traffic",
                              config["store_traffic"] + ".json")
    mark = meter.mark()
    src, sites, size, capacity = plate_driver.write_well(
        work, plate_config, plate_traffic, on_chip, args.seed)
    unit = plate_driver.submit(work, 0, src, sites, plate_config, capacity)
    plate_driver.join_speculation()
    experiment = unit.root
    store = ExperimentStore.open(Path(experiment))
    columns = {o: list(store.read_features(o).columns)
               for o in traffic["objects"]}
    enumerated = payloads(traffic, columns, rng, on_chip)

    # ---- set-up 2: every query shape once, through the daemon itself
    sroot = os.path.join(work, "serve_root")
    shapes = {}
    for p in enumerated:
        shapes.setdefault((p["objects_name"], p["k"]), p)
    for i, p in enumerate(shapes.values()):
        enqueue(sroot, experiment, traffic, "warmup", f"warmup-{i}",
                {**p, traffic["free_key"]: -1 - i})
    tmx(daemon_argv(sroot, config) + ["--max-jobs", len(shapes)])
    run_.compile["setup"] = meter.since(mark)
    setup_s = time.time() - t_process
    emit({"line": "setup", "setup_s": setup_s, "field": [size, size],
          "store_objects": {o: int(len(store.read_features(o)))
                            for o in traffic["objects"]},
          "shapes_warmed": len(shapes), "payloads": len(enumerated),
          "compile": run_.compile["setup"]})

    # ---- the window
    stream = Stream(enumerated, traffic, rng)
    results, errors, threads = [], [], []
    mark = meter.mark()
    if args.trace:
        # a few seconds of the window, not all of it: traces are large
        run_.tracer = TraceWindow(os.path.join(work, "trace"))
        run_.tracer.start()
        stopper = threading.Timer(min(TRACED_S, args.seconds),
                                  run_.tracer.stop)
        stopper.start()
    t0 = time.time()
    stop_at = t0 + args.seconds
    number = 0
    for tenant, n in traffic["clients"].items():
        for _ in range(n):
            t = threading.Thread(
                target=client, name=f"client-{tenant}-{number}",
                args=(tenant, number, stream, sroot, experiment, stop_at,
                      results, errors))
            threads.append(t)
            number += 1
    for t in threads:
        t.start()
    # the daemon serves until the clients have stopped and the queue has
    # been empty for idle_exit_s
    tmx(daemon_argv(sroot, config)
        + ["--idle-exit", config["serve"]["idle_exit_s"]])
    for t in threads:
        t.join(timeout=120.0)
    alive = [t.name for t in threads if t.is_alive()]
    if run_.tracer is not None:
        stopper.join()
    ends = [r["end"] for r in results if r["state"] == "done"]
    t1 = max(ends) if ends else time.time()
    window_s = t1 - t0
    run_.compile["window"] = meter.since(mark)
    run_.window = (t0, t1)

    # ---- after the window
    run_.serve_events = ledger.serve_ledger(sroot)
    spool = Path(sroot) / "spool"
    done = [r for r in results if r["state"] == "done"]
    places = {r["job"]: [s for s in TERMINAL
                         if (spool / s / f"{r['job']}.json").exists()]
              for r in results}
    exactly_one = all(len(p) == 1 for p in places.values())
    latencies = [r["end"] - r["due"] for r in done]

    # kNN answers against float64 brute force, on a seeded sample
    reference = load_module(os.path.join(HERE, "configs",
                                         config["reference"]))
    sample = [done[int(i)] for i in rng.choice(
        len(done), size=min(config["reference_sample_jobs"], len(done)),
        replace=False)] if done else []
    knn, matrices = [], {}
    for r in sample:
        record = json.loads((spool / "done" / f"{r['job']}.json").read_text())
        answer = ToolResult.load(Path(record["summary"]["result_dir"]))
        p = r["payload"]
        key = (p["objects_name"], tuple(p["features"]))
        if key not in matrices:
            _, x, _ = FeatureStore.ensure(store, p["objects_name"]) \
                .standardized(p["features"])
            matrices[key] = np.asarray(x, np.float64)
        knn.append(reference.check_answer(answer.values, matrices[key],
                                          p["k"]))
    window = run_.compile["window"]
    checks = {
        "every_job_in_exactly_one_place": exactly_one and bool(results),
        "every_job_done": len(done) == len(results) and bool(done),
        "knn_equals_bruteforce": bool(knn) and all(c["equal"] for c in knn),
        "no_compile_in_window": real_compiles(window) == 0,
        "clients_ended": not alive and not errors,
    }
    p95, supported = stats.tail(latencies) if latencies else (0.0, 0.0)
    emit({"line": "checks", "checks": checks, "jobs": len(results),
          "done": len(done), "client_errors": errors[:5],
          "states": {s: sum(1 for r in results if r["state"] == s)
                     for s in TERMINAL + ("open",)},
          "knn_worst_distance_error": max(
              (c["max_abs_distance_error"] for c in knn), default=None),
          "knn_slots_differing": sum(c["slots_differing"] for c in knn),
          "knn_worst_units_of_tolerance": max(
              (max(c["worst_distance_units"], c["worst_index_units"])
               for c in knn), default=None),
          "repeats": sum(1 for r in results if r["repeat"]),
          "highest_supported_percentile": supported,
          "job_at_supported_percentile_s": (
              stats.percentile(latencies, supported) if latencies else None),
          "window_s": window_s, "window_compile": window})
    metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
    if done:
        metrics.update({
            "jobs_per_s": {"value": len(done) / window_s, "unit": "jobs/s"},
            "job_p50_s": {"value": stats.percentile(latencies, 50.0),
                          "unit": "s"},
            "job_p95_s": {"value": p95, "unit": "s"},
        })
    return {"run": run_, "metrics": metrics, "correct": all(checks.values()),
            "attempted": len(results), "failed": len(results) - len(done)}
