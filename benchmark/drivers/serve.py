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
                               load_json, load_module, real_compiles, tmx,
                               tmx_exit)

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


def end_to_end(results: list, t0: float) -> tuple:
    """``(metrics, window seconds)`` of a window's client records: the
    window runs from the first enqueue to the last answer seen, the rate
    is every ``done`` job over all of it, and a latency runs from the
    moment a job was due to the moment its record was seen."""
    done = [r for r in results if r["state"] == "done"]
    if not done:
        return {}, time.time() - t0
    window_s = max(r["end"] for r in done) - t0
    latencies = [r["end"] - r["due"] for r in done]
    return {
        "jobs_per_s": {"value": len(done) / window_s, "unit": "jobs/s"},
        "job_p50_s": {"value": stats.percentile(latencies, 50.0),
                      "unit": "s"},
        "job_p95_s": {"value": stats.tail(latencies)[0], "unit": "s"},
    }, window_s


def daemon_argv(sroot: str, config: dict) -> list:
    s = config["serve"]
    return ["serve", "run", "--root", sroot, "--poll", s["poll_s"],
            "--max-queue", s["max_queue"], "--tenant-quota",
            s["tenant_quota"], "--lease", s["lease_s"]]


def experiment_of(work: str, config: dict, traffic: dict, on_chip: bool,
                  seed: int, rng) -> tuple:
    """``(store, field size, enumerated payloads)``: the experiment the
    queries are asked of, written through the normal path (one unit of
    the ``store_from`` configuration under ``store_traffic``), and the
    seed's payloads over its feature columns."""
    from tmlibrary_tpu.models.store import ExperimentStore

    plate_config = load_json(HERE, "configs", config["store_from"] + ".json")
    plate_traffic = load_json(HERE, "traffic",
                              config["store_traffic"] + ".json")
    src, sites, size, capacity = plate_driver.write_well(
        work, plate_config, plate_traffic, on_chip, seed)
    unit = plate_driver.submit(work, 0, src, sites, plate_config, capacity)
    plate_driver.join_speculation()
    store = ExperimentStore.open(Path(unit.root))
    columns = {o: list(store.read_features(o).columns)
               for o in traffic["objects"]}
    return store, size, payloads(traffic, columns, rng, on_chip)


def matrix_of(reference, store, payload: dict) -> np.ndarray:
    """The matrix a payload's query runs on, as the reference makes it
    from the experiment's raw feature shards: float64, z-scored by the
    reference itself."""
    return reference.feature_matrix(store.root, payload["objects_name"],
                                    payload["features"])


def control(seed: int, config: dict, traffic: dict, device: dict,
            work: str) -> dict:
    """The cell's control on one seed (``benchmark/control.py``): the
    reference's lower-precision answer in the program's place, on the
    matrices of a seeded sample of the seed's payloads, through the
    comparison a run makes.  It has to read over the limit."""
    import jax
    import jax.numpy as jnp

    on_chip = device["platform"] == "tpu"
    rng = np.random.default_rng(seed)
    store, _, enumerated = experiment_of(work, config, traffic, on_chip,
                                         seed, rng)
    reference = load_module(os.path.join(HERE, "configs",
                                         config["reference"]))

    @jax.jit
    def cross_high(x):
        return jnp.matmul(x, x.T, precision=jax.lax.Precision.HIGH)

    readings = []
    for i in rng.choice(len(enumerated), size=config["reference_sample_jobs"],
                        replace=False):
        p = enumerated[int(i)]
        x = matrix_of(reference, store, p)
        verdict = reference.check_answer(
            reference.control_answer(x, p["k"]), x, p["k"])
        # the same step down as the device takes it: on a TPU this is the
        # three-pass product itself, anywhere else float32 (no control)
        cross = np.asarray(cross_high(jnp.asarray(x, jnp.float32)))
        device_verdict = reference.check_answer(
            reference.control_answer(x, p["k"], cross=cross), x, p["k"])
        readings.append({
            "objects": p["objects_name"], "k": p["k"], "rows": len(x),
            "distance_units": verdict["worst_distance_units"],
            "index_units": verdict["worst_index_units"],
            "device_high_distance_units":
                device_verdict["worst_distance_units"],
            "device_high_index_units": device_verdict["worst_index_units"],
            "equal": verdict["equal"]})

    def worst(key: str) -> float:
        return max(r[key] for r in readings)

    # a run compares the worst of its sample: that is the control's number
    return {"seed": seed, "limit": reference.LIMIT_UNITS,
            "distance_units": worst("distance_units"),
            "index_units": worst("index_units"),
            "device_high_distance_units":
                worst("device_high_distance_units"),
            "device_high_index_units": worst("device_high_index_units"),
            "least_answer_distance_units": min(r["distance_units"]
                                               for r in readings),
            "answers_failed": sum(1 for r in readings if not r["equal"]),
            "answers": readings}


def run(args, config, traffic, device, meter, work, t_process) -> dict:
    from tmlibrary_tpu.tools.base import ToolResult

    on_chip = device["platform"] == "tpu"
    rng = np.random.default_rng(args.seed)
    run_ = ServeRun(config, device)

    # ---- set-up 1: the experiment, through the normal path
    mark = meter.mark()
    store, size, enumerated = experiment_of(work, config, traffic, on_chip,
                                            args.seed, rng)
    experiment = str(store.root)

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
    # been empty for idle_exit_s; one that ends any other way has failed
    # the run, and the result says so instead of ending with a traceback
    daemon_exit = tmx_exit(daemon_argv(sroot, config) + [
        "--idle-exit", config["serve"]["idle_exit_s"]])
    for t in threads:
        t.join(timeout=120.0)
    alive = [t.name for t in threads if t.is_alive()]
    if run_.tracer is not None:
        stopper.join()
    metrics, window_s = end_to_end(results, t0)
    run_.compile["window"] = meter.since(mark)
    run_.window = (t0, t0 + window_s)

    # ---- after the window
    run_.serve_events = ledger.serve_ledger(sroot)
    spool = Path(sroot) / "spool"
    done = [r for r in results if r["state"] == "done"]
    places = {r["job"]: [s for s in TERMINAL
                         if (spool / s / f"{r['job']}.json").exists()]
              for r in results}
    latencies = [r["end"] - r["due"] for r in done]

    # kNN answers against float64 brute force, on a seeded sample
    t_reference = time.time()
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
            matrices[key] = matrix_of(reference, store, p)
        knn.append(reference.check_answer(answer.values, matrices[key],
                                          p["k"]))
    reference_s = time.time() - t_reference
    window = run_.compile["window"]
    # every number compared, beside its limit: [number, limit]; a run is
    # correct when none is over its limit and there was something to compare
    limit = reference.LIMIT_UNITS
    compared = {
        "knn_distance_units": [max((c["worst_distance_units"] for c in knn),
                                   default=None), limit],
        "knn_index_units": [max((c["worst_index_units"] for c in knn),
                                default=None), limit],
        "jobs_not_done": [len(results) - len(done), 0],
        "jobs_not_in_one_place": [
            sum(1 for p in places.values() if len(p) != 1), 0],
        "window_compiles": [real_compiles(window), 0],
        "daemon_exit_code": [daemon_exit, 0],
        "clients_not_ended": [len(alive) + len(errors), 0],
    }

    def within(name: str) -> bool:
        number, bound = compared[name]
        return number is not None and number <= bound

    checks = {
        "every_job_in_exactly_one_place":
            within("jobs_not_in_one_place") and bool(results),
        "every_job_done": within("jobs_not_done") and bool(done),
        "knn_equals_bruteforce":
            within("knn_distance_units") and within("knn_index_units"),
        "no_compile_in_window": within("window_compiles"),
        "clients_ended": within("clients_not_ended"),
        "daemon_ended_by_idle_exit": within("daemon_exit_code"),
    }
    supported = stats.tail(latencies)[1] if latencies else 0.0
    # what the daemon's ledger says of every job that did not end in done/
    # alone: the reason of a rejection is nowhere else
    odd = {r["job"] for r in results
           if r["state"] != "done" or len(places[r["job"]]) != 1}
    emit({"line": "checks", "checks": checks, "jobs": len(results),
          "done": len(done), "client_errors": errors[:5],
          "jobs_not_done_alone": [
              {"job": job, "places": places[job], "ledger": [
                  {k: v for k, v in e.items()
                   if k not in ("crc", "trace_id", "job")}
                  for e in run_.serve_events if e.get("job") == job][:12]}
              for job in sorted(odd)[:5]],
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
          # held back from the end-to-end metrics (its spread), said here
          "job_p50_s": metrics.get("job_p50_s", {}).get("value"),
          "job_at_supported_percentile_s": (
              stats.percentile(latencies, supported) if latencies else None),
          "answers_compared": len(knn), "reference_s": reference_s,
          "window_s": window_s, "window_compile": window})
    metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    return {"run": run_, "metrics": metrics, "correct": all(checks.values()),
            "attempted": len(results), "failed": len(results) - len(done),
            "compared": compared}
