#!/usr/bin/env python
"""CI serving smoke: a LIVE ``tmx serve`` daemon under flood + SIGTERM.

    python scripts/ci_serve_smoke.py [ARTIFACT_DIR] [--keep DIR]

``tests/test_serve.py`` proves the admission/drain contracts inside one
pytest process; this harness crosses the real boundary the serving
tentpole promises to survive (DESIGN.md §20): a separate ``tmx serve
run`` process admits two tenants' jobs, sheds a third tenant-b flood
past the watermark with the pinned retry-after envelopes, receives an
actual SIGTERM while its first job's jterator window is in flight,
re-spools everything admitted-but-unfinished, exits with the pinned
``EXIT_PREEMPTED`` code (75), and a second daemon process resumes from
the spool alone.  Convergence bar: labels + feature tables of both
tenants' stores must equal a never-interrupted in-process reference run
bit for bit, and the overload path must appear ONLY as ``job_rejected``
ledger events — never a crash or a ``step_failed``.

When ARTIFACT_DIR is given, the drained serve ledger (exactly as the
SIGTERM'd daemon left it) and a ``tmx top --once --json`` fleet view
are copied there for CI artifact upload.  Exit 0 and ``SERVE PASS`` on
convergence; 1 otherwise.
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "scripts"))

# a CI smoke is a CPU run, whatever is attached
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from chaos_run import make_source, make_store, resilience  # noqa: E402

#: pinned drain exit code (resilience.EXIT_PREEMPTED) — asserted, not
#: imported, so this harness also notices the constant drifting
EXIT_PREEMPTED = 75
#: pinned queue-full retry-after (workflow/admission.RETRY_AFTER_S)
RETRY_AFTER_QUEUE_FULL = 30.0


def _env() -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    env.pop("TMX_FAULT_PLAN", None)
    return env


def _ledger_events(path: Path) -> list:
    events = []
    if not path.exists():
        return events
    for line in path.read_text().splitlines():
        try:
            events.append(json.loads(line))
        except ValueError:
            continue
    return events


def _tmx(args: list, out=None, timeout=600) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "tmlibrary_tpu.cli", *args],
        env=_env(), stdout=out or subprocess.PIPE,
        stderr=subprocess.STDOUT, text=(out is None), timeout=timeout,
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("artifacts", nargs="?", default=None,
                        help="copy the drained serve ledger + top view "
                             "here for CI artifact upload")
    parser.add_argument("--keep", metavar="DIR", default=None,
                        help="run inside DIR and keep everything "
                             "(default: a temp dir, removed afterwards)")
    args = parser.parse_args(argv)

    from tmlibrary_tpu.workflow.engine import Workflow

    with tempfile.TemporaryDirectory() as tmp:
        root = Path(args.keep) if args.keep else Path(tmp)
        root.mkdir(parents=True, exist_ok=True)
        source = make_source(root)
        sroot = root / "serve_root"

        print("[1/4] reference run (uninterrupted, in-process)")
        ref, desc = make_store(root, "reference", source)
        Workflow(ref, desc, resilience=resilience()).run()
        ref_labels = ref.read_labels(None, "nuclei")
        ref_feats = ref.read_features("nuclei").sort_values(
            ["site_index", "label"]).reset_index(drop=True)

        print("[2/4] spool two tenants + a tenant-b flood past the "
              "watermark")
        tenants = {}
        for tenant in ("a", "b"):
            store, desc = make_store(root, f"tenant_{tenant}", source)
            desc.save(store.workflow_dir / "workflow.yaml")
            tenants[tenant] = store
            rc = _tmx(["enqueue", "--root", str(sroot),
                       "--experiment", str(store.root),
                       "--tenant", tenant, "--job-id", f"{tenant}-1"])
            if rc.returncode != 0:
                print(f"SERVE FAIL: enqueue {tenant}-1 exited "
                      f"{rc.returncode}\n{rc.stdout}")
                return 1
        # the flood: four more tenant-b jobs; with --max-queue 2 only
        # the two first-tenant jobs fit, so every one of these must shed
        for i in range(2, 6):
            rc = _tmx(["enqueue", "--root", str(sroot),
                       "--experiment", str(tenants["b"].root),
                       "--tenant", "b", "--job-id", f"b-flood{i}"])
            if rc.returncode != 0:
                print(f"SERVE FAIL: flood enqueue exited {rc.returncode}")
                return 1

        print("[3/4] live daemon SIGTERM'd mid-jterator window "
              "(real subprocess)")
        log_path = root / "serve_run.log"
        with open(log_path, "w") as out:
            proc = subprocess.Popen(
                [sys.executable, "-m", "tmlibrary_tpu.cli", "serve", "run",
                 "--root", str(sroot), "--max-queue", "2",
                 "--tenant-quota", "2", "--poll", "0.1"],
                env=_env(), stdout=out, stderr=subprocess.STDOUT, text=True,
            )
            # tenant a sorts first in the WDRR rotation, so job a-1 runs
            # first; SIGTERM once its jterator step is mid-window
            job_ledger = tenants["a"].root / "workflow" / "ledger.jsonl"
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                if proc.poll() is not None:
                    print(f"SERVE FAIL: daemon exited rc {proc.returncode} "
                          "before the first job started\n"
                          + log_path.read_text()[-3000:])
                    return 1
                if any(e.get("step") == "jterator"
                       and e.get("event") == "init_done"
                       for e in _ledger_events(job_ledger)):
                    break
                time.sleep(0.05)
            else:
                proc.kill()
                print("SERVE FAIL: jterator never started in 300s")
                return 1
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=300)
        if rc != EXIT_PREEMPTED:
            print(f"SERVE FAIL: expected exit {EXIT_PREEMPTED}, got {rc}\n"
                  + log_path.read_text()[-3000:])
            return 1

        serve_ledger = sroot / "serve" / "ledger.jsonl"
        events = _ledger_events(serve_ledger)
        if not any(e.get("event") == "serve_preempted" for e in events):
            print("SERVE FAIL: exit 75 without a serve_preempted event")
            return 1
        if any(e.get("event") == "step_failed" for e in events):
            print("SERVE FAIL: overload/preemption produced step_failed")
            return 1
        rejected = [e for e in events if e.get("event") == "job_rejected"]
        flood_rejected = {e["job"] for e in rejected
                         if str(e.get("job", "")).startswith("b-flood")}
        if flood_rejected != {f"b-flood{i}" for i in range(2, 6)}:
            print(f"SERVE FAIL: flood not fully shed (rejected: "
                  f"{sorted(flood_rejected)})")
            return 1
        bad = [e for e in rejected
               if e.get("retry_after_s") != RETRY_AFTER_QUEUE_FULL]
        if bad:
            print(f"SERVE FAIL: unpinned retry_after in rejections: {bad}")
            return 1
        respooled = sorted(
            p.stem for p in (sroot / "spool" / "incoming").glob("*.json"))
        if respooled != ["a-1", "b-1"]:
            print(f"SERVE FAIL: expected a-1+b-1 re-spooled, got "
                  f"{respooled}")
            return 1
        print(f"      shed {len(flood_rejected)} flood jobs "
              f"(retry_after_s={RETRY_AFTER_QUEUE_FULL:g}), "
              f"re-spooled {respooled}")

        # the SIGTERM drain must have dumped the flight recorder ring
        # (telemetry.flight_dump via _drain_and_exit) next to the ledger
        flightrecs = sorted((sroot / "serve").glob("flightrec.*.json"))
        if not flightrecs:
            print("SERVE FAIL: SIGTERM drain left no flightrec dump under "
                  f"{sroot / 'serve'}")
            return 1
        dump = json.loads(flightrecs[0].read_text())
        if dump.get("reason", "").split(":")[0] != "preempted" or not \
                dump.get("events"):
            print(f"SERVE FAIL: flightrec dump malformed: "
                  f"reason={dump.get('reason')!r} "
                  f"events={len(dump.get('events', []))}")
            return 1
        print(f"      flight recorder dumped {len(dump['events'])} events "
              f"(reason {dump['reason']})")

        if args.artifacts:
            art = Path(args.artifacts)
            art.mkdir(parents=True, exist_ok=True)
            shutil.copy(serve_ledger, art / "serve_ledger_drained.jsonl")
            for fr in flightrecs:
                shutil.copy(fr, art / fr.name)

        print("[4/4] fresh daemon resumes from the spool alone")
        with open(root / "serve_resume.log", "w") as out:
            p2 = subprocess.run(
                [sys.executable, "-m", "tmlibrary_tpu.cli", "serve", "run",
                 "--root", str(sroot), "--max-queue", "2",
                 "--tenant-quota", "2", "--poll", "0.1",
                 "--max-jobs", "2"],
                env=_env(), stdout=out, stderr=subprocess.STDOUT,
                text=True, timeout=900,
            )
        if p2.returncode != 0:
            print(f"SERVE FAIL: resume daemon exited {p2.returncode}\n"
                  + (root / "serve_resume.log").read_text()[-3000:])
            return 1
        done = sorted(
            p.stem for p in (sroot / "spool" / "done").glob("*.json"))
        if done != ["a-1", "b-1"]:
            print(f"SERVE FAIL: expected both jobs done, got {done}")
            return 1

        top = _tmx(["top", "--root", str(sroot), "--once", "--json"])
        if args.artifacts:
            (Path(args.artifacts) / "serve_top.json").write_text(
                top.stdout or "")

        # end-to-end trace: one schema-valid Chrome trace reconstructed
        # purely from the ledgers (serve ledger + spooled job roots)
        trace_out = root / "serve_trace.json"
        tr = _tmx(["trace", "--root", str(sroot), "--export", "chrome",
                   str(trace_out)])
        if tr.returncode != 0:
            print(f"SERVE FAIL: chrome trace export exited "
                  f"{tr.returncode}\n{tr.stdout}")
            return 1
        doc = json.loads(trace_out.read_text())
        tev = doc.get("traceEvents") or []
        flows = [e for e in tev if e.get("ph") in ("s", "t", "f")]
        slices = [e for e in tev if e.get("ph") == "X"]
        if not slices or not flows:
            print(f"SERVE FAIL: chrome trace too thin "
                  f"({len(slices)} slices, {len(flows)} flow events)")
            return 1
        print(f"      chrome trace: {len(tev)} events "
              f"({len(slices)} slices, {len(flows)} flow events)")
        if args.artifacts:
            shutil.copy(trace_out, Path(args.artifacts) / "serve_trace.json")

        # SLO view: both tenants reporting latency, zero burn at the
        # generous defaults — and `tmx slo` exiting 0 (no breach)
        slo = _tmx(["slo", "--root", str(sroot), "--json"])
        if slo.returncode != 0:
            print(f"SERVE FAIL: tmx slo exited {slo.returncode} "
                  f"(expected 0 = no burn)\n{slo.stdout}")
            return 1
        slo_view = json.loads(slo.stdout)
        slo_tenants = slo_view.get("tenants") or {}
        if sorted(slo_tenants) != ["a", "b"]:
            print(f"SERVE FAIL: tmx slo saw tenants "
                  f"{sorted(slo_tenants)}, expected ['a', 'b']")
            return 1
        for name, t in sorted(slo_tenants.items()):
            if t.get("latency_p95_s") is None or t.get("breach"):
                print(f"SERVE FAIL: tenant {name} slo malformed: {t}")
                return 1
            print(f"      slo tenant {name}: p95 "
                  f"{t['latency_p95_s']:.3f}s availability "
                  f"{t['availability']:.2%} burn {t['burn']}")
        if args.artifacts:
            (Path(args.artifacts) / "serve_slo.json").write_text(
                slo.stdout or "")

        from tmlibrary_tpu.models.store import ExperimentStore

        ok = True
        for tenant, store in sorted(tenants.items()):
            resumed = ExperimentStore.open(store.root)
            labels_ok = np.array_equal(
                resumed.read_labels(None, "nuclei"), ref_labels)
            got = resumed.read_features("nuclei").sort_values(
                ["site_index", "label"]).reset_index(drop=True)
            feats_ok = got.equals(ref_feats)
            print(f"      tenant {tenant}: labels converged {labels_ok}, "
                  f"features converged {feats_ok}")
            ok = ok and labels_ok and feats_ok
        if ok:
            print("SERVE PASS: flooded + SIGTERM'd daemon converged to "
                  "the uninterrupted reference")
            return 0
        print("SERVE FAIL: served stores diverge from the reference")
        return 1


if __name__ == "__main__":
    sys.exit(main())
