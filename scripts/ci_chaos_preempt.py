#!/usr/bin/env python
"""CI preemption smoke: SIGTERM a LIVE ``tmx workflow submit`` mid-step,
resume, and diff against an uninterrupted run.

    python scripts/ci_chaos_preempt.py [ARTIFACT_DIR] [--keep DIR]

``tests/test_preemption.py`` injects its signals through the fault
harness inside one pytest process; this harness crosses the real
boundary the tentpole promises to survive (DESIGN.md §19): a separate
``tmx`` process receives an actual SIGTERM from outside while its
jterator step is executing, drains its in-flight window, exits with the
pinned ``EXIT_PREEMPTED`` code (75), and a second process resumes from
the on-disk ledger alone.  Convergence bar: labels + feature tables of
the preempted-then-resumed store must equal a never-interrupted
reference run bit for bit.

When ARTIFACT_DIR is given, the drained run ledger (exactly as the
SIGTERM'd process left it) and the interrupted run's output are copied
there for CI artifact upload.  Exit 0 and ``PREEMPT PASS`` on
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


def _ledger_has(ledger_path: Path, step: str, event: str) -> bool:
    if not ledger_path.exists():
        return False
    for line in ledger_path.read_text().splitlines():
        try:
            e = json.loads(line)
        except ValueError:
            continue
        if e.get("step") == step and e.get("event") == event:
            return True
    return False


def run_preempted(store_root: Path, out) -> subprocess.CompletedProcess:
    """Launch a real ``tmx workflow submit`` subprocess and SIGTERM it
    the moment its jterator step has started (init_done in the ledger —
    batch 0 is then executing/compiling, so the signal lands mid-step)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO)}
    env.pop("TMX_FAULT_PLAN", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "tmlibrary_tpu.cli", "workflow", "submit",
         "--root", str(store_root), "--retry-delay", "0"],
        env=env, stdout=out, stderr=subprocess.STDOUT, text=True,
    )
    ledger = store_root / "workflow" / "ledger.jsonl"
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SystemExit(
                f"PREEMPT FAIL: run finished (rc {proc.returncode}) before "
                "the jterator step started — nothing to preempt"
            )
        if _ledger_has(ledger, "jterator", "init_done"):
            break
        time.sleep(0.05)
    else:
        proc.kill()
        raise SystemExit("PREEMPT FAIL: jterator never started in 300s")
    proc.send_signal(signal.SIGTERM)
    rc = proc.wait(timeout=300)
    return subprocess.CompletedProcess(proc.args, rc)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("artifacts", nargs="?", default=None,
                        help="copy the drained ledger + run log here "
                             "for CI artifact upload")
    parser.add_argument("--keep", metavar="DIR", default=None,
                        help="run inside DIR and keep everything "
                             "(default: a temp dir, removed afterwards)")
    args = parser.parse_args(argv)

    from tmlibrary_tpu.workflow.engine import RunLedger, Workflow

    with tempfile.TemporaryDirectory() as tmp:
        root = Path(args.keep) if args.keep else Path(tmp)
        root.mkdir(parents=True, exist_ok=True)
        source = make_source(root)

        print("[1/3] reference run (uninterrupted, in-process)")
        ref, desc = make_store(root, "reference", source)
        Workflow(ref, desc, resilience=resilience()).run()
        ref_labels = ref.read_labels(None, "nuclei")
        ref_feats = ref.read_features("nuclei").sort_values(
            ["site_index", "label"]).reset_index(drop=True)

        print("[2/3] live run SIGTERM'd mid-jterator (real subprocess)")
        victim, desc = make_store(root, "preempted", source)
        desc.save(victim.workflow_dir / "workflow.yaml")
        log_path = root / "preempted_run.log"
        with open(log_path, "w") as out:
            p1 = run_preempted(victim.root, out)
        log_tail = log_path.read_text()[-3000:]
        if p1.returncode != EXIT_PREEMPTED:
            print(f"PREEMPT FAIL: expected exit {EXIT_PREEMPTED}, got "
                  f"{p1.returncode}\n{log_tail}")
            return 1
        ledger = RunLedger(victim.workflow_dir / "ledger.jsonl")
        pre = ledger.preempted()
        if not pre:
            print(f"PREEMPT FAIL: exit 75 without a run_preempted ledger "
                  f"event\n{log_tail}")
            return 1
        print(f"      drained {pre.get('drained', 0)}/"
              f"{pre.get('in_flight', 0)} in-flight at "
              f"'{pre.get('step')}', abandoned {pre.get('abandoned', 0)} "
              f"({pre.get('reason')})")
        if args.artifacts:
            art = Path(args.artifacts)
            art.mkdir(parents=True, exist_ok=True)
            shutil.copy(ledger.path, art / "drained_ledger.jsonl")
            shutil.copy(log_path, art / "preempted_run.log")

        print("[3/3] fresh process resumes from the drained ledger")
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": str(REPO)}
        p2 = subprocess.run(
            [sys.executable, "-m", "tmlibrary_tpu.cli", "workflow",
             "submit", "--root", str(victim.root), "--resume",
             "--retry-delay", "0"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, timeout=600,
        )
        if p2.returncode != 0:
            print(f"PREEMPT FAIL: resume exited {p2.returncode}\n"
                  f"{p2.stdout[-3000:]}")
            return 1

        from tmlibrary_tpu.models.store import ExperimentStore

        resumed = ExperimentStore.open(victim.root)
        labels_ok = np.array_equal(
            resumed.read_labels(None, "nuclei"), ref_labels)
        got = resumed.read_features("nuclei").sort_values(
            ["site_index", "label"]).reset_index(drop=True)
        feats_ok = got.equals(ref_feats)
        print(f"      labels converged:   {labels_ok}")
        print(f"      features converged: {feats_ok}")
        if labels_ok and feats_ok:
            print("PREEMPT PASS: SIGTERM'd run + resume == "
                  "uninterrupted run")
            return 0
        print("PREEMPT FAIL: resumed store diverges from the reference")
        return 1


if __name__ == "__main__":
    sys.exit(main())
