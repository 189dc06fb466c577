"""The bench-regression sentinel: ``perf.compare_history`` verdicts and
the pinned exit codes of ``scripts/bench_regression.py`` (0 ok/improvement,
1 regression, 2 stale, 3 no baseline), plus the re-capture queue the
sentinel writes."""
import json
import os
import subprocess
import sys
import time

import pytest

from tmlibrary_tpu import perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SENTINEL = os.path.join(REPO, "scripts", "bench_regression.py")

NOW = 1_800_000_000.0


def _rec(value, config="3", metric="jterator_sites_per_sec_per_chip",
         backend="tpu", age_h=1.0, sweep=False, **extra):
    rec = {
        "metric": metric, "config": config, "backend": backend,
        "value": value, "recorded_at_unix": NOW - age_h * 3600.0,
        "recorded_at": f"{age_h}h ago",
    }
    if sweep:
        rec["sweep"] = True
    rec.update(extra)
    return rec


def _write(path, records):
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return str(path)


# ------------------------------------------------------- compare_history
def test_compare_improvement_and_ok():
    hist = [_rec(100.0, age_h=30), _rec(120.0, age_h=1)]
    v = perf.compare_history(hist, now=NOW)
    assert (v["status"], v["exit_code"]) == ("improvement", perf.EXIT_OK)
    assert v["delta_frac"] == pytest.approx(0.2)
    assert v["recapture"] == []

    hist = [_rec(100.0, age_h=30), _rec(99.0, age_h=1)]
    v = perf.compare_history(hist, now=NOW)
    assert (v["status"], v["exit_code"]) == ("ok", perf.EXIT_OK)


def test_compare_regression():
    hist = [_rec(100.0, age_h=30), _rec(80.0, age_h=1)]
    v = perf.compare_history(hist, now=NOW)
    assert (v["status"], v["exit_code"]) == ("regression",
                                             perf.EXIT_REGRESSION)
    assert v["delta_frac"] == pytest.approx(-0.2)
    assert v["recapture"] == ["bench:3"]
    assert v["baseline"]["value"] == 100.0


def test_compare_stale_and_regression_outranks_stale():
    hist = [_rec(100.0, age_h=300), _rec(99.0, age_h=200)]
    v = perf.compare_history(hist, stale_hours=72, now=NOW)
    assert (v["status"], v["exit_code"]) == ("stale", perf.EXIT_STALE)
    assert v["recapture"] == ["bench:3"]

    hist = [_rec(100.0, age_h=300), _rec(50.0, age_h=200)]
    v = perf.compare_history(hist, stale_hours=72, now=NOW)
    assert v["exit_code"] == perf.EXIT_REGRESSION  # more actionable


def test_compare_no_baseline():
    v = perf.compare_history([], now=NOW)
    assert v["exit_code"] == perf.EXIT_NO_BASELINE
    # a lone record has nothing comparable before it
    v = perf.compare_history([_rec(100.0)], now=NOW)
    assert (v["status"], v["exit_code"]) == ("no_baseline",
                                             perf.EXIT_NO_BASELINE)
    # backend classes never cross-judge: a CPU rehearsal is not a
    # baseline for a TPU number
    hist = [_rec(500.0, backend="cpu_forced"), _rec(100.0, backend="tpu")]
    v = perf.compare_history(hist, now=NOW)
    assert v["exit_code"] == perf.EXIT_NO_BASELINE


def test_compare_backend_class_collapse():
    # a forced CPU rehearsal is the same evidence class as a CPU run,
    # and never the chip's
    hist = [_rec(100.0, backend="cpu_forced", age_h=30),
            _rec(120.0, backend="cpu", age_h=1)]
    assert perf.compare_history(hist, now=NOW)["status"] == "improvement"
    hist = [_rec(100.0, backend="tpu", age_h=30),
            _rec(80.0, backend="cpu_forced", age_h=1)]
    assert perf.compare_history(hist, now=NOW)["status"] == "no_baseline"


def test_compare_filters_and_sweep_label():
    hist = [
        _rec(100.0, config="3", age_h=30),
        _rec(10.0, config="volume", metric="mv", age_h=20),
        _rec(5.0, config="volume", metric="mv", age_h=1, sweep=True),
    ]
    v = perf.compare_history(hist, config="volume", now=NOW)
    assert v["exit_code"] == perf.EXIT_REGRESSION
    assert v["recapture"] == ["sweep:volume"]  # sweep records re-sweep
    # error / non-positive records never participate
    hist = [_rec(100.0, age_h=30), _rec(0.0, age_h=2),
            {**_rec(1.0, age_h=1), "error": "device lost"}]
    v = perf.compare_history(hist, now=NOW)
    assert v["latest"]["value"] == 100.0


def test_compare_baseline_file_pool():
    baseline = [_rec(100.0, age_h=500)]
    hist = [_rec(80.0, age_h=1)]
    v = perf.compare_history(hist, baseline=baseline, now=NOW)
    assert v["exit_code"] == perf.EXIT_REGRESSION
    # in-history mode the same lone record would be no_baseline
    assert perf.compare_history(hist, now=NOW)["exit_code"] == \
        perf.EXIT_NO_BASELINE


# --------------------------------------------- CLI exit codes, pinned
def _run(args, **env):
    proc = subprocess.run(
        [sys.executable, SENTINEL, *args],
        env={**os.environ, **env}, capture_output=True, text=True,
        timeout=120,
    )
    return proc


def _fresh(age_h):
    """recorded_at_unix relative to real now (the CLI judges against
    wall-clock)."""
    return time.time() - age_h * 3600.0


def test_cli_exit_improvement(tmp_path):
    hist = _write(tmp_path / "h.jsonl", [
        {**_rec(100.0), "recorded_at_unix": _fresh(30)},
        {**_rec(120.0), "recorded_at_unix": _fresh(1)},
    ])
    proc = _run(["--history", hist, "--no-queue"])
    assert proc.returncode == 0, proc.stderr
    assert "improvement" in proc.stdout


def test_cli_exit_regression(tmp_path):
    hist = _write(tmp_path / "h.jsonl", [
        {**_rec(100.0), "recorded_at_unix": _fresh(30)},
        {**_rec(80.0), "recorded_at_unix": _fresh(1)},
    ])
    proc = _run(["--history", hist, "--no-queue"])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "regression" in proc.stdout
    assert "bench:3" in proc.stdout


def test_cli_exit_stale(tmp_path):
    hist = _write(tmp_path / "h.jsonl", [
        {**_rec(100.0), "recorded_at_unix": _fresh(300)},
        {**_rec(99.0), "recorded_at_unix": _fresh(200)},
    ])
    proc = _run(["--history", hist, "--no-queue"])
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "stale" in proc.stdout


def test_cli_exit_no_baseline(tmp_path):
    hist = _write(tmp_path / "h.jsonl",
                  [{**_rec(100.0), "recorded_at_unix": _fresh(1)}])
    proc = _run(["--history", hist, "--no-queue"])
    assert proc.returncode == 3, proc.stdout + proc.stderr


def test_cli_absent_and_empty_history_is_friendly_no_baseline(tmp_path):
    """A fresh checkout has no BENCH_HISTORY.jsonl at all (and a touched
    one is empty): both are the pinned exit 3 with a hint naming the
    file, not a crash or a confusing 'no comparable records'."""
    absent = str(tmp_path / "nowhere" / "BENCH_HISTORY.jsonl")
    proc = _run(["--history", absent, "--no-queue"])
    assert proc.returncode == 3, proc.stdout + proc.stderr
    assert "no_baseline" in proc.stdout
    assert "absent" in proc.stdout and absent in proc.stdout

    empty = tmp_path / "BENCH_HISTORY.jsonl"
    empty.touch()
    proc = _run(["--history", str(empty), "--no-queue"])
    assert proc.returncode == 3, proc.stdout + proc.stderr
    assert "empty" in proc.stdout
    # --json keeps the verdict machine-readable on the same path
    proc = _run(["--history", str(empty), "--no-queue", "--json"])
    assert proc.returncode == 3
    verdict = json.loads(proc.stdout)
    assert verdict["status"] == "no_baseline"
    assert verdict["history_records"] == 0
    # BENCH_HISTORY env routes the default path the same way
    proc = _run(["--no-queue"], BENCH_HISTORY=absent)
    assert proc.returncode == 3
    assert "absent" in proc.stdout


def test_workflow_status_survives_absent_bench_history(
        monkeypatch, tmp_path, capsys):
    """``tmx workflow status`` must render (exit 0) when the bench
    history is absent."""
    from tmlibrary_tpu.cli import main
    from tmlibrary_tpu.models.experiment import Experiment
    from tmlibrary_tpu.models.store import ExperimentStore

    placeholder = Experiment(name="e", plates=[], channels=[],
                             site_height=1, site_width=1)
    store = ExperimentStore.create(tmp_path / "e", placeholder)
    monkeypatch.setenv("BENCH_HISTORY",
                       str(tmp_path / "no" / "BENCH_HISTORY.jsonl"))
    assert main(["workflow", "status", "--root", str(store.root)]) == 0
    out = capsys.readouterr().out
    assert "bench records stale" not in out


def test_cli_baseline_file_and_json(tmp_path):
    baseline = _write(tmp_path / "b.jsonl",
                      [{**_rec(100.0), "recorded_at_unix": _fresh(500)}])
    hist = _write(tmp_path / "h.jsonl",
                  [{**_rec(150.0), "recorded_at_unix": _fresh(1)}])
    proc = _run(["--history", hist, "--baseline", baseline,
                 "--no-queue", "--json"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout)
    assert verdict["status"] == "improvement"
    assert verdict["baseline"]["value"] == 100.0
    # widened threshold turns a small dip into ok (the CI CPU smoke mode)
    hist2 = _write(tmp_path / "h2.jsonl",
                   [{**_rec(70.0), "recorded_at_unix": _fresh(1)}])
    proc = _run(["--history", hist2, "--baseline", baseline,
                 "--threshold", "0.5", "--no-queue"])
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_writes_recapture_queue(tmp_path):
    hist = _write(tmp_path / "h.jsonl", [
        {**_rec(100.0), "recorded_at_unix": _fresh(30)},
        {**_rec(80.0), "recorded_at_unix": _fresh(1)},
    ])
    queue = tmp_path / "RECAPTURE.json"
    proc = _run(["--history", hist, "--queue-out", str(queue)])
    assert proc.returncode == 1
    doc = json.loads(queue.read_text())
    assert doc["items"] == ["bench:3"]
    assert "regression" in doc["reason"]
