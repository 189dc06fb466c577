"""Readers of the program's two ledgers (the run ledger of an experiment
and the serve ledger of a spool): JSON lines, each sealed with a ``crc``
key the benchmark does not need.  Every reduction from ledger events to a
number that a per-layer metric reports lives here, so the readers under
``metrics/`` stay a few lines each and no later PR can move the
arithmetic."""

import json
from pathlib import Path

#: events that mean the run did not do what it reports (chip_smoke.py)
FORBIDDEN_EVENTS = ("backend_degraded", "batch_failed", "depth_clamped")

#: the pipelined executor's phases (``step_done.pipeline_stats.phases``)
PIPELINE_PHASES = ("prefetch_wait", "dispatch", "device_block", "persist")


def read_events(path) -> list:
    """Every parseable event of a ledger file, in file order; a torn or
    foreign line is skipped, as the program's own reader skips it."""
    events = []
    path = Path(path)
    if not path.exists():
        return events
    for line in path.read_text().splitlines():
        try:
            event = json.loads(line)
        except ValueError:
            continue
        if isinstance(event, dict):
            events.append(event)
    return events


def run_ledger(root) -> list:
    return read_events(Path(root) / "workflow" / "ledger.jsonl")


def serve_ledger(serve_root) -> list:
    events = []
    for path in sorted((Path(serve_root) / "serve").glob("ledger*.jsonl")):
        events.extend(read_events(path))
    events.sort(key=lambda e: e.get("ts", 0.0))
    return events


def step_seconds(events: list) -> dict:
    """``step_done.elapsed`` per step name."""
    out: dict = {}
    for e in events:
        if e.get("event") == "step_done":
            out[e["step"]] = out.get(e["step"], 0.0) + float(e["elapsed"])
    return out


def phase_seconds(events: list, step: str) -> dict:
    """``pipeline_stats`` phase totals of one step (seconds per phase)."""
    out = dict.fromkeys(PIPELINE_PHASES, 0.0)
    for e in events:
        if e.get("event") == "step_done" and e.get("step") == step:
            phases = (e.get("pipeline_stats") or {}).get("phases") or {}
            for name in PIPELINE_PHASES:
                out[name] += float((phases.get(name) or {}).get("total_s", 0))
    return out


def batch_results(events: list, step: str) -> list:
    return [e.get("result") or {} for e in events
            if e.get("event") == "batch_done" and e.get("step") == step]


def escalations(events: list) -> int:
    return sum(int(r.get("bucket_escalations", 0))
               for r in batch_results(events, "jterator"))


def resolved_by_the_engine(events: list) -> dict:
    """Batch size, depth and routed rungs as the engine resolved them —
    context for an earlier line, never a metric."""
    results = batch_results(events, "jterator")
    stats = {}
    for e in events:
        if e.get("event") == "step_done" and e.get("step") == "jterator":
            stats = e.get("pipeline_stats") or {}
    return {
        "batches": len(results),
        "batch_size": max((int(r.get("n_sites", 0)) for r in results),
                          default=0),
        "pipeline_depth": stats.get("depth"),
        "pipeline_depth_source": stats.get("source"),
        "routed_capacities": sorted({int(r["bucket_capacity"])
                                     for r in results
                                     if r.get("bucket_capacity")}),
        "bucket_escalations": escalations(events),
    }


def forbidden(events: list) -> list:
    return sorted({e["event"] for e in events
                   if e.get("event") in FORBIDDEN_EVENTS})


def spans(events: list) -> list:
    """``(name, t0, t1)`` of every span event, wall-clock seconds.  A run
    ledger's phase spans are named ``<step>/<phase>`` and its step spans
    ``<step>``; a serve ledger's keep their own name."""
    out = []
    for e in events:
        if e.get("event") != "span" or "t0" not in e:
            continue
        name, step = e.get("span"), e.get("step")
        if name in ("run", "batch"):
            continue
        if step:
            name = step if name == "step" else f"{step}/{name}"
        t0 = float(e["t0"])
        out.append((name, t0, t0 + float(e.get("elapsed", 0.0))))
    return out


def span_durations(events: list, name: str) -> list:
    return [float(e.get("elapsed", 0.0)) for e in events
            if e.get("event") == "span" and e.get("span") == name]
