"""Chrome-trace export (``tmlibrary_tpu/traceexport.py``,
``tmx trace --export chrome``).

Three ledger eras must all render as schema-valid Trace Event Format
documents: a seed-era ledger (no span events — slices synthesized from
``batch_done``/``step_done`` timing), a real depth-4 pipelined run (span
events nest run → step → batch → phase), and a two-host interleaved
serve ledger (one process row per host, one thread lane per tenant/job,
flow arrows linking enqueue → admit → execute per ``trace_id``).  The
validator itself is tested against documents that must fail.
"""

import json

import pytest

from test_workflow import (  # noqa: F401 — fixture re-export
    make_description,
    source_dir,
    store,
    synth_site_image,
)

from tmlibrary_tpu import telemetry, traceexport
from tmlibrary_tpu.workflow.engine import Workflow


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset_registry(enabled=True)
    yield
    telemetry.reset_registry()


def _slices(doc):
    return [e for e in doc["traceEvents"] if e["ph"] == "X"]


def _flows(doc):
    return [e for e in doc["traceEvents"] if e["ph"] in ("s", "t", "f")]


def _meta(doc, name):
    return [e for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == name]


# ------------------------------------------------------------ seed era
def test_seed_era_ledger_synthesizes_slices():
    """A pre-telemetry ledger (no span events at all) still exports:
    slices come from batch_done/step_done ts-elapsed windows."""
    events = [
        {"ts": 100.0, "event": "run_started"},
        {"ts": 100.5, "event": "init_done", "step": "jterator",
         "n_batches": 2},
        {"ts": 103.0, "event": "batch_done", "step": "jterator",
         "batch": 0, "elapsed": 2.0},
        {"ts": 105.0, "event": "batch_done", "step": "jterator",
         "batch": 1, "elapsed": 2.0},
        {"ts": 105.5, "event": "step_done", "step": "jterator",
         "elapsed": 5.0},
    ]
    doc = traceexport.chrome_trace(events)
    assert traceexport.validate_chrome_trace(doc) == []
    names = {e["name"] for e in _slices(doc)}
    assert names == {"batch:0", "batch:1", "step:jterator"}
    # synthesized start = ts - elapsed, in microseconds
    b0 = next(e for e in _slices(doc) if e["name"] == "batch:0")
    assert b0["ts"] == pytest.approx(101.0 * 1e6)
    assert b0["dur"] == pytest.approx(2.0 * 1e6)


def test_span_events_suppress_synthesis_for_covered_steps():
    """When a step has real step/batch spans, its batch_done/step_done
    events must NOT also synthesize slices (no double-rendering)."""
    events = [
        {"ts": 101.0, "event": "span", "span": "batch",
         "step": "jterator", "batch": 0, "t0": 100.0, "elapsed": 1.0},
        {"ts": 101.1, "event": "batch_done", "step": "jterator",
         "batch": 0, "elapsed": 1.0},
        {"ts": 103.0, "event": "span", "span": "step", "step": "jterator",
         "t0": 100.0, "elapsed": 3.0},
        {"ts": 103.1, "event": "step_done", "step": "jterator",
         "elapsed": 3.0},
        # a step WITHOUT span coverage still synthesizes
        {"ts": 110.0, "event": "step_done", "step": "legacy",
         "elapsed": 2.0},
    ]
    doc = traceexport.chrome_trace(events)
    assert traceexport.validate_chrome_trace(doc) == []
    names = sorted(e["name"] for e in _slices(doc))
    assert names == ["batch", "step", "step:legacy"]


# ------------------------------------------------------- real engine run
def test_depth4_pipelined_run_exports_valid_trace(source_dir, store):
    """A real depth-4 pipelined run's ledger renders as a schema-valid
    document whose slices cover run/step/batch and the pipeline phases."""
    desc = make_description(source_dir, store)
    for stage in desc.stages:
        for step in stage.steps:
            if step.name == "jterator":
                step.args["batch_size"] = 4  # 16 sites -> 4 batches
    wf = Workflow(store, desc, pipeline_depth=4)
    wf.run()

    out = store.root / "trace.json"
    doc = traceexport.export_chrome_trace(store.root, out)
    assert out.exists() and json.loads(out.read_text()) == doc
    assert traceexport.validate_chrome_trace(doc) == []
    names = {e["name"] for e in _slices(doc)}
    assert {"run", "step", "batch", "dispatch", "device_block",
            "persist"} <= names
    batches = [e for e in _slices(doc) if e["name"] == "batch"
               and e["args"].get("step") == "jterator"]
    assert len(batches) == 4
    # one process row (single host), named via metadata
    assert len(_meta(doc, "process_name")) == 1


# ------------------------------------------------------------- serve era
def _serve_events():
    """Two hosts' serve ledgers interleaved: h0 runs tenant-a job a-1
    (trace t-aaa), h1 runs tenant-b job b-1 (trace t-bbb)."""
    def job(host, job_id, tenant, tid, base):
        return [
            {"host": host, "ts": base + 0.1, "event": "span",
             "span": "spool_pickup", "t0": base, "elapsed": 0.1,
             "job": job_id},
            {"host": host, "ts": base + 0.2, "event": "span",
             "span": "admission", "t0": base + 0.1, "elapsed": 0.1,
             "trace_id": tid, "job": job_id, "tenant": tenant},
            {"host": host, "ts": base + 0.2, "event": "job_admitted",
             "job": job_id, "tenant": tenant, "trace_id": tid,
             "queue_wait_s": 0.2},
            {"host": host, "ts": base + 0.2, "event": "span",
             "span": "queue_wait", "t0": base, "elapsed": 0.2,
             "trace_id": tid, "job": job_id, "tenant": tenant},
            {"host": host, "ts": base + 0.5, "event": "span",
             "span": "sched_delay", "t0": base + 0.2, "elapsed": 0.3,
             "trace_id": tid, "job": job_id, "tenant": tenant},
            {"host": host, "ts": base + 0.5, "event": "job_started",
             "job": job_id, "tenant": tenant, "trace_id": tid,
             "sched_delay_s": 0.3},
            {"host": host, "ts": base + 2.5, "event": "span", "span": "job",
             "t0": base + 0.5, "elapsed": 2.0, "trace_id": tid,
             "job": job_id, "tenant": tenant},
            {"host": host, "ts": base + 2.5, "event": "job_done",
             "job": job_id, "tenant": tenant, "trace_id": tid,
             "elapsed_s": 2.0},
        ]

    evs = job("h0", "a-1", "a", "t-aaa", 1000.0) \
        + job("h1", "b-1", "b", "t-bbb", 1000.05)
    return sorted(evs, key=lambda e: e["ts"])


def test_two_host_serve_ledger_rows_and_flows():
    doc = traceexport.chrome_trace(_serve_events())
    assert traceexport.validate_chrome_trace(doc) == []
    # one process row per host
    hosts = {m["args"]["name"] for m in _meta(doc, "process_name")}
    assert hosts == {"h0", "h1"}
    # tenant/job lanes named via thread metadata
    lanes = {m["args"]["name"] for m in _meta(doc, "thread_name")}
    assert {"a/a-1", "b/b-1"} <= lanes
    # job lifecycle renders as instants
    instants = {e["name"] for e in doc["traceEvents"] if e["ph"] == "i"}
    assert {"job_admitted", "job_started", "job_done"} <= instants
    # flow arrows: one chain per trace_id, queue_wait -> sched_delay -> job
    flows = _flows(doc)
    ids = {e["id"] for e in flows}
    assert len(ids) == 2
    for fid in ids:
        chain = sorted((e for e in flows if e["id"] == fid),
                       key=lambda e: e["ts"])
        assert [e["ph"] for e in chain] == ["s", "t", "f"]
        assert chain[-1]["bp"] == "e"


def test_flow_chain_links_enqueue_admit_execute_anchor_times():
    """Each flow arrow binds to its anchor slice's start instant, so the
    chain reads enqueue (queue_wait start = submit time) -> admit
    (sched_delay start) -> execute (job start)."""
    doc = traceexport.chrome_trace(_serve_events(), trace_id="t-aaa")
    assert traceexport.validate_chrome_trace(doc) == []
    (fid,) = {e["id"] for e in _flows(doc)}
    chain = sorted((e for e in _flows(doc) if e["id"] == fid),
                   key=lambda e: e["ts"])
    assert [e["ts"] for e in chain] == [
        pytest.approx(1000.0 * 1e6),   # queue_wait starts at submit
        pytest.approx(1000.2 * 1e6),   # sched_delay starts at admit
        pytest.approx(1000.5 * 1e6),   # job starts at execute
    ]


def test_trace_id_filter_drops_other_and_unlabeled_events():
    events = _serve_events() + [
        {"host": "h0", "ts": 1500.0, "event": "span", "span": "compile",
         "t0": 1499.0, "elapsed": 1.0}  # unlabeled: not in any trace
    ]
    doc = traceexport.chrome_trace(events, trace_id="t-bbb")
    args = [e.get("args", {}) for e in _slices(doc)]
    assert args and all(a.get("trace_id") == "t-bbb" for a in args)
    assert doc["otherData"]["trace_id"] == "t-bbb"


def test_multihost_duplicate_events_dedup():
    """The same host's ledger read twice (fleet merge copies) must not
    double-render slices."""
    events = _serve_events()
    doc_once = traceexport.chrome_trace(events)
    doc_twice = traceexport.chrome_trace(events + events)
    assert len(_slices(doc_once)) == len(_slices(doc_twice))
    assert len(_flows(doc_once)) == len(_flows(doc_twice))


# ------------------------------------------------------------ collection
def test_collect_events_follows_serve_spool_to_experiment_ledgers(
        tmp_path):
    """A serve root's export merges the serve ledger with every
    experiment ledger the spooled specs reference — enqueue→result from
    ledgers alone (done envelopes wrap the spec under 'job')."""
    from tmlibrary_tpu import serve
    from tmlibrary_tpu.workflow.engine import RunLedger

    sroot = tmp_path / "srv"
    serve.serve_dir(sroot).mkdir(parents=True)
    sl = RunLedger(serve.ledger_path(sroot), host="h0")
    sl.append(event="serve_started", recovered=0)
    sl.append(event="job_done", job="a-1", tenant="a", trace_id="t-1",
              elapsed_s=1.0)

    exp_root = tmp_path / "exp"
    (exp_root / "workflow").mkdir(parents=True)
    el = RunLedger(exp_root / "workflow" / "ledger.jsonl", host="h0")
    el.append(event="span", span="run", t0=1.0, elapsed=2.0,
              trace_id="t-1", job="a-1", tenant="a")

    done = serve.spool_dir(sroot, "done")
    done.mkdir(parents=True)
    (done / "a-1.json").write_text(json.dumps(
        {"job": {"job_id": "a-1", "root": str(exp_root), "tenant": "a"},
         "elapsed_s": 1.0}))

    events = traceexport.collect_events(sroot)
    kinds = {e.get("event") for e in events}
    assert "serve_started" in kinds and "span" in kinds
    # and a ledger FILE works directly too
    direct = traceexport.collect_events(
        exp_root / "workflow" / "ledger.jsonl")
    assert [e["event"] for e in direct] == ["span"]


# ------------------------------------------------------------- validator
def test_validator_rejects_malformed_documents():
    assert traceexport.validate_chrome_trace(
        "nope") == ["document is not an object"]
    assert traceexport.validate_chrome_trace(
        {}) == ["traceEvents missing or not a list"]
    bad = {"traceEvents": [
        {"ph": "Z", "pid": 1, "tid": 1, "ts": 0, "name": "x"},
        {"ph": "X", "pid": "one", "tid": 1, "ts": 0, "dur": 1,
         "name": "x"},
        {"ph": "X", "pid": 1, "tid": 1, "ts": -5, "dur": 1, "name": "x"},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "name": "x"},  # no dur
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 1, "name": ""},
        {"ph": "s", "pid": 1, "tid": 1, "ts": 0, "name": "f"},  # no id
        {"ph": "s", "pid": 1, "tid": 1, "ts": 0, "name": "f", "id": 9},
        # flow id 9 never finishes -> unmatched chain
    ]}
    problems = traceexport.validate_chrome_trace(bad)
    assert len(problems) >= 6
    assert any("unknown ph" in p for p in problems)
    assert any("pid" in p for p in problems)
    assert any("negative" in p for p in problems)
    assert any("dur" in p for p in problems)
    assert any("unnamed" in p for p in problems)
    assert any("without id" in p for p in problems)
    assert any("exactly one start" in p for p in problems)


def test_export_raises_on_invalid_document(tmp_path, monkeypatch):
    """A broken render must never land silently on disk."""
    monkeypatch.setattr(traceexport, "chrome_trace",
                        lambda *a, **k: {"traceEvents": [{"ph": "?"}]})
    with pytest.raises(ValueError, match="schema validation"):
        traceexport.export_chrome_trace(tmp_path, tmp_path / "out.json")
    assert not (tmp_path / "out.json").exists()


def test_worker_thread_spans_get_their_own_lane_and_nest_by_time():
    """Spans that closed off the engine thread carry ``thread``: each
    such thread is a row of its own, where a child (``escalate`` inside
    ``persist``) nests by time; the engine thread's spans stay on the
    run's row, so a persist that overlaps the next dispatch is drawn
    beside it and not inside it."""
    def sp(span, t0, elapsed, ts, **kw):
        return {"host": "h0", "event": "span", "span": span, "t0": t0,
                "elapsed": elapsed, "ts": ts, "step": "jterator",
                "batch": 0, **kw}

    events = [
        sp("dispatch", 10.0, 0.5, 10.5, parent="step",
           thread="MainThread"),
        sp("persist", 10.2, 3.0, 13.2, thread="tmx-persist_0"),
        sp("escalate", 10.4, 2.0, 12.4, parent="persist", capacity=16,
           thread="tmx-persist_0"),
        sp("load", 10.1, 0.3, 10.4, thread="tmx-prefetch_1"),
        sp("step", 9.0, 5.0, 14.0, parent="run"),
    ]
    doc = traceexport.chrome_trace(events)
    assert traceexport.validate_chrome_trace(doc) == []
    slices = {e["name"]: e for e in _slices(doc)}
    lanes = {m["tid"]: m["args"]["name"] for m in doc["traceEvents"]
             if m.get("name") == "thread_name"}
    assert lanes[slices["persist"]["tid"]] == "tmx-persist_0"
    assert slices["escalate"]["tid"] == slices["persist"]["tid"]
    assert lanes[slices["load"]["tid"]] == "tmx-prefetch_1"
    assert slices["dispatch"]["tid"] == slices["step"]["tid"]
    assert lanes[slices["step"]["tid"]] == "run"
    # the child lies inside its parent on their shared row
    p, c = slices["persist"], slices["escalate"]
    assert p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"]
    assert c["args"]["parent"] == "persist"
    assert c["args"]["capacity"] == 16
