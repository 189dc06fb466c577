"""Unified telemetry layer (``tmlibrary_tpu/telemetry.py``).

Four layers of guarantees:

- Instrument/registry mechanics: counters, gauges, bounded-reservoir
  histograms, throughput trackers, label keying, the null-instrument
  zero-cost path, and span nesting/emission.
- Export surfaces: Prometheus textfile output is parse-checked (a
  malformed exposition would silently break a node_exporter textfile
  collector), JSON carries the same numbers, and the ledger→metrics
  derivation works on seed-era ledgers that predate telemetry.
- Engine integration: a telemetry-enabled jterator run is bit-identical
  to a disabled one (the property that makes telemetry safe to ship on
  by default), and a depth-4 pipelined run's span events reconstruct the
  per-phase critical path shown in ``pipeline_stats``.
- Operational plumbing: resource sampler + heartbeat file, stale-run
  detection in ``tmx workflow status``, the ``RunLedger.events()`` cache,
  ``device_trace`` lifecycle, and the ``warn_once`` reset hook.
"""

import json
import logging
import os
import time

import numpy as np
import pytest

from test_workflow import (  # noqa: F401 — fixture re-export
    make_description,
    source_dir,
    store,
    synth_site_image,
)

from tmlibrary_tpu import log as tm_log
from tmlibrary_tpu import telemetry
from tmlibrary_tpu.workflow.engine import RunLedger, Workflow


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Each test gets a fresh enabled registry; the process-global one is
    restored to config defaults afterwards so no test leaks state."""
    telemetry.reset_registry(enabled=True)
    yield
    telemetry.reset_registry()


# ------------------------------------------------------------- instruments
def test_counter_gauge_basics():
    reg = telemetry.MetricsRegistry(enabled=True)
    c = reg.counter("tmx_things_total", step="jterator")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    # same (name, labels) -> same instrument; different labels -> distinct
    assert reg.counter("tmx_things_total", step="jterator") is c
    assert reg.counter("tmx_things_total", step="corilla") is not c

    g = reg.gauge("tmx_level")
    g.set(7.0)
    g.inc(-2.0)
    assert g.value == 5.0


def test_histogram_exact_and_sampled_stats():
    reg = telemetry.MetricsRegistry(enabled=True)
    h = reg.histogram("tmx_batch_seconds")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100
    assert h.sum == pytest.approx(5050.0)
    assert h.max == 100.0
    assert h.quantile(0.5) == pytest.approx(50.0, abs=2.0)
    assert h.quantile(0.95) == pytest.approx(95.0, abs=2.0)
    s = h.summary()
    assert set(s) >= {"count", "sum", "max", "p50", "p95"}


def test_histogram_reservoir_bounded_but_exact_aggregates():
    h = telemetry.Histogram("h", {})
    n = telemetry.RESERVOIR_SIZE * 3
    for v in range(n):
        h.observe(float(v))
    # aggregates stay exact past the reservoir bound
    assert h.count == n
    assert h.max == float(n - 1)
    assert h.sum == pytest.approx(n * (n - 1) / 2)


def test_throughput_tracker_matches_bench_math():
    reg = telemetry.MetricsRegistry(enabled=True)
    t = reg.throughput("tmx_tiles_per_sec")
    t.add(10, 2.0)
    t.add(30, 2.0)
    # cumulative units / cumulative seconds, like bench.py's sites/sec
    assert reg.gauge("tmx_tiles_per_sec").value == pytest.approx(10.0)
    assert reg.counter("tmx_tiles_per_sec_units_total").value == 40.0


def test_disabled_registry_returns_shared_null():
    reg = telemetry.MetricsRegistry(enabled=False)
    c = reg.counter("x")
    assert c is reg.gauge("y") is reg.histogram("z") is reg.throughput("w")
    # the null instrument accepts every instrument verb silently
    c.inc()
    c.set(1.0)
    c.observe(2.0)
    c.add(3, 1.0)
    assert reg.snapshot() == {"counters": [], "gauges": [], "histograms": []}


def test_snapshot_shape_and_ordering():
    reg = telemetry.MetricsRegistry(enabled=True)
    reg.counter("b_total").inc()
    reg.counter("a_total").inc(2)
    reg.gauge("g", step="s").set(1.5)
    reg.histogram("h").observe(0.25)
    snap = reg.snapshot()
    assert [c["name"] for c in snap["counters"]] == ["a_total", "b_total"]
    assert snap["gauges"] == [{"name": "g", "labels": {"step": "s"},
                              "value": 1.5}]
    (h,) = snap["histograms"]
    assert h["count"] == 1 and h["sum"] == pytest.approx(0.25)


# ------------------------------------------------------------------- spans
def test_span_emits_ledger_event_with_its_parent():
    events = []
    with telemetry.span("run", emit=lambda **kw: events.append(kw)):
        with telemetry.span("step", emit=lambda **kw: events.append(kw),
                            step="jterator"):
            pass
    assert [e["span"] for e in events] == ["step", "run"]  # inner exits first
    assert events[0]["parent"] == "run"
    assert events[0]["step"] == "jterator"
    assert "parent" not in events[1]
    for e in events:
        assert e["event"] == "span"
        assert e["elapsed"] >= 0.0
        assert e["t0"] > 0.0


def test_span_zero_cost_when_disabled():
    telemetry.set_enabled(False)
    events = []
    with telemetry.span("run", emit=lambda **kw: events.append(kw)):
        pass
    assert events == []


def test_span_emit_failure_does_not_raise():
    def boom(**kw):
        raise OSError("disk full")

    with telemetry.span("run", emit=boom):
        pass  # must not propagate


# ------------------------------------------------------------------ export
def test_prometheus_render_parses_and_round_trips():
    reg = telemetry.MetricsRegistry(enabled=True)
    reg.counter("tmx_batches_done_total", step="jterator").inc(4)
    reg.gauge("tmx_pipeline_depth", step="jterator").set(4)
    h = reg.histogram("tmx_batch_seconds", step="jterator")
    h.observe(0.5)
    h.observe(1.5)
    text = telemetry.render_prometheus(reg.snapshot())
    assert "# TYPE tmx_batches_done_total counter" in text
    assert "# TYPE tmx_batch_seconds summary" in text
    samples = telemetry.parse_prometheus(text)
    by_name = {(n, tuple(sorted(lbl.items()))): v for n, lbl, v in samples}
    assert by_name[("tmx_batches_done_total",
                    (("step", "jterator"),))] == 4.0
    assert by_name[("tmx_batch_seconds_count",
                    (("step", "jterator"),))] == 2.0
    assert by_name[("tmx_batch_seconds_sum",
                    (("step", "jterator"),))] == pytest.approx(2.0)
    quantiles = [v for n, lbl, v in samples
                 if n == "tmx_batch_seconds" and "quantile" in lbl]
    assert quantiles  # summary carries its quantile samples


def test_prometheus_label_escaping():
    reg = telemetry.MetricsRegistry(enabled=True)
    reg.counter("tmx_odd_total", step='we"ird\\path\nx').inc()
    samples = telemetry.parse_prometheus(
        telemetry.render_prometheus(reg.snapshot())
    )
    (sample,) = [s for s in samples if s[0] == "tmx_odd_total"]
    assert sample[1]["step"] == 'we"ird\\path\nx'


def test_parse_prometheus_rejects_malformed():
    with pytest.raises(ValueError):
        telemetry.parse_prometheus("this is not an exposition line\n")


def test_json_render_equivalent_to_snapshot():
    reg = telemetry.MetricsRegistry(enabled=True)
    reg.counter("tmx_runs_total").inc()
    reg.gauge("tmx_rss").set(123.0)
    snap = reg.snapshot()
    assert json.loads(telemetry.render_json(snap)) == snap


# -------------------------------------------------- ledger → metrics/trace
def _seed_era_events():
    """A hand-built pre-telemetry ledger: no span events at all."""
    return [
        {"event": "run_started", "t": 1.0},
        {"event": "init_done", "step": "jterator", "n_batches": 2},
        {"event": "batch_done", "step": "jterator", "batch": 0,
         "elapsed": 2.0, "attempts": 2, "result": {"n_sites": 8}},
        {"event": "batch_done", "step": "jterator", "batch": 1,
         "elapsed": 2.0, "result": {"n_sites": 8}},
        {"event": "batch_failed", "step": "jterator", "batch": 2,
         "error": "boom"},
        {"event": "step_partial", "step": "jterator", "elapsed": 5.0,
         "quarantined": [2],
         "pipeline_stats": {"depth": 4, "source": "cli", "n_batches": 2,
                            "phases": {"dispatch": {"total_s": 1.0,
                                                    "max_s": 0.6},
                                       "persist": {"total_s": 3.0,
                                                   "max_s": 1.8}}}},
    ]


def test_registry_from_seed_era_ledger():
    reg = telemetry.registry_from_ledger(_seed_era_events())
    assert reg.counter("tmx_runs_total").value == 1.0
    assert reg.counter("tmx_batches_done_total", step="jterator").value == 2.0
    assert reg.counter("tmx_batch_retries_total", step="jterator").value == 1.0
    assert reg.counter("tmx_batches_failed_total", step="jterator").value == 1.0
    assert reg.counter("tmx_batches_quarantined_total",
                       step="jterator").value == 1.0
    assert reg.counter("tmx_steps_partial_total", step="jterator").value == 1.0
    assert reg.gauge("tmx_pipeline_depth", step="jterator").value == 4.0
    assert reg.gauge("tmx_pipeline_phase_seconds_total", step="jterator",
                     phase="persist").value == 3.0
    # 16 sites over 4.0s of batch time
    assert reg.gauge("tmx_step_units_per_sec",
                     step="jterator").value == pytest.approx(4.0)
    # and the derived registry renders a VALID exposition
    telemetry.parse_prometheus(telemetry.render_prometheus(reg.snapshot()))


def test_span_tree_from_seed_era_ledger_uses_event_timings():
    tree = telemetry.annotate_critical_path(
        telemetry.build_span_tree(_seed_era_events())
    )
    (step_node,) = tree["children"]
    assert step_node["name"] == "step:jterator"
    assert step_node["elapsed"] == pytest.approx(5.0)
    batch_names = {c["name"] for c in step_node["children"]}
    assert batch_names >= {"batch:0", "batch:1"}
    assert tree["critical"] and step_node["critical"]


def test_critical_path_marks_longest_child_per_level():
    events = [
        {"event": "span", "span": "run", "elapsed": 10.0},
        {"event": "span", "span": "step", "step": "a", "elapsed": 2.0},
        {"event": "span", "span": "step", "step": "b", "elapsed": 8.0},
        {"event": "span", "span": "batch", "step": "b", "batch": 0,
         "elapsed": 8.0},
        {"event": "span", "span": "dispatch", "step": "b", "batch": 0,
         "elapsed": 1.0},
        {"event": "span", "span": "device_block", "step": "b", "batch": 0,
         "elapsed": 6.0},
    ]
    tree = telemetry.annotate_critical_path(telemetry.build_span_tree(events))
    by_name = {c["name"]: c for c in tree["children"]}
    assert not by_name["step:a"]["critical"]
    step_b = by_name["step:b"]
    assert step_b["critical"]
    (batch,) = step_b["children"]
    assert batch["critical"]
    phase_flags = {c["name"]: c["critical"] for c in batch["children"]}
    assert phase_flags == {"phase:dispatch": False,
                           "phase:device_block": True}
    rendered = telemetry.render_span_tree(tree)
    assert rendered.splitlines()[0].startswith("*")
    assert telemetry.phase_totals(events) == {
        "dispatch": 1.0, "device_block": 6.0}


# ------------------------------------------------- sampler + heartbeat
def test_heartbeat_roundtrip_and_age(tmp_path):
    hb_path = tmp_path / telemetry.HEARTBEAT_FILENAME
    telemetry.write_heartbeat(hb_path, period=2.0, extra={"rss_bytes": 42})
    hb = telemetry.read_heartbeat(hb_path)
    assert hb["period"] == 2.0
    assert hb["rss_bytes"] == 42
    age = telemetry.heartbeat_age(hb_path)
    assert 0.0 <= age < 5.0
    # stale relative to an artificial 'now' — the fresher-of rule takes
    # the file mtime (written a hair after the embedded ts), so the age
    # is ~100s, not exactly 100s
    assert telemetry.heartbeat_age(hb_path, now=hb["ts"] + 100) == \
        pytest.approx(100.0, abs=1.0)
    assert telemetry.read_heartbeat(tmp_path / "missing.json") is None


def test_resource_sampler_sets_gauges_and_heartbeat(tmp_path):
    reg = telemetry.MetricsRegistry(enabled=True)
    hb_path = tmp_path / "hb.json"
    sampler = telemetry.ResourceSampler(
        period=0.5, heartbeat_path=hb_path, registry=reg
    )
    sample = sampler.sample_once()
    assert sample["rss_bytes"] > 0
    assert reg.gauge("tmx_process_rss_bytes").value > 0
    assert reg.gauge("tmx_process_open_fds").value > 0
    hb = telemetry.read_heartbeat(hb_path)
    assert hb["rss_bytes"] == sample["rss_bytes"]
    assert hb["period"] == 0.5


def test_resource_sampler_thread_lifecycle(tmp_path):
    reg = telemetry.MetricsRegistry(enabled=True)
    hb_path = tmp_path / "hb.json"
    with telemetry.ResourceSampler(0.05, hb_path, reg) as sampler:
        deadline = time.time() + 2.0
        while not hb_path.exists() and time.time() < deadline:
            time.sleep(0.01)
        assert hb_path.exists()
    assert sampler._thread is None  # stopped and joined


# ---------------------------------------------------- ledger events cache
def test_ledger_events_cached_until_append(tmp_path):
    path = tmp_path / "ledger.jsonl"
    ledger = RunLedger(path)
    ledger.append(event="run_started")
    ledger.append(event="init_done", step="s", n_batches=1)
    first = ledger.events()
    assert ledger.events() is first  # cache hit: same parsed list
    ledger.append(event="batch_done", step="s", batch=0)
    second = ledger.events()
    assert second is not first
    assert len(second) == 3


def test_ledger_events_cache_detects_external_writes(tmp_path):
    path = tmp_path / "ledger.jsonl"
    ledger = RunLedger(path)
    ledger.append(event="run_started")
    assert len(ledger.events()) == 1
    # another process appends behind our back (resume from a second CLI)
    with path.open("a") as fh:
        fh.write(json.dumps({"event": "step_done", "step": "s"}) + "\n")
    events = ledger.events()
    assert len(events) == 2
    assert events[-1]["event"] == "step_done"


# --------------------------------------------------------- device_trace
def test_device_trace_none_is_noop(monkeypatch):
    from tmlibrary_tpu import profiling

    def explode(*a, **kw):  # jax.profiler must not be touched
        raise AssertionError("profiler invoked for log_dir=None")

    monkeypatch.setattr("jax.profiler.trace", explode)
    with profiling.device_trace(None):
        pass


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the names
    entered, as a trace would hold them."""

    entered: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.entered.append(self.name)

    def __exit__(self, *exc):
        return False


def test_device_trace_creates_dir_and_spans_annotate_inside_it(
        tmp_path, monkeypatch):
    """No bridge to toggle: a span is a trace annotation in any trace,
    the one ``--profile`` starts included."""
    from tmlibrary_tpu import profiling

    calls = []

    class FakeTrace:
        def __init__(self, path):
            calls.append(("init", path))

        def __enter__(self):
            calls.append(("enter",))

        def __exit__(self, *exc):
            calls.append(("exit",))
            return False

    monkeypatch.setattr("jax.profiler.trace", FakeTrace)
    monkeypatch.setattr("jax.profiler.TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.entered = []
    log_dir = tmp_path / "trace" / "run1"
    with profiling.device_trace(log_dir):
        assert log_dir.is_dir()
        with telemetry.span("outer"), telemetry.span_scope(step="corilla"), \
                telemetry.span("step"), telemetry.span("scan"):
            pass
    assert calls == [("init", str(log_dir)), ("enter",), ("exit",)]
    # named as the benchmark names a ledger span: <step>/<span>
    assert _FakeAnnotation.entered == ["outer", "corilla", "corilla/scan"]
    telemetry.drain_spans()


def test_spans_annotate_a_trace_somebody_else_started(monkeypatch):
    """Outside ``device_trace`` too (the benchmark starts its own trace),
    and an error in the body still closes the annotation and the span."""
    monkeypatch.setattr("jax.profiler.TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.entered = []
    telemetry.drain_spans()
    with pytest.raises(RuntimeError):
        with telemetry.span("work"):
            raise RuntimeError("body failed")
    assert _FakeAnnotation.entered == ["work"]
    assert [r["span"] for r in telemetry.drain_spans()] == ["work"]
    assert telemetry._span_stack() == []


# ----------------------------------------------------------- warn_once
def test_warn_once_reset_reopens_suppression(caplog):
    logger = logging.getLogger("tmx.test.warn_once")
    with caplog.at_level(logging.WARNING, logger=logger.name):
        tm_log.warn_once(logger, "k1", "first %s", "warning")
        tm_log.warn_once(logger, "k1", "first %s", "warning")
        assert len(caplog.records) == 1
        tm_log.reset_warned()
        tm_log.warn_once(logger, "k1", "first %s", "warning")
        assert len(caplog.records) == 2


# ---------------------------------------------------- engine integration
def _read_features_sorted(st, name):
    return (st.read_features(name)
            .sort_values(["site_index", "label"])
            .reset_index(drop=True))


def test_jterator_bit_identical_with_telemetry_on_and_off(source_dir, store):
    """The property that makes telemetry safe to ship enabled: the
    instrumented run persists exactly the same label stacks and feature
    tables as a run with the registry disabled."""
    import pandas.testing

    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    for name in ("metaconfig", "imextract", "corilla"):
        sd = next(s for stage in desc.stages for s in stage.steps
                  if s.name == name)
        step = get_step(name)(store)
        step.init(sd.args)
        for j in step.list_batches():
            step.run(j)
    jd = next(s for stage in desc.stages for s in stage.steps
              if s.name == "jterator")

    telemetry.reset_registry(enabled=True)
    jt = get_step("jterator")(store)
    jt.init(jd.args)
    for j in jt.list_batches():
        jt.run(j)
    on_labels = store.read_labels(None, "nuclei").copy()
    on_feats = _read_features_sorted(store, "nuclei")
    # the instrumented run actually recorded throughput
    reg = telemetry.get_registry()
    assert reg.counter("tmx_jterator_sites_total").value == 16.0
    assert reg.gauge("tmx_jterator_sites_per_sec").value > 0.0

    telemetry.reset_registry(enabled=False)
    jt2 = get_step("jterator")(store)
    jt2.delete_previous_output()
    jt2.init(jd.args)
    for j in jt2.list_batches():
        jt2.run(j)
    assert np.array_equal(store.read_labels(None, "nuclei"), on_labels)
    pandas.testing.assert_frame_equal(
        _read_features_sorted(store, "nuclei"), on_feats
    )


def test_depth4_run_spans_reconstruct_pipeline_critical_path(
        source_dir, store):
    """Acceptance: a depth-4 pipelined run's span events sum to the same
    per-phase totals as ``pipeline_stats``, the span tree nests
    run → step → batch → phase, and ``tmx metrics``/``tmx trace`` export
    from the live artifacts."""
    from tmlibrary_tpu.cli import main

    desc = make_description(source_dir, store)
    for stage in desc.stages:
        for step in stage.steps:
            if step.name == "jterator":
                step.args["batch_size"] = 4  # 16 sites -> 4 batches
    wf = Workflow(store, desc, pipeline_depth=4)
    wf.run()
    events = wf.ledger.events()

    # pipeline_stats per-phase totals vs summed phase spans
    (done,) = [e for e in events if e.get("event") == "step_done"
               and e.get("step") == "jterator"]
    ps = done["pipeline_stats"]
    assert ps["depth"] == 4 and ps["n_batches"] == 4
    totals = telemetry.phase_totals(
        e for e in events if e.get("step") == "jterator"
    )
    for phase, vals in ps["phases"].items():
        assert totals[phase] == pytest.approx(vals["total_s"], abs=1e-3), \
            f"span sum for {phase} diverged from pipeline_stats"

    # span tree: run -> step -> batch -> phase with one critical chain
    tree = telemetry.annotate_critical_path(telemetry.build_span_tree(events))
    jt_node = next(c for c in tree["children"]
                   if c["name"] == "step:jterator")
    batch_nodes = [c for c in jt_node["children"]
                   if c["name"].startswith("batch:")]
    assert len(batch_nodes) == 4
    for bn in batch_nodes:
        phases = {c["name"].removeprefix("phase:") for c in bn["children"]}
        assert phases >= {"dispatch", "device_block", "persist"}
    crit_batch = [b for b in batch_nodes if b["critical"]]
    assert len(crit_batch) == 1
    assert sum(c["critical"] for c in crit_batch[0]["children"]) == 1

    # live-run export surfaces: snapshot file, prom + json, trace
    snap_path = store.workflow_dir / "metrics.json"
    assert snap_path.exists()
    prom_file = store.root / "metrics.prom"
    assert main(["metrics", "--root", str(store.root),
                 "--out", str(prom_file)]) == 0
    samples = telemetry.parse_prometheus(prom_file.read_text())
    by_key = {(n, lbl.get("step")): v for n, lbl, v in samples}
    assert by_key.get(("tmx_batches_done_total", "jterator")) == 4.0
    assert by_key.get(("tmx_runs_total", None)) == 1.0
    json_file = store.root / "metrics.json.out"
    assert main(["metrics", "--root", str(store.root), "--format", "json",
                 "--out", str(json_file)]) == 0
    snap = json.loads(json_file.read_text())
    assert any(c["name"] == "tmx_batches_done_total"
               for c in snap["counters"])
    assert main(["trace", "--root", str(store.root)]) == 0

    # heartbeat landed next to the ledger and is fresh
    age = telemetry.heartbeat_age(
        store.workflow_dir / telemetry.HEARTBEAT_FILENAME
    )
    assert age is not None and age >= 0.0


def _minimal_run_store(tmp_path):
    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.models.store import ExperimentStore

    exp = grid_experiment("tele", well_rows=1, well_cols=1,
                          sites_per_well=(1, 1), channel_names=("DAPI",),
                          site_shape=(8, 8))
    return ExperimentStore.create(tmp_path / "exp", exp)


def test_cli_metrics_from_seed_era_ledger(tmp_path, capsys):
    """``tmx metrics`` derives a valid exposition from a ledger written
    before telemetry existed (no snapshot, no span events)."""
    from tmlibrary_tpu.cli import main

    st = _minimal_run_store(tmp_path)
    ledger_path = st.workflow_dir / "ledger.jsonl"
    ledger_path.parent.mkdir(parents=True, exist_ok=True)
    with ledger_path.open("w") as fh:
        for ev in _seed_era_events():
            fh.write(json.dumps(ev) + "\n")

    assert main(["metrics", "--root", str(st.root)]) == 0
    prom = capsys.readouterr().out
    samples = telemetry.parse_prometheus(prom)
    names = {n for n, _, _ in samples}
    assert "tmx_batches_done_total" in names
    assert "tmx_step_units_per_sec" in names

    assert main(["metrics", "--root", str(st.root), "--format", "json",
                 "--source", "ledger"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert any(c["name"] == "tmx_runs_total" for c in snap["counters"])

    assert main(["trace", "--root", str(st.root)]) == 0
    out = capsys.readouterr().out
    assert "step:jterator" in out

    # --source snapshot without a snapshot file is an explicit error
    assert main(["metrics", "--root", str(st.root),
                 "--source", "snapshot"]) == 1


def test_cli_metrics_empty_store_errors(tmp_path, capsys):
    from tmlibrary_tpu.cli import main

    st = _minimal_run_store(tmp_path)
    assert main(["metrics", "--root", str(st.root)]) == 1
    assert main(["trace", "--root", str(st.root)]) == 1


def test_cli_status_flags_stale_heartbeat(tmp_path, capsys):
    """A running step whose heartbeat is older than 2x the sampler period
    is flagged as hung by ``tmx workflow status``."""
    from tmlibrary_tpu.cli import main

    st = _minimal_run_store(tmp_path)
    ledger_path = st.workflow_dir / "ledger.jsonl"
    ledger_path.parent.mkdir(parents=True, exist_ok=True)
    with ledger_path.open("w") as fh:
        fh.write(json.dumps({"event": "run_started"}) + "\n")
        fh.write(json.dumps({"event": "init_done", "step": "jterator",
                             "n_batches": 4}) + "\n")
    hb_path = st.workflow_dir / telemetry.HEARTBEAT_FILENAME
    stale_t = time.time() - 100.0
    hb_path.write_text(json.dumps(
        {"ts": stale_t, "pid": 1, "period": 5.0}
    ))
    # staleness is fresher-of(ts, mtime): backdate the mtime too, or the
    # fresh file mtime would (correctly) mark the heartbeat live
    os.utime(hb_path, (stale_t, stale_t))
    assert main(["workflow", "status", "--root", str(st.root)]) == 0
    out = capsys.readouterr().out
    assert "heartbeat:" in out
    assert "STALE: run appears hung" in out

    # fresh heartbeat on the same running step: reported, not flagged
    telemetry.write_heartbeat(hb_path, period=5.0)
    assert main(["workflow", "status", "--root", str(st.root)]) == 0
    out = capsys.readouterr().out
    assert "heartbeat:" in out
    assert "STALE" not in out


# ---------------------------------------- bucketed ledgers (PR-5 era on)
def _bucketed_events(with_ceiling):
    """A capacity-bucketed run ledger: PR-5-era batch summaries carry
    bucket_capacity/slot_occupancy/bucket_escalations; bucket_ceiling
    joined later for the padding-waste derivation."""
    def result(cap, occ, esc=0):
        r = {"n_sites": 4, "bucket_capacity": cap, "slot_occupancy": occ,
             "bucket_escalations": esc}
        if with_ceiling:
            r["bucket_ceiling"] = 32
        return r

    return [
        {"event": "run_started", "t": 1.0},
        {"event": "init_done", "step": "jterator", "n_batches": 3},
        {"event": "batch_done", "step": "jterator", "batch": 0,
         "elapsed": 1.0, "result": result(8, 0.5)},
        {"event": "batch_done", "step": "jterator", "batch": 1,
         "elapsed": 1.0, "result": result(8, 0.7, esc=2)},
        {"event": "batch_done", "step": "jterator", "batch": 2,
         "elapsed": 1.0, "result": result(32, 0.9)},
        {"event": "step_done", "step": "jterator", "elapsed": 3.0,
         "pipeline_stats": {
             "depth": 2, "source": "tuned", "n_batches": 3,
             "phases": {"dispatch": {"total_s": 1.0, "max_s": 0.5},
                        "device_block": {"total_s": 0.5, "max_s": 0.3},
                        "persist": {"total_s": 1.5, "max_s": 0.9}}}},
    ]


def test_registry_from_pr5_era_bucketed_ledger():
    """Satellite: bucket routing/saturation/occupancy gauges must be
    derivable from a ledger that predates the bucket_ceiling field."""
    reg = telemetry.registry_from_ledger(_bucketed_events(False))
    assert reg.counter("tmx_jterator_bucket_routed_total",
                       capacity="8").value == 2.0
    assert reg.counter("tmx_jterator_bucket_routed_total",
                       capacity="32").value == 1.0
    assert reg.counter("tmx_jterator_bucket_saturated_total").value == 2.0
    assert reg.gauge("tmx_jterator_slot_occupancy").value == pytest.approx(
        (0.5 + 0.7 + 0.9) / 3)
    # no ceiling -> no padding-waste estimate (never a crash, never a lie)
    names = {g["name"] for g in reg.snapshot()["gauges"]}
    assert "tmx_jterator_padded_flops_avoided_frac" not in names
    telemetry.parse_prometheus(telemetry.render_prometheus(reg.snapshot()))


def test_registry_from_ledger_padding_waste_gauge():
    reg = telemetry.registry_from_ledger(_bucketed_events(True))
    # capacities 8+8+32 routed against a 32 ceiling each:
    # 1 - 48/96 = 0.5 of the ceiling's padded FLOPs never executed
    assert reg.gauge(
        "tmx_jterator_padded_flops_avoided_frac"
    ).value == pytest.approx(0.5)


# ------------------------------------------------------------- tmx perf
def test_cli_perf_renders_roofline_table(tmp_path, capsys, monkeypatch):
    """Acceptance: ``tmx perf`` renders the per-program roofline table
    (FLOPs, bytes, intensity, bound-by) with one row per capacity bucket,
    the phase device/host split, and the padding gauge."""
    from tmlibrary_tpu import perf
    from tmlibrary_tpu.cli import main

    monkeypatch.setenv("BENCH_HISTORY", str(tmp_path / "h.jsonl"))
    st = _minimal_run_store(tmp_path)
    perf.reset_profiles()
    for cap in (8, 32):
        perf.record_compile(
            program="jterator_batch@abc123", capacity=cap,
            backend="cpu", compile_s=0.5,
            cost=perf.ProgramCost(2e9, 4e7),
        )
    (st.workflow_dir / "perf.json").write_text(
        json.dumps(perf.perf_snapshot()))
    perf.reset_profiles()
    with (st.workflow_dir / "ledger.jsonl").open("w") as fh:
        for ev in _bucketed_events(True):
            fh.write(json.dumps(ev) + "\n")

    assert main(["perf", "--root", str(st.root)]) == 0
    out = capsys.readouterr().out
    assert "jterator_batch@abc123" in out
    # one row per capacity bucket rung
    assert len([l for l in out.splitlines()
                if "jterator_batch@abc123" in l]) == 2
    assert "bound-by" in out and "memory" in out  # 50 flops/B < ridge
    assert "device=" in out and "host=" in out
    assert "padded-FLOPs-avoided: 50.0%" in out

    assert main(["perf", "--root", str(st.root), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert len(doc["programs"]) == 2
    row = doc["programs"][0]
    assert row["flops"] == 2e9 and row["bytes"] == 4e7
    assert row["arithmetic_intensity"] == pytest.approx(50.0)
    assert row["bound_by"] == "memory"
    assert doc["padded_flops_avoided_frac"] == pytest.approx(0.5)
    assert doc["latest_bench"] is None  # empty history redirect


def test_cli_perf_requires_root_or_history_verb(tmp_path, capsys,
                                                monkeypatch):
    from tmlibrary_tpu.cli import main

    assert main(["perf"]) == 2

    hist = tmp_path / "h.jsonl"
    monkeypatch.setenv("BENCH_HISTORY", str(hist))
    assert main(["perf", "history"]) == 1  # empty history is an error
    capsys.readouterr()
    from tmlibrary_tpu import tuning
    tuning.append_bench_history(
        {"metric": "m", "config": "3", "backend": "tpu", "value": 100.0})
    tuning.append_bench_history(
        {"metric": "m", "config": "3", "backend": "tpu", "value": 80.0})
    assert main(["perf", "history", "--tail", "5"]) == 0
    out = capsys.readouterr().out
    assert "2 records" in out
    assert "verdict: regression" in out
    assert "recapture -> bench:3" in out


# ------------------------------------------- trace context (serving path)
def test_trace_scope_sets_and_restores_context():
    assert telemetry.trace_context() == {}
    with telemetry.trace_scope(trace_id="t-1", job="a-1", tenant="a",
                               ignored=None):
        assert telemetry.trace_context() == {
            "trace_id": "t-1", "job": "a-1", "tenant": "a"}
        with telemetry.trace_scope(job="a-2"):  # nested scopes merge
            assert telemetry.trace_context()["job"] == "a-2"
            assert telemetry.trace_context()["trace_id"] == "t-1"
        assert telemetry.trace_context()["job"] == "a-1"
    assert telemetry.trace_context() == {}
    # exception-safe restore
    with pytest.raises(RuntimeError):
        with telemetry.trace_scope(trace_id="t-2"):
            raise RuntimeError("boom")
    assert telemetry.trace_context() == {}


def test_ledger_append_stamps_trace_context(tmp_path):
    """RunLedger.append labels every sealed event with the ambient trace
    context — the one edit point that links enqueue → run → phase — but
    never overwrites an explicitly-passed label."""
    led = RunLedger(tmp_path / "ledger.jsonl")
    with telemetry.trace_scope(trace_id="t-1", job="a-1", tenant="a"):
        led.append(event="batch_done", step="s", batch=0, elapsed=0.1)
        led.append(event="job_done", job="explicit", elapsed_s=1.0)
    led.append(event="step_done", step="s", elapsed=0.2)
    evs = led.events()
    assert evs[0]["trace_id"] == "t-1" and evs[0]["job"] == "a-1" \
        and evs[0]["tenant"] == "a"
    assert evs[1]["job"] == "explicit"  # setdefault keeps explicit labels
    assert "trace_id" not in evs[2]  # outside the scope: unstamped


# ----------------------------------------------------- flight recorder
@pytest.fixture()
def _fresh_flightrec():
    telemetry.reset_flight_recorder()
    yield
    telemetry.reset_flight_recorder()


def test_flight_recorder_ring_bounded_and_dump(tmp_path, monkeypatch,
                                               _fresh_flightrec):
    monkeypatch.setenv("TMX_FLIGHTREC_N", "8")
    for i in range(20):
        telemetry.flight_record({"event": "e", "i": i})
    evs = telemetry.flight_events()
    assert [e["i"] for e in evs] == list(range(12, 20))  # last 8 kept
    out = telemetry.flightrec_path(tmp_path)
    assert out.name == f"flightrec.{telemetry.host_id()}.json"
    got = telemetry.flight_dump(out, reason="watchdog",
                                extra={"step": "jterator"})
    assert got == str(out)
    payload = json.loads(out.read_text())
    assert payload["reason"] == "watchdog"
    assert payload["step"] == "jterator"
    assert payload["capacity"] == 8
    assert payload["pid"] == os.getpid()
    assert [e["i"] for e in payload["events"]] == list(range(12, 20))


def test_flight_dump_empty_ring_returns_none(tmp_path, _fresh_flightrec):
    assert telemetry.flight_dump(tmp_path / "x.json") is None
    assert not (tmp_path / "x.json").exists()


def test_flight_recorder_zero_cost_when_disabled(_fresh_flightrec):
    """Telemetry off ⇒ no ring is ever allocated — the pin behind the
    'disabled runs carry zero new instrument cost' acceptance bar."""
    telemetry.reset_registry(enabled=False)
    for i in range(5):
        telemetry.flight_record({"event": "e", "i": i})
    assert telemetry.flight_events() == []
    assert telemetry._flight is None  # not even an empty deque


def test_engine_run_feeds_flight_recorder(tmp_path, _fresh_flightrec,
                                          source_dir, store):
    """Every ledger append lands in the ring, so a post-mortem dump shows
    the exact event tail."""
    desc = make_description(source_dir, store)
    Workflow(store, desc).run()
    evs = telemetry.flight_events()
    assert evs, "run appended nothing to the flight ring"
    kinds = {e.get("event") for e in evs}
    assert "run_done" in kinds or "step_done" in kinds


# ------------------------------------- ledger replay: serve/slo kinds
def test_registry_from_ledger_queue_wait_sched_delay_and_burn():
    events = [
        {"host": "h0", "ts": 1.0, "event": "job_admitted", "job": "a-1",
         "tenant": "a", "queue_wait_s": 0.25},
        {"host": "h0", "ts": 2.0, "event": "job_started", "job": "a-1",
         "tenant": "a", "sched_delay_s": 0.5},
        {"host": "h0", "ts": 3.0, "event": "slo_burn", "tenant": "a",
         "window": "3600", "burn": 2.0},
        {"host": "h0", "ts": 4.0, "event": "job_done", "job": "a-1",
         "tenant": "a", "elapsed_s": 1.5},
    ]
    reg = telemetry.registry_from_ledger(events + events)  # dup read
    qw = reg.histogram("tmx_serve_queue_wait_seconds", tenant="a",
                       host="h0")
    assert qw.count == 1 and qw.sum == pytest.approx(0.25)
    sd = reg.histogram("tmx_serve_sched_delay_seconds", tenant="a",
                       host="h0")
    assert sd.count == 1 and sd.sum == pytest.approx(0.5)
    assert reg.counter("tmx_slo_burn_total", tenant="a", window="3600",
                       host="h0").value == 1
    assert reg.counter("tmx_slo_jobs_total", tenant="a", outcome="ok",
                       host="h0").value == 1
    lat = reg.histogram("tmx_slo_job_latency_seconds", tenant="a",
                        host="h0")
    assert lat.count == 1 and lat.sum == pytest.approx(1.5)


def test_prometheus_escaping_full_spec_round_trip():
    """Label values exercising every escape the text format defines —
    backslash, double quote, newline — plus commas and equals signs
    inside quoted values, across multiple labels on one series
    (the naive comma-split parser choked on all of these)."""
    reg = telemetry.MetricsRegistry(enabled=True)
    nasty = 'a"b\\c\nd,e=f'
    reg.counter("tmx_esc_total", path=nasty, other="x,y=z").inc(2)
    text = telemetry.render_prometheus(reg.snapshot())
    assert '\\n' in text and '\\"' in text and "\\\\" in text
    samples = telemetry.parse_prometheus(text)
    (sample,) = [s for s in samples if s[0] == "tmx_esc_total"]
    assert sample[1] == {"path": nasty, "other": "x,y=z"}
    assert sample[2] == 2.0
    # and a second render/parse trip is stable
    again = telemetry.render_prometheus(reg.snapshot())
    assert telemetry.parse_prometheus(again)


def test_parse_prometheus_rejects_broken_labels():
    for bad in ('m{a="unterminated} 1\n',
                'm{a=unquoted} 1\n',
                'm{="noname"} 1\n',
                'm{a="x"junk} 1\n'):
        with pytest.raises(ValueError):
            telemetry.parse_prometheus(bad)


def test_snapshot_stamps_captured_at_and_sequence():
    reg = telemetry.MetricsRegistry(enabled=True)
    reg.counter("c").inc()
    s1 = reg.snapshot()
    s2 = reg.snapshot()
    assert s1["captured_at"] <= s2["captured_at"]
    # sequence is monotonic per registry, independent of the clock
    assert (s1["sequence"], s2["sequence"]) == (1, 2)
    # and render_json round-trips the stamps
    doc = json.loads(telemetry.render_json(s2))
    assert doc["sequence"] == 2 and "captured_at" in doc
