"""Data-quality & numerics observability (``tmlibrary_tpu.qc``).

Pins the subsystem's hard invariants:

- pipeline outputs are bit-identical with QC on or off (QC only reads);
- disabled QC hands out the shared null session (one attribute lookup
  and a no-op call per instrumentation point);
- P² sketch quantiles track ``np.percentile`` and merge across hosts
  with the ``merge_snapshots`` discipline;
- a QC-on workflow run writes ``workflow/qc.json``, appends
  ``qc_batch``/``qc_site`` ledger events and mirrors ``tmx_qc_*``
  registry series — and flags never fail the run;
- ``registry_from_ledger`` rebuilds the QC gauges post-hoc, tolerates
  unknown event kinds (warn once, never raise), and ``tmx metrics
  --merge`` carries ``tmx_qc_*`` across a 2-host fleet;
- the drift sentinel's exit codes are pinned: 0 ok · 1 drift · 2 stale
  · 3 no reference.
"""

import json
import logging
import time

import numpy as np
import pytest

from tmlibrary_tpu import qc, telemetry
from tmlibrary_tpu.ops import qc as qc_ops

from test_workflow import (  # noqa: F401  (fixtures)
    make_description,
    source_dir,
    store,
    synth_site_image,
)


# ------------------------------------------------------------- P² sketches
def test_p2_quantile_tracks_numpy_percentile():
    rng = np.random.default_rng(7)
    values = rng.normal(100.0, 15.0, 5000)
    p50, p95 = qc.P2Quantile(0.50), qc.P2Quantile(0.95)
    for v in values:
        p50.update(v)
        p95.update(v)
    assert p50.value() == pytest.approx(np.percentile(values, 50), abs=1.5)
    assert p95.value() == pytest.approx(np.percentile(values, 95), abs=2.5)


def test_p2_quantile_exact_below_five_observations():
    p = qc.P2Quantile(0.50)
    assert np.isnan(p.value())
    for v in (3.0, 1.0, 2.0):
        p.update(v)
    assert p.value() == 2.0  # exact interpolation over the sorted sample


def test_feature_sketch_counts_exact_and_nan_tallies():
    s = qc.FeatureSketch()
    n_nan, n_inf = s.update(np.array([1.0, np.nan, 3.0, np.inf, -np.inf]))
    assert (n_nan, n_inf) == (1, 2)
    d = s.to_dict()
    assert d["count"] == 2 and d["min"] == 1.0 and d["max"] == 3.0
    assert d["nan"] == 1 and d["inf"] == 2
    assert d["mean"] == pytest.approx(2.0)


def test_feature_sketch_empty_serializes_none():
    d = qc.FeatureSketch().to_dict()
    assert d["count"] == 0
    assert d["min"] is None and d["max"] is None
    assert d["p50"] is None and d["p95"] is None


def test_merge_sketch_dicts_discipline():
    a, b = qc.FeatureSketch(), qc.FeatureSketch()
    a.update(np.arange(100, dtype=np.float64))
    b.update(np.arange(1000, 1010, dtype=np.float64))
    da, db = a.to_dict(), b.to_dict()
    m = qc.merge_sketch_dicts(da, db)
    # counts/sums add, min/max fold
    assert m["count"] == 110
    assert m["min"] == 0.0 and m["max"] == 1009.0
    assert m["sum"] == pytest.approx(da["sum"] + db["sum"])
    # quantiles follow the LARGER sample (a has 100 >> b's 10)
    assert m["p50"] == da["p50"] and m["p95"] == da["p95"]
    # ties keep the first argument
    t = qc.merge_sketch_dicts(da, da)
    assert t["p50"] == da["p50"]


def test_merge_of_one_is_identity():
    """Satellite edge case: a single-host run merged through the same
    path as a fleet run must not change any sketch value."""
    s = qc.FeatureSketch()
    s.update(np.linspace(0.0, 50.0, 77))
    d = s.to_dict()
    profile = {"schema_version": qc.QC_SCHEMA_VERSION,
               "written_at_unix": 123.0,
               "steps": {"jterator": {"batches": 2, "sites": 8,
                                      "flagged": 0}},
               "channels": {"DAPI": {"focus_tenengrad": {
                   "min": 1.0, "max": 2.0, "mean": 1.5, "count": 8}}},
               "illumination": {}, "features": {"nuclei.area": d},
               "guards": {"nan_columns": [], "nan_values": 0,
                          "inf_values": 0, "count_z_max": 0.0,
                          "capacity_saturated_batches": 0},
               "worst_sites": [], "flagged": [], "flagged_total": 0}
    merged = qc.merge_profiles([("host0", profile)])
    assert merged["features"]["nuclei.area"] == d
    assert merged["steps"] == profile["steps"]
    assert merged["channels"]["DAPI"]["focus_tenengrad"]["min"] == 1.0
    assert merged["hosts"] == ["host0"]


# -------------------------------------------------------- on-device stats
def test_saturation_fraction_all_saturated_channel():
    img = np.full((32, 32), 65535.0, np.float32)
    assert float(qc_ops.saturation_fraction(img)) == 1.0
    assert float(qc_ops.saturation_fraction(img * 0.0)) == 0.0


def test_focus_metrics_flat_image_near_zero_and_rank_sharpness():
    flat = np.full((64, 64), 500.0, np.float32)
    assert float(qc_ops.focus_tenengrad(flat)) == pytest.approx(0.0)
    assert float(qc_ops.laplacian_variance(flat)) == pytest.approx(0.0)
    rng = np.random.default_rng(3)
    sharp = synth_site_image(rng).astype(np.float32)
    # crude blur: 2x2 box mean, applied twice
    blurred = sharp.copy()
    for _ in range(2):
        blurred = (blurred + np.roll(blurred, 1, 0) + np.roll(blurred, 1, 1)
                   + np.roll(np.roll(blurred, 1, 0), 1, 1)) / 4.0
    assert float(qc_ops.focus_tenengrad(sharp)) > float(
        qc_ops.focus_tenengrad(blurred))
    assert float(qc_ops.laplacian_variance(sharp)) > float(
        qc_ops.laplacian_variance(blurred))


def test_background_level_is_darkest_tile_mean():
    img = np.full((64, 64), 1000.0, np.float32)
    img[:8, :8] = 100.0  # one dark 8x8 corner tile
    assert float(qc_ops.background_level(img)) == pytest.approx(100.0)
    # degrades to the global mean when smaller than one tile
    tiny = np.full((4, 4), 7.0, np.float32)
    assert float(qc_ops.background_level(tiny)) == pytest.approx(7.0)


# ------------------------------------------------------ gate + null session
def test_disabled_qc_hands_out_shared_null_session(monkeypatch):
    monkeypatch.delenv("TMX_QC", raising=False)
    qc.set_enabled(None)
    assert not qc.enabled()
    s = qc.get_session()
    assert s is qc._NULL_SESSION
    assert s is qc.get_session()  # shared, not allocated per call
    assert s.observe_batch("jterator", [0, 1]) is None
    assert s.observe_illumination("DAPI", [50], [300.0]) is None
    assert s.snapshot() == {}
    assert qc.record_summary() is None


def test_enabled_resolution_override_beats_env(monkeypatch):
    monkeypatch.setenv("TMX_QC", "0")
    qc.set_enabled(True)
    assert qc.enabled()
    qc.set_enabled(None)
    assert not qc.enabled()
    monkeypatch.setenv("TMX_QC", "1")
    assert qc.enabled()


def test_cached_batch_fn_keys_on_qc_gate():
    from tmlibrary_tpu.benchmarks import smooth_threshold_description
    from tmlibrary_tpu.jterator import pipeline as jp

    jp._BATCH_FN_CACHE.clear()
    off = jp.cached_batch_fn(smooth_threshold_description(), 64, qc=False)
    on = jp.cached_batch_fn(smooth_threshold_description(), 64, qc=True)
    assert off is not on
    assert off is jp.cached_batch_fn(smooth_threshold_description(), 64,
                                     qc=False)
    # qc=None resolves the live gate onto the same keys
    qc.set_enabled(True)
    assert on is jp.cached_batch_fn(smooth_threshold_description(), 64)
    qc.set_enabled(False)
    assert off is jp.cached_batch_fn(smooth_threshold_description(), 64)
    jp._BATCH_FN_CACHE.clear()


def test_perf_wrapper_never_reuses_executable_across_qc_gate():
    """Regression: perf's AOT executable cache keys on the program
    digest — a QC-off run compiling first (same description, window,
    capacity, shapes) must NOT hand its executable to the
    QC-on wrapper, which expects a (SiteResult, qc_stats) pytree back.
    Order-dependent in the full suite (any engine run before a QC-on
    one), deterministic here."""
    from tmlibrary_tpu import perf, telemetry
    from tmlibrary_tpu.benchmarks import smooth_threshold_description
    from tmlibrary_tpu.jterator import pipeline as jp

    jp._BATCH_FN_CACHE.clear()
    jp._WRAPPED_FN_CACHE.clear()
    telemetry.reset_registry(enabled=True)
    perf.reset_profiles()
    try:
        import jax.numpy as jnp

        raw = {"DAPI": jnp.asarray(
            np.random.default_rng(0).integers(
                0, 4000, (2, 64, 64)).astype(np.uint16))}
        shifts = jnp.zeros((2, 2), jnp.int32)
        off = jp.cached_batch_fn(smooth_threshold_description(), 16,
                                 qc=False)
        assert not isinstance(off(raw, {}, shifts), tuple)
        on = jp.cached_batch_fn(smooth_threshold_description(), 16,
                                qc=True)
        out = on(raw, {}, shifts)
        assert isinstance(out, tuple)
        result, qc_stats = out
        assert set(qc_stats) == {"DAPI"}
        assert "saturation_frac" in qc_stats["DAPI"]
    finally:
        jp._BATCH_FN_CACHE.clear()
        jp._WRAPPED_FN_CACHE.clear()
        perf.reset_profiles()
        telemetry.reset_registry()


# ------------------------------------------------------- observe_batch
def _image_stats(n, focus=None, sat=None, background=None):
    return {"DAPI": {
        "saturation_frac": np.full(n, 0.0 if sat is None else sat),
        "background": np.full(n, 300.0 if background is None
                              else background),
        "focus_tenengrad": np.full(n, 10.0 if focus is None else focus),
        "laplacian_var": np.full(n, 0.05),
    }}


def test_observe_batch_zero_object_sites():
    """Satellite edge case: noise-only sites with zero objects must fold
    cleanly — no flags, no NaN tallies, empty sketches stay empty."""
    qc.set_enabled(True)
    qc.reset_session()
    s = qc.get_session()
    summary = s.observe_batch(
        "jterator", [0, 1, 2, 3],
        image_stats=_image_stats(4),
        counts={"nuclei": np.zeros(4, np.int32)},
        measurements={"nuclei": {
            # all-padding rows: every value masked out by count=0
            "Intensity_mean_DAPI": np.full((4, 8), np.nan),
        }},
    )
    assert summary["flagged_sites"] == []
    assert summary["nan_values"] == 0 and summary["nan_columns"] == 0
    snap = s.snapshot()
    assert snap["features"]["nuclei.Intensity_mean_DAPI"]["count"] == 0
    assert snap["guards"]["nan_columns"] == []
    assert snap["steps"]["jterator"]["sites"] == 4


def test_observe_batch_flags_saturated_sites_and_masks_padding():
    qc.set_enabled(True)
    qc.reset_session()
    s = qc.get_session()
    sat = np.array([0.0, 0.9, 0.0, 1.0])
    stats = _image_stats(4)
    stats["DAPI"]["saturation_frac"] = sat
    meas = np.full((4, 8), np.nan)
    meas[:, :2] = 5.0  # two real objects per site, six padding rows
    summary = s.observe_batch(
        "jterator", [10, 11, 12, 13], image_stats=stats,
        counts={"nuclei": np.full(4, 2, np.int32)},
        measurements={"nuclei": {"Intensity_mean_DAPI": meas}},
    )
    flags = summary["flagged_sites"]
    assert [f["site"] for f in flags] == [11, 13]
    assert all(f["reason"] == "saturation" for f in flags)
    # padding NaNs were masked out, not counted as numerics faults
    assert summary["nan_values"] == 0
    assert s.snapshot()["features"]["nuclei.Intensity_mean_DAPI"][
        "count"] == 8
    # cumulative gauge fields + live registry mirror
    assert summary["flagged_total"] == 2
    telemetry.reset_registry(enabled=True)
    s.observe_batch("jterator", [14], image_stats=_image_stats(1),
                    counts={"nuclei": np.array([2], np.int32)})
    reg = telemetry.get_registry()
    assert reg.gauge("tmx_qc_worst_focus", channel="DAPI").value == 10.0
    assert reg.gauge("tmx_qc_max_saturation_frac",
                     channel="DAPI").value == 1.0


def test_observe_batch_nan_feature_columns_counted():
    qc.set_enabled(True)
    qc.reset_session()
    s = qc.get_session()
    summary = s.observe_batch(
        "jterator", [0, 1], image_stats=_image_stats(2),
        counts={"nuclei": np.full(2, 3, np.int32)},
        measurements={"nuclei": {
            "Texture_bad": np.array([[np.nan, 2.0, np.inf],
                                     [1.0, np.nan, 3.0]]),
            "Intensity_ok": np.ones((2, 3)),
        }},
    )
    assert summary["nan_values"] == 2 and summary["inf_values"] == 1
    assert summary["nan_columns"] == 1
    snap = s.snapshot()
    assert snap["guards"]["nan_columns"] == ["nuclei.Texture_bad"]
    assert qc.record_summary()["nan_columns"] == 1


def test_capacity_saturation_flag_reused_as_guard():
    qc.set_enabled(True)
    qc.reset_session()
    s = qc.get_session()
    summary = s.observe_batch("jterator", [0],
                              image_stats=_image_stats(1), saturated=True)
    assert summary["capacity_saturated"]
    assert s.snapshot()["guards"]["capacity_saturated_batches"] == 1


# ------------------------------------------- bit-identity (the hard pin)
def _read_features_sorted(st, name):
    return (st.read_features(name)
            .sort_values(["site_index", "label"])
            .reset_index(drop=True))


def test_jterator_bit_identical_with_qc_on_and_off(source_dir, store):
    """THE invariant that makes QC safe to ship enabled: the instrumented
    run persists exactly the same label stacks and feature tables — QC
    only reads batch inputs/outputs, never feeds back into them."""
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

    qc.set_enabled(True)
    qc.reset_session()
    jt = get_step("jterator")(store)
    jt.init(jd.args)
    for j in jt.list_batches():
        jt.run(j)
    on_labels = store.read_labels(None, "nuclei").copy()
    on_feats = _read_features_sorted(store, "nuclei")
    # the QC-on run actually observed evidence
    snap = qc.get_session().snapshot()
    assert snap["steps"]["jterator"]["sites"] == 16
    assert "DAPI" in snap["channels"]
    assert snap["channels"]["DAPI"]["focus_tenengrad"]["count"] == 16
    assert any(k.startswith("nuclei.") for k in snap["features"])

    qc.set_enabled(False)
    qc.reset_session()
    jt2 = get_step("jterator")(store)
    jt2.delete_previous_output()
    jt2.init(jd.args)
    for j in jt2.list_batches():
        jt2.run(j)
    assert np.array_equal(store.read_labels(None, "nuclei"), on_labels)
    pandas.testing.assert_frame_equal(
        _read_features_sorted(store, "nuclei"), on_feats
    )


# ------------------------------------------- engine + workflow integration
def test_workflow_run_with_qc_writes_profile_and_ledger(source_dir, store):
    from tmlibrary_tpu.workflow.engine import RunLedger, Workflow

    qc.set_enabled(True)
    desc = make_description(source_dir, store)
    summary = Workflow(store, desc).run()
    assert summary["jterator"]["collected"]["objects_total"]["nuclei"] > 0

    # profile written next to the ledger (host0 convenience copy too)
    profile = json.loads((store.workflow_dir / "qc.json").read_text())
    assert profile["schema_version"] == qc.QC_SCHEMA_VERSION
    assert profile["steps"]["jterator"]["sites"] == 16
    assert profile["channels"]["DAPI"]["saturation_frac"]["max"] == 0.0
    assert profile["illumination"]["DAPI"]["p50"] > 0  # corilla hook
    feats = profile["features"]
    assert feats and all(v["nan"] == 0 for v in feats.values())

    # qc_batch events rode the engine thread into the ledger ...
    events = RunLedger(store.workflow_dir / "ledger.jsonl").events()
    qc_batches = [e for e in events if e.get("event") == "qc_batch"]
    assert len(qc_batches) == 2  # batch_size=8 over 16 sites
    assert all("flagged_sites" not in (e.get("summary") or {})
               for e in qc_batches)
    # ... and registry_from_ledger rebuilds the QC gauges post-hoc
    reg = telemetry.registry_from_ledger(events)
    snap = reg.snapshot()
    focus = [g for g in snap["gauges"]
             if g["name"] == "tmx_qc_worst_focus"]
    assert focus and focus[0]["labels"]["channel"] == "DAPI"
    live = telemetry.get_registry()
    assert live.gauge("tmx_qc_worst_focus",
                      channel="DAPI").value == pytest.approx(
        focus[0]["value"])
    # `tmx qc` renders from these artifacts and exits 3 (no reference)
    from tmlibrary_tpu.cli import main

    assert main(["qc", "--root", str(store.root)]) == qc.EXIT_NO_REFERENCE


def test_workflow_run_without_qc_writes_nothing(source_dir, store):
    from tmlibrary_tpu.workflow.engine import RunLedger, Workflow

    qc.set_enabled(False)
    desc = make_description(source_dir, store)
    Workflow(store, desc).run()
    assert not (store.workflow_dir / "qc.json").exists()
    assert not list(store.workflow_dir.glob("qc.*.json"))
    events = RunLedger(store.workflow_dir / "ledger.jsonl").events()
    assert not [e for e in events if str(e.get("event", "")
                                         ).startswith("qc")]


def test_note_qc_flags_sites_without_failing(tmp_path):
    """QC flags are ledger evidence, never control flow: _note_qc appends
    qc_batch + per-site qc_site events and the step keeps running."""
    from tmlibrary_tpu.workflow.engine import RunLedger, Workflow

    ledger = RunLedger(tmp_path / "ledger.jsonl", host="host0")
    wf = Workflow.__new__(Workflow)
    wf.ledger = ledger
    flagged = [{"site": 3, "step": "jterator", "channel": "DAPI",
                "reason": "saturation", "value": 0.9}]
    n = wf._note_qc("jterator", 0, {"qc": {
        "channels": {"DAPI": {"focus_min": 2.0}},
        "worst_focus": 2.0, "nan_columns": 0, "nan_values": 0,
        "inf_values": 0, "count_z_max": 0.0, "flagged_total": 1,
        "flagged_sites": flagged, "capacity_saturated": False,
    }})
    assert n == 1
    events = ledger.events()
    kinds = [e["event"] for e in events]
    assert kinds == ["qc_batch", "qc_site"]
    site_ev = events[1]
    assert site_ev["site"] == 3 and site_ev["reason"] == "saturation"
    assert site_ev["step"] == "jterator"  # once — from ledger.append
    # results without QC evidence are a no-op
    assert wf._note_qc("jterator", 1, {"n_sites": 8}) == 0
    assert wf._note_qc("jterator", 2, None) == 0


# ------------------------------------------------ multi-host fleet paths
def _qc_batch_event(host, focus, ts):
    return {"event": "qc_batch", "step": "jterator", "batch": 0,
            "ts": ts, "host": host,
            "summary": {"channels": {"DAPI": {"focus_min": focus,
                                              "saturation_max": 0.1,
                                              "background_mean": 300.0}},
                        "worst_focus": focus, "nan_columns": 1,
                        "nan_values": 2, "inf_values": 0,
                        "count_z_max": 1.5, "flagged_total": 1}}


def test_registry_from_ledger_two_host_qc_attribution(tmp_path):
    events = [
        {"event": "run_started", "ts": 1.0, "host": "host0"},
        _qc_batch_event("host0", 4.0, 2.0),
        _qc_batch_event("host1", 9.0, 2.5),
        {"event": "qc_site", "step": "jterator", "batch": 0, "site": 7,
         "reason": "focus", "ts": 3.0, "host": "host1"},
    ]
    snap = telemetry.registry_from_ledger(events).snapshot()
    focus = {g["labels"]["host"]: g["value"] for g in snap["gauges"]
             if g["name"] == "tmx_qc_worst_focus"}
    assert focus == {"host0": 4.0, "host1": 9.0}
    flagged = [c for c in snap["counters"]
               if c["name"] == "tmx_qc_sites_flagged_total"]
    assert len(flagged) == 1 and flagged[0]["labels"]["host"] == "host1"
    nan_bad = [c for c in snap["counters"]
               if c["name"] == "tmx_qc_nan_values_total"]
    assert {c["labels"]["host"] for c in nan_bad} == {"host0", "host1"}

    # the same 2-host ledger renders one fleet view end to end through
    # `tmx metrics --merge` (per-host ledger-derived snapshots on disk)
    from tmlibrary_tpu.cli import main

    wf = tmp_path / "run" / "workflow"
    wf.mkdir(parents=True)
    with (wf / "ledger.jsonl").open("w") as fh:
        for ev in events:
            fh.write(json.dumps(ev) + "\n")
    for host in ("host0", "host1"):
        per_host = [e for e in events if e.get("host") == host]
        (wf / f"metrics.{host}.json").write_text(telemetry.render_json(
            telemetry.registry_from_ledger(per_host).snapshot()))
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert main(["metrics", "--merge", str(tmp_path / "run")]) == 0
    prom = buf.getvalue()
    assert "tmx_qc_worst_focus" in prom
    assert 'host="host0"' in prom and 'host="host1"' in prom


def test_registry_from_ledger_unknown_kind_warns_once(caplog):
    """Satellite forward-compat pin: an old checkout must keep deriving
    metrics from a newer writer's ledger — unknown kinds warn once per
    kind and are otherwise ignored."""
    events = [
        {"event": "run_started", "ts": 1.0},
        {"event": "hologram_calibrated", "ts": 2.0, "step": "jterator"},
        {"event": "hologram_calibrated", "ts": 3.0, "step": "jterator"},
        {"event": "batch_done", "step": "jterator", "batch": 0,
         "elapsed": 1.0, "ts": 4.0, "result": {"n_sites": 8}},
    ]
    with caplog.at_level(logging.WARNING,
                         logger="tmlibrary_tpu.telemetry"):
        snap = telemetry.registry_from_ledger(events).snapshot()
    warned = [r for r in caplog.records
              if "hologram_calibrated" in r.getMessage()]
    assert len(warned) == 1
    # the known events still derived
    assert any(c["name"] == "tmx_batches_done_total"
               for c in snap["counters"])


def test_profile_roundtrip_and_host_merge(tmp_path):
    qc.set_enabled(True)
    qc.reset_session()
    s = qc.get_session()
    s.observe_batch("jterator", [0, 1], image_stats=_image_stats(2),
                    counts={"nuclei": np.array([2, 3], np.int32)},
                    measurements={"nuclei": {
                        "area": np.array([[4.0, 5.0, 0.0],
                                          [6.0, 7.0, 8.0]])}})
    prof0 = s.snapshot()
    qc.write_profile(tmp_path / "qc.host0.json", prof0)
    prof1 = json.loads(json.dumps(prof0, default=float))
    prof1["host"] = "host1"
    prof1["features"]["nuclei.area"]["max"] = 99.0
    qc.write_profile(tmp_path / "qc.host1.json", prof1)
    pairs = qc.load_run_profiles(tmp_path)
    assert [h for h, _ in pairs] == ["host0", "host1"]
    merged = qc.merge_profiles(pairs)
    area = merged["features"]["nuclei.area"]
    assert area["count"] == 10 and area["max"] == 99.0
    assert merged["steps"]["jterator"]["sites"] == 4


# --------------------------------------------------------- drift sentinel
def _profile_with_feature(p50, p95=None, nan=0, written=None, sat=0.0):
    return {
        "schema_version": qc.QC_SCHEMA_VERSION,
        "written_at_unix": time.time() if written is None else written,
        "features": {"nuclei.area": {
            "count": 100, "sum": p50 * 100, "mean": p50, "min": 0.0,
            "max": p50 * 2, "nan": nan, "inf": 0, "p50": p50,
            "p95": p50 * 1.2 if p95 is None else p95}},
        "channels": {"DAPI": {"saturation_frac": {
            "min": 0.0, "max": sat, "mean": sat, "count": 100}}},
    }


def test_compare_profiles_exit_codes_pinned():
    cur = _profile_with_feature(100.0)
    ref = _profile_with_feature(100.0)
    # 3: no reference at all
    v = qc.compare_profiles(cur, None)
    assert (v["status"], v["exit_code"]) == ("no_reference", 3)
    # 0: within threshold
    v = qc.compare_profiles(cur, ref, threshold=0.25)
    assert (v["status"], v["exit_code"]) == ("ok", 0)
    assert v["checked"] == 2  # one feature + one channel saturation
    # 1: median shifted beyond threshold x spread
    v = qc.compare_profiles(_profile_with_feature(200.0), ref)
    assert (v["status"], v["exit_code"]) == ("drift", 1)
    assert v["drifted"][0]["kind"] == "median_shift"
    # 1: new NaNs where the reference had none
    v = qc.compare_profiles(_profile_with_feature(100.0, nan=3), ref)
    assert v["exit_code"] == 1
    assert any(d["kind"] == "new_nan" for d in v["drifted"])
    # 1: saturation rose > 0.25 absolute
    v = qc.compare_profiles(_profile_with_feature(100.0, sat=0.5), ref)
    assert v["exit_code"] == 1
    assert any(d["kind"] == "saturation" for d in v["drifted"])
    # 2: stale reference (only when a budget is set; default 0 = off)
    old = _profile_with_feature(100.0, written=time.time() - 48 * 3600)
    v = qc.compare_profiles(cur, old, stale_hours=24.0)
    assert (v["status"], v["exit_code"]) == ("stale", 2)
    assert v["age_hours"] == pytest.approx(48.0, abs=0.2)
    v = qc.compare_profiles(cur, old, stale_hours=0.0)
    assert v["exit_code"] == 0
    # drift outranks stale
    v = qc.compare_profiles(_profile_with_feature(200.0), old,
                            stale_hours=24.0)
    assert v["exit_code"] == 1


def test_cmd_qc_cli_exit_codes(store, tmp_path, monkeypatch, capsys):
    from tmlibrary_tpu.cli import main

    monkeypatch.chdir(tmp_path)  # no accidental tuning/QC_BASELINE.json
    monkeypatch.delenv("TMX_QC_BASELINE", raising=False)
    monkeypatch.delenv("TMX_QC_STALE_HOURS", raising=False)

    # no QC evidence at all: generic failure (1), not a pinned verdict
    assert main(["qc", "--root", str(store.root)]) == 1
    assert "no QC evidence" in capsys.readouterr().err

    profile = _profile_with_feature(100.0)
    profile["steps"] = {"jterator": {"batches": 2, "sites": 16,
                                     "flagged": 0}}
    (store.workflow_dir / "qc.json").write_text(
        json.dumps(profile, default=float))
    # 3: evidence but no reference
    assert main(["qc", "--root", str(store.root)]) == 3
    # 0: reference == own profile
    ref = tmp_path / "ref.json"
    ref.write_text(json.dumps(profile, default=float))
    assert main(["qc", "--root", str(store.root),
                 "--reference", str(ref)]) == 0
    out = capsys.readouterr().out
    assert "drift verdict: ok" in out and "jterator" in out
    # reference also resolves via the TMX_QC_BASELINE env
    monkeypatch.setenv("TMX_QC_BASELINE", str(ref))
    assert main(["qc", "--root", str(store.root)]) == 0
    monkeypatch.delenv("TMX_QC_BASELINE")
    # 1: doctored reference median
    doctored = json.loads(ref.read_text())
    doctored["features"]["nuclei.area"]["p50"] = 500.0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doctored))
    assert main(["qc", "--root", str(store.root),
                 "--reference", str(bad)]) == 1
    assert "DRIFT" in capsys.readouterr().out
    # 2: old reference + a staleness budget
    stale = json.loads(ref.read_text())
    stale["written_at_unix"] = time.time() - 100 * 3600
    sp = tmp_path / "stale.json"
    sp.write_text(json.dumps(stale))
    assert main(["qc", "--root", str(store.root), "--reference", str(sp),
                 "--stale-hours", "24"]) == 2
    capsys.readouterr()
    # --json emits the machine view with the same verdict
    assert main(["qc", "--root", str(store.root), "--reference", str(ref),
                 "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["verdict"]["exit_code"] == 0
    assert payload["profile"]["steps"]["jterator"]["sites"] == 16


# ----------------------------------------------------------- tmx top / qc
def test_top_once_json_includes_qc(store, capsys):
    from tmlibrary_tpu.cli import main

    profile = _profile_with_feature(100.0)
    profile["flagged_total"] = 2
    profile["guards"] = {"nan_columns": ["nuclei.bad"], "nan_values": 1,
                         "inf_values": 0, "count_z_max": 0.0,
                         "capacity_saturated_batches": 0}
    (store.workflow_dir / "qc.json").write_text(
        json.dumps(profile, default=float))
    assert main(["top", "--root", str(store.root), "--once",
                 "--json"]) == 0
    view = json.loads(capsys.readouterr().out)
    assert view["qc"]["flagged_total"] == 2
    # and the text dashboard paints the QC row with the non-finite flag
    assert main(["top", "--root", str(store.root), "--once"]) == 0
    out = capsys.readouterr().out
    assert "qc: flagged 2" in out
    assert "NON-FINITE FEATURES" in out
