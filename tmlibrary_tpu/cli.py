"""Unified command-line interface.

Reference parity: ``tmlib/workflow/cli.py`` + per-step console scripts
(``metaconfig``, ``imextract``, ``corilla``, ``align``, ``illuminati``,
``jterator``) and ``tm_workflow`` (``manager.py``) — argparse verbs
``init`` / ``run`` / ``collect`` / ``submit`` / ``resume`` / ``status`` /
``log`` / ``cleanup`` / ``info`` (SURVEY.md §2 row 1).

Here the per-step scripts fold into one ``tmx`` entry point::

    tmx create  --root DIR --name NAME
    tmx <step>  init    --root DIR [step args...]
    tmx <step>  run     --root DIR --job N
    tmx <step>  collect --root DIR
    tmx <step>  info    --root DIR
    tmx workflow submit --root DIR [--description wf.yaml] [--resume]
    tmx workflow status --root DIR
    tmx log     --root DIR [--tail N]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

from tmlibrary_tpu.log import configure_logging
from tmlibrary_tpu.models.experiment import Experiment
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.workflow.engine import (
    RunLedger,
    Workflow,
    WorkflowDescription,
)
from tmlibrary_tpu.workflow.registry import get_step, list_steps


def _add_common(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--root", required=True, help="experiment store directory")
    parser.add_argument("-v", "--verbosity", action="count", default=0)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tmx", description="TPU-native microscopy image analysis"
    )
    sub = parser.add_subparsers(dest="command", required=True)

    p_create = sub.add_parser("create", help="create an empty experiment store")
    _add_common(p_create)
    p_create.add_argument("--name", required=True)

    p_inspect = sub.add_parser(
        "inspect",
        help="print a microscope file's dimensions/channels (the "
             "Bio-Formats 'showinf' role, on the native parsers)")
    p_inspect.add_argument("files", nargs="+")
    p_inspect.add_argument("--json", action="store_true", dest="as_json",
                           help="one JSON object per file")

    p_log = sub.add_parser("log", help="show the run ledger or captured step logs")
    _add_common(p_log)
    p_log.add_argument("--tail", type=int, default=20)
    p_log.add_argument("--step", default=None,
                       help="print a step's captured log file instead")
    p_log.add_argument("--job", type=int, default=None,
                       help="batch index (with --step); omit for the "
                            "whole-step run log")

    p_export = sub.add_parser(
        "export", help="export feature tables / polygons / illumination stats"
    )
    _add_common(p_export)
    p_export.add_argument("--objects", default=None, help="object type name")
    p_export.add_argument(
        "--illumstats", type=int, default=None, metavar="CHANNEL",
        help="instead of a feature table, write this channel's illumination "
             "statistics as an HDF5 file with the reference IllumstatsFile "
             "layout (mutually exclusive with --objects)",
    )
    p_export.add_argument(
        "--cycle", type=int, default=0,
        help="acquisition cycle for --illumstats/--images (default 0)",
    )
    p_export.add_argument(
        "--images", type=int, default=None, metavar="CHANNEL",
        help="instead of a feature table, write this channel's site images "
             "as uint16 TIFFs into --out (a directory), named with the "
             "canonical <well>_s<site>_... pattern",
    )
    p_export.add_argument(
        "--correct", action="store_true",
        help="--images only: apply illumination correction (corilla stats)",
    )
    p_export.add_argument(
        "--align", action="store_true",
        help="--images only: apply cycle alignment shifts + intersection crop",
    )
    p_export.add_argument(
        "--ome", action="store_true",
        help="--images only: write OME-TIFFs (OME-XML in ImageDescription, "
             "the Bio-Formats convention) instead of bare TIFFs",
    )
    p_export.add_argument(
        "--ngff", action="store_true",
        help="write the WHOLE experiment as an OME-NGFF (OME-Zarr v0.4) "
             "HCS plate into --out (a directory, conventionally *.zarr): "
             "every channel/tpoint/zplane as multiscale tczyx fields; the "
             "exported plate re-ingests via the ngff metaconfig handler",
    )
    p_export.add_argument(
        "--ngff-levels", type=int, default=3, metavar="N",
        help="--ngff only: number of 2x multiscale levels (default 3)",
    )
    p_export.add_argument(
        "--ngff-labels", default=None, metavar="NAME[,NAME...]",
        help="--ngff only: also export these segmentation stacks as NGFF "
             "image-label multiscales under each field's labels/ group",
    )
    p_export.add_argument("--out", required=True, help="output file path")
    p_export.add_argument(
        "--format", choices=("csv", "parquet", "geojson"), default=None,
        help="inferred from --out suffix when omitted; geojson exports the "
             "traced object polygons (run jterator with --as-polygons)",
    )
    p_export.add_argument(
        "--join-features", default=None, metavar="COL[,COL...]",
        help="geojson only: join these measurement columns onto each "
             "polygon's properties by (site, label) — viewer-ready colored "
             "overlays (reference: tmserver joins FeatureValues onto "
             "mapobjects)",
    )
    p_export.add_argument(
        "--simplify", type=float, default=0.0, metavar="TOL",
        help="geojson only: Douglas-Peucker-simplify polygon rings to this "
             "perpendicular-distance tolerance in pixels (reference: PostGIS "
             "geometry simplification for viewer-scale objects)",
    )

    p_metrics = sub.add_parser(
        "metrics",
        help="export run metrics (Prometheus textfile or JSON) from the "
             "live registry snapshot or derived from any run ledger",
    )
    # --root is optional here (unlike _add_common): `tmx metrics --merge`
    # takes the run root positionally and needs no open store
    p_metrics.add_argument("--root", default=None,
                           help="experiment store directory")
    p_metrics.add_argument("-v", "--verbosity", action="count", default=0)
    p_metrics.add_argument(
        "--merge", default=None, metavar="RUN_ROOT",
        help="merge every per-host workflow/metrics.<host>.json under this "
             "run root into one fleet view (adds host labels)",
    )
    p_metrics.add_argument(
        "--format", choices=("prom", "json"), default="prom",
        help="Prometheus textfile exposition format (default) or JSON",
    )
    p_metrics.add_argument(
        "--source", choices=("auto", "snapshot", "ledger"), default="auto",
        help="'snapshot' reads the registry snapshot the last submit wrote "
             "(workflow/metrics.json); 'ledger' derives metrics from the "
             "run ledger (works for runs that predate telemetry); 'auto' "
             "prefers the snapshot and falls back to the ledger",
    )
    p_metrics.add_argument("--out", default=None,
                           help="write to this file instead of stdout")

    p_top = sub.add_parser(
        "top",
        help="live fleet dashboard over a run's heartbeat + metrics "
             "snapshot files (curses-free repaint loop; --once for CI)",
    )
    _add_common(p_top)
    p_top.add_argument("--interval", type=float, default=2.0,
                       help="repaint period in seconds (default 2.0)")
    p_top.add_argument("--once", action="store_true",
                       help="render a single frame and exit (tests/CI)")
    p_top.add_argument("--json", action="store_true", dest="as_json",
                       help="emit the fleet view as JSON (implies --once) "
                            "so CI can assert on dashboard "
                            "state without screen-scraping")

    p_timeline = sub.add_parser(
        "timeline",
        help="metric history from the durable time-series "
             "(tsdb.<host>.jsonl segments): per-series sparklines with "
             "last/rate summaries; ledger-replay fallback for roots that "
             "predate the tsdb",
    )
    _add_common(p_timeline)
    p_timeline.add_argument("--metric", default=None, metavar="NAME",
                            help="restrict to series whose metric name "
                                 "contains this substring")
    p_timeline.add_argument("--window", type=float, default=None,
                            metavar="SECONDS",
                            help="rate window for counter series "
                                 "(default: full history)")
    p_timeline.add_argument("--width", type=int, default=48,
                            help="sparkline width in columns (default 48)")
    p_timeline.add_argument("--json", action="store_true", dest="as_json",
                            help="emit the merged series as JSON")

    p_trace = sub.add_parser(
        "trace",
        help="dump the run's span tree (run > step > batch > phase) with "
             "critical-path annotation from the run ledger, or export a "
             "Chrome trace; accepts experiment AND serve roots",
    )
    _add_common(p_trace)
    p_trace.add_argument("--json", action="store_true", dest="as_json",
                         help="emit the annotated tree as JSON")
    p_trace.add_argument("--export", choices=("chrome",), default=None,
                         help="export format: 'chrome' writes Trace Event "
                              "Format JSON (chrome://tracing / Perfetto)")
    p_trace.add_argument("out", nargs="?", default=None,
                         help="output path for --export (default "
                              "trace.json)")
    p_trace.add_argument("--trace-id", default=None,
                         help="restrict the export to one job's trace id")

    p_perf = sub.add_parser(
        "perf",
        help="per-program roofline/compile attribution from the last run "
             "(`tmx perf --root DIR`), or the bench history + regression "
             "verdict (`tmx perf history`)",
    )
    # --root is optional here (unlike _add_common): `tmx perf history`
    # reads tuning/BENCH_HISTORY.jsonl, no experiment store involved
    p_perf.add_argument("--root", default=None,
                        help="experiment store directory (roofline table + "
                             "phase breakdown from its last run)")
    p_perf.add_argument("-v", "--verbosity", action="count", default=0)
    p_perf.add_argument("--top", type=int, default=10,
                        help="show the N costliest programs (default 10)")
    p_perf.add_argument("--json", action="store_true", dest="as_json",
                        help="emit the attribution as JSON")
    perf_sub = p_perf.add_subparsers(dest="verb")
    p_phist = perf_sub.add_parser(
        "history",
        help="bench history tail + sentinel verdict (latest vs best "
             "comparable record)",
    )
    p_phist.add_argument("--history", default=None,
                         help="history file (default tuning/"
                              "BENCH_HISTORY.jsonl, BENCH_HISTORY env)")
    p_phist.add_argument("--config", default=None,
                         help="judge this bench config only")
    p_phist.add_argument("--metric", default=None,
                         help="judge this metric only")
    p_phist.add_argument("--threshold", type=float, default=0.05,
                         help="regression/improvement fraction "
                              "(default 0.05)")
    p_phist.add_argument("--stale-hours", type=float, default=None,
                         dest="stale_hours",
                         help="staleness budget (default BENCH_STALE_HOURS "
                              "or 72)")
    p_phist.add_argument("--tail", type=int, default=10,
                         help="history lines to print (default 10)")

    p_cache = sub.add_parser(
        "cache",
        help="the serialized-executable store (aotstore): list entries "
             "or garbage-collect stale/oversize artifacts",
    )
    p_cache.add_argument("-v", "--verbosity", action="count", default=0)
    cache_sub = p_cache.add_subparsers(dest="verb", required=True)
    p_clist = cache_sub.add_parser(
        "list", help="store entries, most recently used first")
    p_clist.add_argument("--dir", default=None, dest="store_dir",
                         help="store directory (default TMX_AOT_STORE_DIR, "
                              "config aot_store_dir, or beside the compile "
                            "cache)")
    p_clist.add_argument("--json", action="store_true", dest="as_json",
                         help="emit entries + stats as JSON (CI manifest)")
    p_cgc = cache_sub.add_parser(
        "gc", help="evict stale-fingerprint, over-age and over-cap entries")
    p_cgc.add_argument("--dir", default=None, dest="store_dir",
                       help="store directory (default TMX_AOT_STORE_DIR, "
                            "config aot_store_dir, or beside the compile "
                            "cache)")
    p_cgc.add_argument("--max-bytes", type=int, default=None,
                       dest="max_bytes",
                       help="LRU size cap to enforce (default the "
                            "configured store cap)")
    p_cgc.add_argument("--max-age-days", type=float, default=None,
                       dest="max_age_days",
                       help="drop entries unused for this many days")
    p_cgc.add_argument("--keep-stale", action="store_true",
                       dest="keep_stale",
                       help="keep entries from other jax/backend "
                            "fingerprints (default: drop them)")
    p_cgc.add_argument("--json", action="store_true", dest="as_json",
                       help="emit the gc summary as JSON")

    p_qc = sub.add_parser(
        "qc",
        help="data-quality report for a run (per-step table, worst-focus "
             "sites, flagged sites) + drift verdict vs a reference "
             "profile; exit codes: 0 ok, 1 drift, 2 stale reference, "
             "3 no reference",
    )
    _add_common(p_qc)
    p_qc.add_argument("--json", action="store_true", dest="as_json",
                      help="emit the QC report + verdict as JSON")
    p_qc.add_argument("--worst", type=int, default=5, metavar="N",
                      help="worst-focus sites to list (default 5)")
    p_qc.add_argument("--reference", default=None, metavar="PATH",
                      help="reference qc.json profile for the drift "
                           "sentinel (default: TMX_QC_BASELINE env, then "
                           "tuning/QC_BASELINE.json if present)")
    p_qc.add_argument("--threshold", type=float, default=0.25,
                      help="drift threshold: allowed median shift as a "
                           "fraction of the reference spread "
                           "(default 0.25)")
    p_qc.add_argument("--stale-hours", type=float, default=None,
                      dest="stale_hours",
                      help="reference staleness budget in hours (default "
                           "TMX_QC_STALE_HOURS, 0 = no staleness check — "
                           "committed baselines age by design)")
    p_qc.add_argument("--profile-kind", choices=("run", "model"),
                      default="run", dest="profile_kind",
                      help="what to compare: 'run' = acquisition + "
                           "feature drift (the default); 'model' = only "
                           "the __model__.* sketches (DL flow-magnitude/"
                           "probability streams) vs the committed "
                           "checkpoint baseline (default reference "
                           "TMX_QC_DL_BASELINE env, then "
                           "tuning/QC_DL_BASELINE.json) — the model "
                           "deploy gate")

    p_weights = sub.add_parser(
        "weights",
        help="DL segmentation checkpoints (tmlibrary_tpu.nn): list the "
             "weights directory or digest a weight spec",
    )
    w_sub = p_weights.add_subparsers(dest="verb", required=True)
    p_wl = w_sub.add_parser(
        "list", help="inventory of the weights directory "
                     "(TMX_WEIGHTS_DIR) with content digests")
    p_wl.add_argument("--dir", default=None,
                      help="weights directory (default TMX_WEIGHTS_DIR)")
    p_wl.add_argument("--json", action="store_true", dest="as_json")
    p_wd = w_sub.add_parser(
        "digest", help="resolve a weight spec (name, path or seed:N) and "
                       "print its content digest — the identity the "
                       "compiled-program cache and the bench sentinel "
                       "key on")
    p_wd.add_argument("spec", help="checkpoint name, .npz path, or "
                                   "seed:N[:base=C][:depth=D]")
    p_wd.add_argument("--json", action="store_true", dest="as_json")

    p_wf = sub.add_parser("workflow", help="full workflow orchestration")
    wf_sub = p_wf.add_subparsers(dest="verb", required=True)
    # submit and resume (the reference's verb) share the same options and
    # code path; resume just defaults resume=True
    shared = argparse.ArgumentParser(add_help=False)
    shared.add_argument(
        "--description",
        help="workflow YAML (default: the store's workflow/workflow.yaml)",
    )
    shared.add_argument("--profile", metavar="DIR", default=None,
                        help="write a jax.profiler device trace to DIR")
    shared.add_argument(
        "--pipeline-depth", type=int, default=None, metavar="N",
        help="in-flight device batches for the pipelined executor "
             "(default: TM_PIPELINE_DEPTH / config, else the tuning "
             "sweep's best_pipeline on device backends, else a safe "
             "per-backend default; 1 = minimal overlap)",
    )
    shared.add_argument(
        "--object-buckets", default=None, metavar="SPEC",
        help="object-capacity bucket ladder for the jterator step "
             "(capacity.py): 'auto' compiles power-of-two capacity "
             "buckets up to max_objects and routes each batch by its "
             "observed object counts (bit-identical results, fewer "
             "padded-slot FLOPs), 'off' pins every batch at "
             "max_objects, or a comma list of capacities like '8,32' "
             "(default: TMX_OBJECT_BUCKETS / TM_OBJECT_BUCKETS config, "
             "else auto)",
    )
    shared.add_argument(
        "--schedule", default=None, metavar="MODE",
        choices=("auto", "pack", "off"),
        help="work-aware site scheduling for the jterator step "
             "(workflow/schedule.py): 'pack' predicts per-site object "
             "counts from prior-run history, packs rung-homogeneous "
             "batches and balances per-device shard work (bit-identical "
             "per-site results, higher slot occupancy, lower straggler "
             "skew), 'off' keeps directory-order batching, 'auto' "
             "follows TMX_SCHEDULE / TM_SCHEDULE config, else the "
             "provenance-gated tuning/TUNING.json verdict, else pack",
    )
    # fault-tolerance knobs (resilience.py; defaults from LibraryConfig /
    # TM_RETRY_ATTEMPTS, TM_MAX_BATCH_FAILURES, ... env)
    shared.add_argument(
        "--max-batch-failures", type=float, default=None, metavar="X",
        help="per-step quarantine budget before the step fails: a value "
             "< 1 is a fraction of the step's batches, >= 1 an absolute "
             "count (default 0.5); 0 disables quarantine (first failure "
             "aborts the step, the pre-resilience behavior)",
    )
    shared.add_argument(
        "--retry-attempts", type=int, default=None, metavar="N",
        help="total tries per batch for transient faults (1 = no retry)",
    )
    shared.add_argument(
        "--retry-delay", type=float, default=None, metavar="SECONDS",
        help="first backoff delay; doubles per retry, with jitter",
    )
    shared.add_argument(
        "--probe-timeout", type=float, default=None, metavar="SECONDS",
        help="device health probe deadline before the circuit breaker "
             "counts a failure (an unreachable device can hang, not error)",
    )
    shared.add_argument(
        "--no-telemetry", action="store_true",
        help="disable the metrics registry, span events and resource "
             "sampler for this run (also: TM_TELEMETRY=0)",
    )
    shared.add_argument(
        "--sample-resources", type=float, default=None, metavar="SECONDS",
        help="resource sampler period (RSS/fds/device-memory gauges + "
             "heartbeat file; default from TM_RESOURCE_SAMPLE_PERIOD, "
             "0 disables)",
    )
    shared.add_argument(
        "--qc", action=argparse.BooleanOptionalAction, default=None,
        help="collect data-quality evidence for this run (qc.py): fused "
             "on-device image stats, NaN/outlier guards, feature "
             "sketches -> workflow/qc.json + qc_* ledger events, "
             "inspected with `tmx qc` (default: TMX_QC / TM_QC config, "
             "off; --no-qc forces off)",
    )
    p_submit = wf_sub.add_parser("submit", help="run the workflow",
                                 parents=[shared])
    _add_common(p_submit)
    p_submit.add_argument("--resume", action="store_true",
                          help="skip work completed in a previous run")
    p_resume = wf_sub.add_parser(
        "resume", help="shorthand for submit --resume (reference verb)",
        parents=[shared],
    )
    _add_common(p_resume)
    p_resume.set_defaults(resume=True)
    p_status = wf_sub.add_parser("status", help="per-step progress")
    _add_common(p_status)
    p_clean = wf_sub.add_parser(
        "cleanup", help="remove every step's outputs, batch plans and the "
                        "run ledger (reference cleanup verb, workflow-wide)"
    )
    _add_common(p_clean)
    p_tmpl = wf_sub.add_parser(
        "template", help="write a typed skeleton workflow.yaml"
    )
    _add_common(p_tmpl)
    p_tmpl.add_argument(
        "--type", dest="wf_type", choices=("canonical", "multiplexing"),
        default="canonical", help="workflow type (multiplexing adds align)",
    )

    p_serve = sub.add_parser(
        "serve", help="always-on analysis service (spool-fed job stream "
                      "with admission control)")
    serve_sub = p_serve.add_subparsers(dest="verb", required=True)
    p_srun = serve_sub.add_parser(
        "run", help="run the serve daemon over a spool root")
    _add_common(p_srun)
    p_srun.add_argument("--max-queue", type=int, default=None, metavar="N",
                        help="admission-queue high watermark: at this depth "
                             "new jobs are shed with the pinned queue_full "
                             "retry-after (default TM_SERVE_MAX_QUEUE, 64)")
    p_srun.add_argument("--low-watermark", type=int, default=None,
                        metavar="N",
                        help="shedding stops once the queue drains to this "
                             "depth (hysteresis; default max-queue/2)")
    p_srun.add_argument("--tenant-quota", type=int, default=None,
                        metavar="N",
                        help="max queued jobs per tenant (default "
                             "TM_SERVE_TENANT_QUOTA, 16)")
    p_srun.add_argument("--retry-budget", type=int, default=None,
                        metavar="N",
                        help="per-tenant retry budget: resubmissions spend "
                             "one token, successes refund one (default "
                             "TM_SERVE_RETRY_BUDGET, 8)")
    p_srun.add_argument("--tenant-weights", default=None, metavar="T=W,...",
                        help="weighted deficit-round-robin weights, e.g. "
                             "'prod=3,dev=1' (default: 1 each)")
    p_srun.add_argument("--poll", type=float, default=None,
                        metavar="SECONDS",
                        help="spool poll period (default TM_SERVE_POLL_S, "
                             "0.5)")
    p_srun.add_argument("--max-jobs", type=int, default=0, metavar="N",
                        help="exit 0 after N completed jobs (0 = serve "
                             "forever; CI/smoke harnesses)")
    p_srun.add_argument("--idle-exit", type=float, default=0.0,
                        metavar="SECONDS",
                        help="exit 0 after this long with an empty queue "
                             "(0 = never)")
    p_srun.add_argument("--no-telemetry", action="store_true",
                        help="disable the metrics registry for the daemon")
    p_srun.add_argument("--host", default=None, metavar="ID",
                        help="fleet host identity: claims are leased as "
                             "this id and events/heartbeats land in "
                             "per-host files (default TMX_HOST_ID when a "
                             "fleet is active, else single-host mode)")
    p_srun.add_argument("--lease", type=float, default=None,
                        metavar="SECONDS",
                        help="claim lease duration; an expired lease whose "
                             "owner's heartbeat is stale is reclaimed by "
                             "a peer (default TM_SERVE_LEASE_S, 15)")
    p_srun.add_argument("--canary", type=float, default=None,
                        metavar="SECONDS",
                        help="canary probe period: enqueue one tiny "
                             "self-addressed health probe this often "
                             "(default TM_SERVE_CANARY_PERIOD_S, 0 = off)")
    p_sstatus = serve_sub.add_parser(
        "status", help="queue depth, per-tenant admitted/rejected/"
                       "budget-remaining, oldest-job age")
    _add_common(p_sstatus)
    p_sstatus.add_argument("--json", action="store_true", dest="as_json",
                           help="emit the full status view as JSON")

    p_enq = sub.add_parser(
        "enqueue", help="submit one job spec to a serve spool")
    _add_common(p_enq)
    p_enq.add_argument("--experiment", required=True, metavar="DIR",
                       help="experiment store root the job runs against")
    p_enq.add_argument("--tenant", default="default",
                       help="tenant the job is accounted to")
    p_enq.add_argument("--job-id", default=None,
                       help="unique job id (default: generated)")
    p_enq.add_argument("--description", default=None,
                       help="workflow YAML (default: the experiment's "
                            "workflow/workflow.yaml)")
    p_enq.add_argument("--priority", type=int, default=0,
                       help="within-tenant priority (higher first)")
    p_enq.add_argument("--deadline", type=float, default=None,
                       metavar="SECONDS",
                       help="relative deadline; an expired job is "
                            "cancelled at the next batch boundary")
    p_enq.add_argument("--pipeline-depth", type=int, default=None,
                       metavar="N", help="per-job pipelined-executor depth")
    p_enq.add_argument("--attempt", type=int, default=0, metavar="N",
                       help="resubmission count (attempt > 0 spends one "
                            "retry-budget token)")
    p_enq.add_argument("--trace-id", default=None,
                       help="end-to-end trace correlation id (default: "
                            "generated); every ledger event the job "
                            "produces carries it, and `tmx trace --export "
                            "chrome --trace-id ID` renders the full "
                            "enqueue-to-result timeline")
    p_enq.add_argument("--kind", choices=("workflow", "query"),
                       default="workflow",
                       help="job kind: 'workflow' runs the experiment's "
                            "workflow; 'query' answers one analytics "
                            "query (digest-cached; see `tmx query`)")
    p_enq.add_argument("--tool", default=None,
                       help="query jobs: tool name (clustering, heatmap, "
                            "classification, knn, pca, embedding, "
                            "spatial) — merged into the payload")
    p_enq.add_argument("--objects", default=None, metavar="NAME",
                       help="query jobs: objects_name shorthand — merged "
                            "into the payload")
    p_enq.add_argument("--payload", default=None,
                       help="query jobs: payload as inline JSON")
    p_enq.add_argument("--payload-file", default=None,
                       help="query jobs: payload from a JSON file")
    p_enq.add_argument("--index", default=None,
                       choices=["auto", "ivf", "brute"],
                       help="query jobs: kNN index routing — merged into "
                            "the payload (default: auto via env/config/"
                            "tuned verdict/store size)")
    p_enq.add_argument("--affinity-key", default=None, metavar="KEY",
                       help="compiled-program affinity key for fleet "
                            "routing (default: auto-derived content "
                            "digest of the workflow description + "
                            "jterator pipelines; hosts prefer jobs whose "
                            "key is warm in their compile caches)")

    p_query = sub.add_parser(
        "query", help="one-shot analytics query over an experiment's "
                      "feature store (kNN/PCA/embedding/spatial/"
                      "clustering/heatmap/classification; results are "
                      "cached by feature-store digest — the daemon path "
                      "is `tmx enqueue --kind query`)")
    _add_common(p_query)
    p_query.add_argument("--tool", required=True,
                         help="tool name (see 'tmx tool available')")
    p_query.add_argument("--objects", default=None, metavar="NAME",
                         help="objects_name shorthand (else put "
                              "objects_name in the payload)")
    p_query.add_argument("--payload", default=None,
                         help="tool payload as inline JSON")
    p_query.add_argument("--payload-file", default=None,
                         help="tool payload from a JSON file")
    p_query.add_argument("--index", default=None,
                         choices=["auto", "ivf", "brute"],
                         help="kNN index routing (knn/embedding/"
                              "clustering/classification tools) — merged "
                              "into the payload")
    p_query.add_argument("--no-cache", action="store_true",
                         help="recompute even when a digest-keyed cached "
                              "result exists")

    p_index = sub.add_parser(
        "index", help="IVF kNN index over an experiment's feature store "
                      "(analytics/index.py): build or inspect the "
                      "persisted per-selection index artifacts")
    index_sub = p_index.add_subparsers(dest="verb", required=True)
    p_ibuild = index_sub.add_parser(
        "build", help="build (or reuse) the index for one objects_name; "
                      "prints the manifest JSON")
    _add_common(p_ibuild)
    p_ibuild.add_argument("--objects", required=True, metavar="NAME",
                          help="mapobject type to index")
    p_ibuild.add_argument("--features", default=None,
                          help="comma list of feature columns (default: "
                               "all)")
    p_ibuild.add_argument("--cells", type=int, default=None,
                          help="cell count override (default: 4*sqrt(N))")
    p_ibuild.add_argument("--rebuild", action="store_true",
                          help="force a rebuild even when the persisted "
                               "index matches the live store digest")
    p_ilist = index_sub.add_parser(
        "list", help="list persisted indexes for one objects_name with "
                     "staleness vs the live store digest")
    _add_common(p_ilist)
    p_ilist.add_argument("--objects", required=True, metavar="NAME")

    p_slo = sub.add_parser(
        "slo", help="per-tenant SLO report over a serve root: p50/p95 "
                    "latency, availability, multi-window burn rates "
                    "(exit 0 ok / 1 burn / 3 no data)")
    _add_common(p_slo)
    p_slo.add_argument("--json", action="store_true", dest="as_json",
                       help="emit the report as JSON")

    p_tool = sub.add_parser("tool", help="analysis tools over the feature store")
    tool_sub = p_tool.add_subparsers(dest="verb", required=True)
    p_tsubmit = tool_sub.add_parser("submit", help="run one tool request")
    _add_common(p_tsubmit)
    p_tsubmit.add_argument("--name", required=True,
                           help="tool name (see 'tool available')")
    p_tsubmit.add_argument("--payload", default="{}",
                           help="request payload as inline JSON")
    p_tsubmit.add_argument("--payload-file", default=None,
                           help="request payload from a JSON file")
    p_tsubmit.add_argument("--background", action="store_true",
                           help="run the request as a detached job and "
                                "return its id immediately (reference "
                                "ToolJob fan-out); poll with 'tool status'")
    p_tlist = tool_sub.add_parser(
        "list", help="tool requests with lifecycle state")
    _add_common(p_tlist)
    p_tstatus = tool_sub.add_parser("status", help="one request's state")
    _add_common(p_tstatus)
    p_tstatus.add_argument("--request", required=True)
    p_trun = tool_sub.add_parser(
        "run-request", help="execute a submitted request (internal: the "
                            "--background job body)")
    _add_common(p_trun)
    p_trun.add_argument("--request", required=True)
    tool_sub.add_parser("available", help="registered tool names")

    p_proj = sub.add_parser("project", help="manage a jterator pipeline project")
    proj_sub = p_proj.add_subparsers(dest="verb", required=True)
    p_pcreate = proj_sub.add_parser("create", help="create a skeleton project")
    p_pcreate.add_argument("--dir", required=True, help="project directory")
    p_pcreate.add_argument("--description", default="")
    p_padd = proj_sub.add_parser("add-module", help="append a module instance")
    p_padd.add_argument("--dir", required=True)
    p_padd.add_argument("--module", required=True)
    p_padd.add_argument("--instance", default=None)
    p_premove = proj_sub.add_parser("remove-module", help="remove a module instance")
    p_premove.add_argument("--dir", required=True)
    p_premove.add_argument("--instance", required=True)
    p_pchan = proj_sub.add_parser("add-channel", help="declare an input channel")
    p_pchan.add_argument("--dir", required=True)
    p_pchan.add_argument("--name", required=True)
    p_pchan.add_argument("--no-correct", action="store_true")
    p_pchan.add_argument("--align", action="store_true")
    p_pshow = proj_sub.add_parser("show", help="modules in pipeline order")
    p_pshow.add_argument("--dir", required=True)
    proj_sub.add_parser("modules", help="registered module names")
    p_pcheck = proj_sub.add_parser(
        "check", help="validate a pipeline without running it: dataflow, "
                      "module names, parameter names (reference jterator's "
                      "pipeline check role)")
    p_pcheck.add_argument("--pipe", required=True, help="path to .pipe.yaml")

    for name in list_steps():
        step_cls = get_step(name)
        p_step = sub.add_parser(name, help=f"{name} step")
        verb_sub = p_step.add_subparsers(dest="verb", required=True)
        p_init = verb_sub.add_parser("init", help="plan batches")
        _add_common(p_init)
        step_cls.batch_args.add_to_parser(p_init)
        p_run = verb_sub.add_parser("run", help="run one batch (or all)")
        _add_common(p_run)
        p_run.add_argument("--job", type=int, default=None,
                           help="batch index (default: all)")
        p_collect = verb_sub.add_parser("collect", help="merge phase")
        _add_common(p_collect)
        p_info = verb_sub.add_parser("info", help="planned batches")
        _add_common(p_info)
        p_clean = verb_sub.add_parser(
            "cleanup", help="delete this step's previous outputs"
        )
        _add_common(p_clean)
        verb_sub.add_parser("args", help="argument schema as JSON")
    return parser


def _open_store(args) -> ExperimentStore:
    return ExperimentStore.open(Path(args.root))


def _render_heartbeats(hb_dir: Path, running: bool) -> None:
    """Heartbeat liveness lines, shared by ``tmx workflow status`` and
    ``tmx serve status``: a running process with a stale heartbeat is a
    HUNG one (sampler/daemon thread dead or blocked), not a slow one."""
    from tmlibrary_tpu import telemetry

    for hb_path in sorted(Path(hb_dir).glob("heartbeat*.json")):
        hb = telemetry.read_heartbeat(hb_path)
        if not hb or "ts" not in hb:
            continue
        # fresher-of(embedded ts, file mtime): cross-host clock skew
        # must not flag a live remote host's run as hung
        age = telemetry.heartbeat_age(hb_path)
        period = float(hb.get("period", 0) or 0)
        host = str(hb.get("host") or "host0")
        tag = "" if host == "host0" else f"[{host}]"
        line = (f"heartbeat{tag}: {age:.1f}s ago "
                f"(sampler period {period:g}s)")
        if running and period > 0 and age > 2 * period:
            line += " — STALE: run appears hung"
        print(line)


def _cleanup_step(step) -> None:
    """One step's cleanup recipe (shared by the per-step verb and
    workflow-wide cleanup): outputs + batch plans."""
    step.delete_previous_output()
    for p in step.step_dir.glob("batch_*.json"):
        p.unlink()


#: reader attributes surfaced by ``tmx inspect`` (whichever exist)
_INSPECT_ATTRS = (
    "height", "width", "n_channels", "n_zplanes", "n_tpoints",
    "n_series", "n_scenes", "n_tiles", "n_sequences", "n_components",
    "n_fields",
)


def _inspect_source_dir(src: Path) -> dict:
    """Dry-run ingest preview of a source DIRECTORY: which sidecar
    handler resolves it (metaconfig's auto order) and the layout it
    would produce — without creating a store."""
    from tmlibrary_tpu.errors import VendorConflictError
    from tmlibrary_tpu.workflow.steps.vendors import (
        SIDECAR_HANDLERS,
        resolve_sidecars,
    )

    try:
        # the SAME resolution loop metaconfig's auto mode runs — a
        # separate copy here would drift from real ingest behavior
        resolved = resolve_sidecars(src, list(SIDECAR_HANDLERS), True)
    except VendorConflictError as exc:
        return {"format": "source-dir", "error": str(exc)}
    if resolved is None:
        return {
            "format": "source-dir",
            "handler": None,
            "note": "no sidecar handler resolved this directory; "
                    "metaconfig would fall back to filename patterns",
        }
    handler, entries, skipped = resolved
    wells = {(e["plate"], e["well_row"], e["well_col"]) for e in entries}
    return {
        "format": "source-dir",
        "handler": handler,
        "n_planes": len(entries),
        "n_skipped_files": skipped,
        "n_wells": len(wells),
        "n_sites": len({
            (e["plate"], e["well_row"], e["well_col"], e["site"])
            for e in entries
        }),
        "channels": sorted({e["channel"] for e in entries}),
        "n_zplanes": max(e["zplane"] for e in entries) + 1,
        "n_tpoints": max(e["tpoint"] for e in entries) + 1,
        "n_cycles": max(e["cycle"] for e in entries) + 1,
    }


def cmd_inspect(args) -> int:
    """Bio-Formats ``showinf`` equivalent over the first-party parsers
    (reference users inspect vendor files with showinf before ingest;
    SURVEY.md §3 Readers row).  Prints dims/channels per file — or, for
    a source DIRECTORY, a dry-run ingest preview (resolved handler +
    layout).  Exits non-zero if anything could not be read."""
    from tmlibrary_tpu import readers as _readers

    failed = 0
    for name in args.files:
        path = Path(name)
        info: dict = {"file": str(path)}
        if path.is_dir() and not str(path).lower().endswith(".zarr"):
            preview = _inspect_source_dir(path)
            info.update(preview)
            # an unresolved dir is a legitimate answer (filename-pattern
            # fallback), NOT a failure; a well conflict is
            if "error" in preview:
                failed += 1
            if args.as_json:
                print(json.dumps(info))
            else:
                print(f"{info['file']}: source dir "
                      f"(handler={info.get('handler')})")
                for key, val in info.items():
                    if key not in ("file", "format", "handler"):
                        print(f"  {key:16s} {val}")
            continue
        try:
            # _open_container, not _container_reader: a TIFF-flavored
            # container the dedicated reader declines (RGB .flex/.stk)
            # must fall to the plain-image branch exactly like ingest does
            r = _readers._open_container(path)
            if r is not None:
                try:
                    info["format"] = type(r).__name__.replace("Reader", "")
                    for attr in _INSPECT_ATTRS:
                        val = getattr(r, attr, None)
                        if val is not None:
                            info[attr] = int(val)
                    names = getattr(r, "channel_names", None)
                    if callable(names):
                        names = names()
                    if names:
                        info["channel_names"] = list(names)
                    loops = getattr(r, "loop_shape", None)
                    if callable(loops):
                        loops = loops()
                    if loops:  # ND2 acquisition nesting, outermost first
                        info["loops"] = [[kind, size] for kind, size in loops]
                finally:
                    r.__exit__()
            else:
                plane = _readers.ImageReader(path).read(0)
                info["format"] = "image"
                info["height"], info["width"] = map(int, plane.shape[:2])
                info["dtype"] = str(plane.dtype)
        except Exception as exc:
            info["error"] = str(exc)
            failed += 1
        if args.as_json:
            print(json.dumps(info))
        else:
            head = f"{info['file']}: " + (
                f"ERROR {info['error']}" if "error" in info
                else info.get("format", "?")
            )
            print(head)
            for key, val in info.items():
                if key not in ("file", "format", "error"):
                    print(f"  {key:14s} {val}")
    return 1 if failed else 0


def cmd_create(args) -> int:
    root = Path(args.root)
    if (root / ExperimentStore.MANIFEST).exists():
        print(f"error: store already exists at {root}", file=sys.stderr)
        return 1
    placeholder = Experiment(
        name=args.name, plates=[], channels=[], site_height=1, site_width=1
    )
    ExperimentStore.create(root, placeholder)
    print(f"created experiment '{args.name}' at {root}")
    return 0


def cmd_workflow(args) -> int:
    store = _open_store(args)
    if args.verb == "status":
        status = RunLedger(store.workflow_dir / "ledger.jsonl").status()
        from tmlibrary_tpu.tools.base import ToolRequestManager

        tool_requests = ToolRequestManager(store).list_requests()
        if not status and not tool_requests:
            print("no workflow runs recorded")
            return 0
        for step, entry in status.items():
            done = entry["batches_done"]
            total = entry["n_batches"]
            frac = f"{done}/{total}" if total is not None else str(done)
            line = f"{step:12s} {entry['state']:8s} batches {frac} " \
                   f"({entry['elapsed']:.1f}s)"
            if entry.get("quarantined"):
                line += f" quarantined: {sorted(entry['quarantined'])}"
            if entry.get("error"):
                line += f" error: {entry['error']}"
            print(line)
            ps = entry.get("pipeline_stats")
            if ps:
                phases = " ".join(
                    f"{ph}={v['total_s']:.2f}s"
                    for ph, v in ps.get("phases", {}).items()
                )
                print(f"{'':12s} pipeline depth {ps.get('depth')} "
                      f"({ps.get('source')}) over {ps.get('n_batches')} "
                      f"batches: {phases}")
            for clamp in entry.get("depth_clamps", []):
                print(f"{'':12s} depth clamped {clamp.get('from')} -> "
                      f"{clamp.get('to')} (resource exhausted)")
            if entry.get("watchdog_fires"):
                print(f"{'':12s} watchdog fired {entry['watchdog_fires']} "
                      "time(s) — hung phase(s) classified transient")
            buckets = entry.get("buckets")
            if buckets:
                routed = " ".join(
                    f"cap{c}x{n}" for c, n in sorted(
                        buckets["routed"].items(), key=lambda kv: int(kv[0])
                    )
                )
                line = f"{'':12s} buckets: {routed}"
                if buckets.get("occupancy_n"):
                    occ = buckets["occupancy_sum"] / buckets["occupancy_n"]
                    line += f" slot occupancy {occ:.1%}"
                if buckets.get("escalations"):
                    line += f" escalations {buckets['escalations']}"
                print(line)
            qc_entry = entry.get("qc")
            if qc_entry:
                line = (f"{'':12s} qc: flagged "
                        f"{qc_entry.get('flagged', 0)} site(s)")
                if qc_entry.get("nan_columns"):
                    line += f" nan columns {qc_entry['nan_columns']}"
                if qc_entry.get("worst_focus") is not None:
                    line += f" worst focus {qc_entry['worst_focus']:.4g}"
                if qc_entry.get("budget_exceeded"):
                    line += " ** OVER FLAG BUDGET — inspect with tmx qc **"
                print(line)
        ledger = RunLedger(store.workflow_dir / "ledger.jsonl")
        preempted = ledger.preempted()
        if preempted:
            print(f"PREEMPTED ({preempted.get('reason', 'signal')}) at step "
                  f"'{preempted.get('step')}': drained "
                  f"{preempted.get('drained', 0)}/"
                  f"{preempted.get('in_flight', 0)} in-flight, abandoned "
                  f"{preempted.get('abandoned', 0)} — resume with "
                  "`tmx workflow submit --resume`")
        running = any(e.get("state") == "running" for e in status.values())
        _render_heartbeats(store.workflow_dir, running)
        # tool request lifecycle (reference ToolRequestManager submissions
        # surface in the same status view the UI polls)
        for req in tool_requests:
            line = f"tool:{req['request']:30s} {req.get('state', '?'):8s}"
            if req.get("error"):
                line += f" error: {req['error']}"
            print(line)
        return 0
    if args.verb == "cleanup":
        from tmlibrary_tpu.models.mapobject import MapobjectTypeRegistry

        for name in list_steps():
            _cleanup_step(get_step(name)(store))
        # the registry would otherwise advertise object types whose
        # label/feature artifacts were just removed
        registry = MapobjectTypeRegistry(store.root)
        for name in registry.names():
            registry.delete(name)
        ledger_path = store.workflow_dir / "ledger.jsonl"
        ledger_path.unlink(missing_ok=True)
        print("removed all step outputs, batch plans, mapobject "
              "registrations and the run ledger")
        return 0
    if args.verb == "template":
        out = store.workflow_dir / "workflow.yaml"
        if out.exists():
            print(f"error: {out} already exists", file=sys.stderr)
            return 1
        WorkflowDescription.for_type(args.wf_type).save(out)
        print(f"wrote {args.wf_type} workflow template to {out} — fill in "
              "step args and set active: true on the steps to run")
        return 0
    # submit
    if args.description:
        desc = WorkflowDescription.load(Path(args.description))
    else:
        wf_yaml = store.workflow_dir / "workflow.yaml"
        if wf_yaml.exists():
            desc = WorkflowDescription.load(wf_yaml)
        else:
            print("error: no workflow description (pass --description or put "
                  "workflow.yaml in the store's workflow dir)", file=sys.stderr)
            return 1
    from tmlibrary_tpu import telemetry
    from tmlibrary_tpu.profiling import device_trace
    from tmlibrary_tpu.resilience import ResilienceConfig

    if args.no_telemetry:
        telemetry.set_enabled(False)
    if getattr(args, "object_buckets", None):
        import os as _os

        # the env (not a plumbed parameter): the bucket router resolves
        # the spec at every launch (capacity.py resolution order), so
        # the request must outlive this function; "auto" clears any
        # stale explicit request
        if args.object_buckets == "auto":
            _os.environ.pop("TMX_OBJECT_BUCKETS", None)
        else:
            _os.environ["TMX_OBJECT_BUCKETS"] = args.object_buckets
    if getattr(args, "schedule", None):
        import os as _os

        # same env pattern as --object-buckets: the scheduler resolves
        # its mode at init/create_batches time (workflow/schedule.py
        # precedence: explicit > env > config > tuning > default), so
        # the request must outlive this function; "auto" clears any
        # stale explicit request so the chain falls through
        if args.schedule == "auto":
            _os.environ.pop("TMX_SCHEDULE", None)
        else:
            _os.environ["TMX_SCHEDULE"] = args.schedule
    if getattr(args, "qc", None) is not None:
        import os as _os

        # env (not a plumbed parameter), same pattern as
        # --object-buckets: the QC gate is part of the compiled-
        # program cache key (jterator.pipeline.cached_batch_fn) and is
        # re-read at every build site, so the request must outlive this
        # function; an explicit --no-qc writes "0" to beat the config
        _os.environ["TMX_QC"] = "1" if args.qc else "0"
    if args.sample_resources is not None:
        from tmlibrary_tpu.config import cfg as _cfg

        _cfg.resource_sample_period = args.sample_resources
    resilience = ResilienceConfig.from_library_config()
    if args.max_batch_failures is not None:
        resilience.max_batch_failures = args.max_batch_failures
    if args.retry_attempts is not None or args.retry_delay is not None:
        import dataclasses as _dc

        resilience.policy = _dc.replace(
            resilience.policy,
            **{k: v for k, v in (
                ("max_attempts", args.retry_attempts),
                ("base_delay", args.retry_delay),
            ) if v is not None},
        )
    if args.probe_timeout is not None and resilience.guard is not None:
        resilience.guard.timeout = args.probe_timeout
    from tmlibrary_tpu.errors import PreemptedError
    from tmlibrary_tpu.resilience import (
        EXIT_PREEMPTED,
        install_preemption_handlers,
    )

    # SIGTERM/SIGINT ask for a graceful drain instead of killing the
    # process mid-batch: the engine stops admitting work, persists the
    # in-flight window, records run_preempted and we exit with the
    # pinned code so wrappers re-launch `tmx workflow submit --resume`
    restore = install_preemption_handlers()
    try:
        with device_trace(args.profile):
            summary = Workflow(store, desc, resilience=resilience,
                               pipeline_depth=args.pipeline_depth).run(
                resume=args.resume
            )
    except PreemptedError as exc:
        print(f"preempted ({exc.reason}): drained {exc.drained}/"
              f"{exc.in_flight} in-flight batches at step '{exc.step}', "
              f"abandoned {exc.abandoned} — resume with "
              "`tmx workflow submit --resume`", file=sys.stderr)
        return EXIT_PREEMPTED
    finally:
        restore()
    print(json.dumps(summary, default=str, indent=2))
    return 0


def cmd_serve(args) -> int:
    from tmlibrary_tpu import serve as serve_mod

    root = Path(args.root)
    if args.verb == "status":
        view = serve_mod.serve_status_view(root)
        if args.as_json:
            print(json.dumps(view, indent=2, sort_keys=True))
            return 0
        live = "LIVE" if view.get("live") else "not running"
        print(f"serve root: {view['root']}  [{live}]")
        status = view.get("status") or {}
        if status:
            depth = status.get("depth", 0)
            line = (f"queue depth {depth}/{status.get('high_watermark', '?')}"
                    f" (low watermark {status.get('low_watermark', '?')})")
            if status.get("shedding"):
                line += " — SHEDDING"
            print(line)
            age = status.get("oldest_job_age_s")
            if age is not None:
                print(f"oldest queued job: {age:.1f}s ago")
        spool = view.get("spool") or {}
        if spool:
            print("spool: " + "  ".join(
                f"{state} {n}" for state, n in spool.items()))
        # per-tenant table: live queue/budget/breaker state from the
        # daemon's snapshot, lifetime outcomes from the serve ledger
        live_tenants = (status.get("tenants") or {})
        ledger_tenants = view.get("tenants") or {}
        names = sorted(set(live_tenants) | set(ledger_tenants))
        if names:
            print(f"{'tenant':16s} {'queued':>6s} {'admitted':>8s} "
                  f"{'rejected':>8s} {'done':>5s} {'failed':>6s} "
                  f"{'budget':>6s} breaker")
            for name in names:
                lt = live_tenants.get(name, {})
                gt = ledger_tenants.get(name, {})
                print(f"{name:16s} {lt.get('queued', 0):>6d} "
                      f"{gt.get('admitted', lt.get('admitted', 0)):>8d} "
                      f"{gt.get('rejected', lt.get('rejected', 0)):>8d} "
                      f"{gt.get('done', 0):>5d} {gt.get('failed', 0):>6d} "
                      f"{str(lt.get('retry_budget_remaining', '-')):>6s} "
                      f"{lt.get('breaker', '-')}")
        if view.get("preemptions"):
            print(f"preemptions: {view['preemptions']} (drained + "
                  "re-spooled; jobs converge on restart)")
        fleet = view.get("fleet") or {}
        hosts = fleet.get("hosts") or {}
        if hosts:
            aff = fleet.get("affinity") or {}
            rate = aff.get("hit_rate")
            print(f"fleet: {len(hosts)} host(s)  "
                  f"reclaims {fleet.get('reclaims_total', 0)}  "
                  f"stale claims {fleet.get('stale_claims_total', 0)}  "
                  f"affinity "
                  + (f"{rate:.0%}" if rate is not None else "-")
                  + f" ({aff.get('hits', 0)}/{aff.get('known', 0)})")
            for name in sorted(hosts):
                h = hosts[name]
                age = h.get("heartbeat_age_s")
                print(f"  {name:14s} "
                      f"{'LIVE' if h.get('live') else 'dead':4s}  "
                      f"hb " + (f"{age:.1f}s" if age is not None else "-")
                      + f"  leases {h.get('leases', 0)}")
        _render_heartbeats(serve_mod.serve_dir(root),
                           running=bool(view.get("live")))
        return 0
    # run
    from tmlibrary_tpu import telemetry
    from tmlibrary_tpu.resilience import EXIT_PREEMPTED
    from tmlibrary_tpu.workflow.admission import AdmissionConfig

    if args.no_telemetry:
        telemetry.set_enabled(False)
    admission = AdmissionConfig.from_library_config()
    if args.max_queue is not None:
        admission.max_queue = args.max_queue
    if args.low_watermark is not None:
        admission.low_watermark = args.low_watermark
    if args.tenant_quota is not None:
        admission.tenant_quota = args.tenant_quota
    if args.retry_budget is not None:
        admission.retry_budget = args.retry_budget
    if args.tenant_weights:
        weights = {}
        for part in args.tenant_weights.split(","):
            name, _, w = part.partition("=")
            if not name or not w:
                print(f"error: bad --tenant-weights entry '{part}' "
                      "(expected TENANT=WEIGHT)", file=sys.stderr)
                return 1
            weights[name.strip()] = float(w)
        admission.tenant_weights = weights
    rc = serve_mod.run_serve(
        root, admission=admission, poll_s=args.poll,
        max_jobs=args.max_jobs, idle_exit_s=args.idle_exit,
        host=args.host, lease_s=args.lease,
        canary_period_s=args.canary,
    )
    if rc == EXIT_PREEMPTED:
        print("serve preempted: queued jobs re-spooled — restart "
              "`tmx serve run` to resume", file=sys.stderr)
    return rc


def _query_payload(args) -> dict:
    """Assemble one analytics-query payload from --tool/--objects plus
    inline or file JSON (shared by `tmx query` and `tmx enqueue
    --kind query`).  Explicit payload keys win over the shorthands."""
    if args.payload_file and args.payload:
        raise SystemExit("--payload and --payload-file are mutually "
                         "exclusive")
    if args.payload_file:
        payload = json.loads(Path(args.payload_file).read_text())
    elif args.payload:
        payload = json.loads(args.payload)
    else:
        payload = {}
    if not isinstance(payload, dict):
        raise SystemExit("query payload must be a JSON object")
    if getattr(args, "tool", None):
        payload.setdefault("tool", args.tool)
    if getattr(args, "objects", None):
        payload.setdefault("objects_name", args.objects)
    if getattr(args, "index", None):
        payload.setdefault("index", args.index)
    if not payload.get("tool"):
        raise SystemExit("query needs a tool (--tool or payload 'tool')")
    if not payload.get("objects_name"):
        raise SystemExit("query needs an objects_name (--objects or "
                         "payload 'objects_name')")
    return payload


def cmd_query(args) -> int:
    from tmlibrary_tpu.analytics import query as analytics_query

    store = _open_store(args)
    payload = _query_payload(args)
    summary = analytics_query.run_query(
        store, payload, use_cache=not args.no_cache,
    )
    print(json.dumps(summary, default=str))
    return 0


def cmd_index(args) -> int:
    from tmlibrary_tpu.analytics.index import IvfIndex
    from tmlibrary_tpu.analytics.store import FeatureStore

    store = _open_store(args)
    fs = FeatureStore.ensure(store, args.objects)
    if args.verb == "build":
        features = (
            [f.strip() for f in args.features.split(",") if f.strip()]
            if args.features else None
        )
        idx = IvfIndex.ensure(fs, features, n_cells=args.cells,
                              rebuild=args.rebuild)
        print(json.dumps({**idx.meta, "cache": idx.cache_state,
                          "root": str(idx.root)}, default=str))
        return 0
    # list: every persisted selection, with staleness vs the live digest
    rows = []
    for meta_path in sorted((fs.root / "index").glob("*/index_meta.json")):
        try:
            meta = json.loads(meta_path.read_text())
        except Exception:
            continue
        rows.append({
            "selection": meta.get("selection"),
            "n_cells": meta.get("n_cells"),
            "n_objects": meta.get("n_objects"),
            "recall_at_k": meta.get("recall_at_k"),
            "digest": meta.get("digest"),
            "state": ("fresh" if meta.get("store_digest") == fs.digest
                      else "stale"),
            "root": str(meta_path.parent),
        })
    print(json.dumps({"objects_name": args.objects,
                      "store_digest": fs.digest, "indexes": rows},
                     default=str))
    return 0


def cmd_enqueue(args) -> int:
    import uuid

    from tmlibrary_tpu import serve as serve_mod
    from tmlibrary_tpu.workflow.admission import JobSpec

    now = time.time()
    job_id = args.job_id or f"{args.tenant}-{uuid.uuid4().hex[:10]}"
    trace_id = getattr(args, "trace_id", None) or uuid.uuid4().hex
    kind = getattr(args, "kind", "workflow")
    payload = None
    if kind == "query":
        payload = _query_payload(args)
    spec = JobSpec(
        job_id=job_id,
        tenant=args.tenant,
        root=str(Path(args.experiment).resolve()),
        description=args.description,
        priority=args.priority,
        deadline=(now + args.deadline) if args.deadline else None,
        pipeline_depth=args.pipeline_depth,
        attempt=args.attempt,
        submitted_at=now,
        trace_id=trace_id,
        kind=kind,
        payload=payload,
        affinity_key=getattr(args, "affinity_key", None),
    )
    try:
        path = serve_mod.enqueue_job(Path(args.root), spec)
    except Exception as exc:
        print(f"error: enqueue failed for job {job_id}: {exc}",
              file=sys.stderr)
        return 1
    print(f"enqueued {job_id} (tenant {spec.tenant}, trace {trace_id}) "
          f"-> {path}")
    return 0


def cmd_tool(args) -> int:
    from tmlibrary_tpu.tools import base as tools_base

    if args.verb == "available":
        for name in tools_base.list_tools():
            print(name)
        return 0
    store = _open_store(args)
    manager = tools_base.ToolRequestManager(store)
    if args.verb == "submit":
        if args.payload_file and args.payload != "{}":
            raise SystemExit("--payload and --payload-file are mutually exclusive")
        if args.payload_file:
            payload = json.loads(Path(args.payload_file).read_text())
        else:
            payload = json.loads(args.payload)
        if args.background:
            request_id = manager.submit_async(args.name, payload)
            print(json.dumps(manager.status(request_id), default=str))
            return 0
        result = manager.submit(args.name, payload)
        print(json.dumps(
            {
                "tool": result.tool,
                "objects_name": result.objects_name,
                "layer_type": result.layer_type,
                "n_objects": int(len(result.values)),
                "attributes": result.attributes,
            },
            default=str,
        ))
        return 0
    if args.verb == "status":
        print(json.dumps(manager.status(args.request), default=str))
        return 0
    if args.verb == "run-request":
        manager.run_request(args.request)
        print(json.dumps(manager.status(args.request), default=str))
        return 0
    # list
    for entry in manager.list_requests():
        print(json.dumps(entry, default=str))
    return 0


def cmd_project(args) -> int:
    from tmlibrary_tpu.jterator.project import Project

    if args.verb == "modules":
        from tmlibrary_tpu.jterator.modules import list_modules

        for name in list_modules():
            print(name)
        return 0
    if args.verb == "create":
        Project.create(Path(args.dir), description=args.description)
        print(f"created project at {args.dir}")
        return 0
    if args.verb == "check":
        import yaml

        from tmlibrary_tpu.errors import (
            PipelineDescriptionError,
            PipelineError,
            RegistryError,
        )
        from tmlibrary_tpu.jterator.description import PipelineDescription
        from tmlibrary_tpu.jterator.modules import get_module, module_accepts

        try:
            desc = PipelineDescription.load(Path(args.pipe))
        except (PipelineError, OSError, ValueError, KeyError,
                yaml.YAMLError) as e:
            # PipelineError covers the description AND handle-type
            # errors; KeyError = a handle dict missing a required field
            print(f"FAIL: cannot load pipeline: {e}")
            return 1
        problems: list[str] = []
        try:
            desc.validate()
        except PipelineDescriptionError as e:
            problems.append(str(e))
        for mod in desc.modules:
            try:
                get_module(mod.module, mod.backend)
            except RegistryError as e:
                problems.append(str(e))
                continue
            # exactly the names the runner will bind (constants + traced
            # arrays; Plot/Figure handles are display-only and unbound)
            bound = list(mod.constants()) + list(mod.array_inputs())
            for name in bound:
                if not module_accepts(mod.module, mod.backend, name):
                    problems.append(
                        f"module '{mod.module}' has no parameter "
                        f"'{name}'"
                    )
        if problems:
            for p in problems:
                print(f"FAIL: {p}")
            return 1
        print(
            f"OK: {len(desc.modules)} modules, dataflow valid, every "
            "module and parameter resolves"
        )
        return 0
    proj = Project(Path(args.dir))
    if args.verb == "add-module":
        hc = proj.add_module(args.module, instance=args.instance)
        print(f"added '{args.module}' as "
              f"'{args.instance or args.module}' "
              f"({len(hc.input)} inputs, {len(hc.output)} outputs)")
        return 0
    if args.verb == "remove-module":
        proj.remove_module(args.instance)
        print(f"removed '{args.instance}'")
        return 0
    if args.verb == "add-channel":
        proj.add_channel(args.name, correct=not args.no_correct, align=args.align)
        print(f"added channel '{args.name}'")
        return 0
    if args.verb == "show":
        for name in proj.module_names():
            hc = proj.get_handles(name)
            print(f"{name}: module={hc.module} backend={hc.backend}")
        return 0
    return 1


def cmd_step(args) -> int:
    if args.verb == "args":
        # schema introspection needs no experiment store
        print(json.dumps(get_step(args.command).batch_args.to_schema(), indent=2))
        return 0
    store = _open_store(args)
    step = get_step(args.command)(store)
    if args.verb == "init":
        step_args = {
            a.name: getattr(args, a.name)
            for a in step.batch_args
            if getattr(args, a.name, None) is not None
        }
        batches = step.init(step_args)
        print(f"{args.command}: planned {len(batches)} batches")
        return 0
    if args.verb == "run":
        indices = [args.job] if args.job is not None else step.list_batches()
        for i in indices:
            result = step.run(i)
            print(f"{args.command} batch {i}: {json.dumps(result, default=str)}")
        return 0
    if args.verb == "collect":
        print(json.dumps(step.collect(), default=str))
        return 0
    if args.verb == "info":
        for i in step.list_batches():
            batch = step.load_batch(i)
            keys = {k: v for k, v in batch.items() if k not in ("args",)}
            print(f"batch {i}: {json.dumps(keys, default=str)[:200]}")
        return 0
    if args.verb == "cleanup":
        # reference `cleanup` verb: idempotent removal of step outputs
        _cleanup_step(step)
        print(f"{args.command}: outputs removed")
        return 0
    return 1


def cmd_log(args) -> int:
    store = _open_store(args)
    if args.step:
        name = "run" if args.job is None else f"batch_{args.job:03d}"
        path = store.workflow_dir / args.step / "logs" / f"{name}.log"
        if not path.exists():
            print(f"error: no captured log at {path}", file=sys.stderr)
            return 1
        lines = path.read_text().splitlines()
        for line in lines[-args.tail:] if args.tail else lines:
            print(line)
        return 0
    ledger = RunLedger(store.workflow_dir / "ledger.jsonl")
    for event in ledger.events()[-args.tail:]:
        print(json.dumps(event, default=str))
    return 0


def _export_images(store: ExperimentStore, args, out: Path) -> int:
    """Write one channel's (optionally corrected/aligned) site planes as
    uint16 TIFFs — the road OUT of the store (reference parity: tmserver's
    original/corrected image download endpoints).  Every tpoint/zplane is
    exported; names use the default filename handler's grammar
    (``[<plate>_]<well>_s<site>[_t<t>][_z<z>]_<channel>.tif``) so the
    exported tree re-ingests as-is — EXCEPT under ``--align`` when a
    cycle-intersection window is stored: aligned exports are cropped to
    the intersection (smaller than the manifest site shape, matching what
    the analysis actually saw), so that tree re-ingests only as a new
    experiment, not back into this one."""
    import re as _re

    import cv2
    import jax.numpy as jnp

    from tmlibrary_tpu.errors import StoreError
    from tmlibrary_tpu.models.experiment import Well
    from tmlibrary_tpu.models.image import IllumstatsContainer
    from tmlibrary_tpu.ops import image_ops
    from tmlibrary_tpu.writers import OMETiffWriter, minimal_ome_xml

    channel, cycle = args.images, args.cycle
    exp = store.experiment
    # the default ingest pattern accepts [A-Za-z0-9-] channel tokens and
    # [A-Za-z0-9] plate tokens only — sanitize both or the documented
    # re-ingest round-trip breaks on vendor names with '_'/'-'/spaces
    ch_name = _re.sub(r"[^A-Za-z0-9\-]", "-", exp.channels[channel].name)
    plate_token = {p.name: _re.sub(r"[^A-Za-z0-9]", "", p.name) or "plate"
                   for p in exp.plates}
    out.mkdir(parents=True, exist_ok=True)

    stats = None
    if args.correct:
        if not store.has_illumstats(cycle=cycle, channel=channel):
            print("error: --correct requested but corilla stats are missing "
                  f"for cycle {cycle} channel {channel}", file=sys.stderr)
            return 1
        stats = IllumstatsContainer.from_store(
            store.read_illumstats(cycle=cycle, channel=channel)
        )
    shifts = None
    window = (0, 0, 0, 0)
    if args.align:
        if not store.has_shifts(cycle):
            print(f"error: --align requested but no shifts stored for cycle "
                  f"{cycle} (run the align step)", file=sys.stderr)
            return 1
        shifts = store.read_shifts(cycle)
        try:
            w = store.read_intersection()
            window = (w["top"], w["bottom"], w["left"], w["right"])
        except StoreError:
            pass  # align ran but no intersection stored: shift-only

    prep = image_ops.make_batch_prep(
        stats, apply_shift=shifts is not None,
        window=window if any(window) else None,
    )

    # site index within the well (row-major over the well grid) so the
    # exported names round-trip through the default filename handler
    spw_x = max((r.site_x for r in exp.sites()), default=0) + 1
    refs = list(exp.sites())
    multi_plate = len(exp.plates) > 1
    shift_table = (shifts if shifts is not None
                   else np.zeros((len(refs), 2), np.int32))

    from tmlibrary_tpu.utils import create_partitions

    n = 0
    for tpoint in range(exp.n_tpoints):
        for zplane in range(exp.n_zplanes):
            for part in create_partitions(list(range(len(refs))), 32):
                stack = store.read_sites(
                    part, cycle=cycle, channel=channel,
                    tpoint=tpoint, zplane=zplane,
                )
                prepped = np.asarray(
                    prep(jnp.asarray(stack), jnp.asarray(shift_table[part]))
                )
                for b, idx in enumerate(part):
                    ref = refs[idx]
                    arr = np.clip(prepped[b], 0, 65535).astype(np.uint16)
                    well = Well(row=ref.well_row, column=ref.well_column,
                                sites=())
                    name = f"{well.name}_s{ref.site_y * spw_x + ref.site_x:d}"
                    if multi_plate:
                        name = f"{plate_token[ref.plate]}_{name}"
                    if exp.n_tpoints > 1:
                        name += f"_t{tpoint:d}"
                    if exp.n_zplanes > 1:
                        name += f"_z{zplane:d}"
                    name += f"_{ch_name}.tif"
                    if args.ome:
                        OMETiffWriter(out / name).write(
                            arr,
                            minimal_ome_xml(name, *arr.shape),
                        )
                    elif not cv2.imwrite(str(out / name), arr):
                        print(f"error: failed writing {out / name}",
                              file=sys.stderr)
                        return 1
                    n += 1
    print(f"wrote {n} {ch_name} site images to {out}")
    return 0


def cmd_export(args) -> int:
    """Combined per-object feature table → one CSV/Parquet file.

    Reference parity: the reference serves feature values through tmserver's
    data-export endpoints (FeatureValues over the Citus shards); here the
    Parquet shards the jterator step appended are concatenated and written
    as one table with the site/well metadata columns already joined.
    """
    store = _open_store(args)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    modes = [m for m, v in (("--objects", args.objects),
                            ("--illumstats", args.illumstats),
                            ("--images", args.images),
                            ("--ngff", args.ngff or None)) if v is not None]
    if len(modes) > 1:
        print(f"error: {' and '.join(modes)} are mutually exclusive",
              file=sys.stderr)
        return 1
    if args.ngff:
        from tmlibrary_tpu.ngff import write_ngff_plate

        label_names = (
            [n.strip() for n in args.ngff_labels.split(",") if n.strip()]
            if args.ngff_labels else None
        )
        write_ngff_plate(store, out, n_levels=args.ngff_levels,
                         label_names=label_names)
        extra = (f" + labels {','.join(label_names)}" if label_names else "")
        print(f"wrote OME-NGFF 0.4 HCS plate "
              f"({len(store.experiment.channels)} channels{extra}) to {out}")
        return 0
    if args.images is not None:
        return _export_images(store, args, out)
    if args.illumstats is not None:
        store.export_illumstats_hdf5(
            out, cycle=args.cycle, channel=args.illumstats
        )
        print(f"wrote cycle {args.cycle} channel {args.illumstats} "
              f"illumination statistics (reference IllumstatsFile layout) "
              f"to {out}")
        return 0
    if args.objects is None:
        print("error: pass --objects NAME (feature/polygon export) or "
              "--illumstats CHANNEL", file=sys.stderr)
        return 1
    suffix_fmt = {".csv": "csv", ".geojson": "geojson", ".json": "geojson"}
    fmt = args.format or suffix_fmt.get(out.suffix.lower(), "parquet")
    if fmt == "geojson":
        # reference parity: tmserver serves MapobjectSegmentation polygons
        # as GeoJSON FeatureCollections for the viewer
        import pandas as pd

        shards = sorted(
            (store.root / "segmentations").glob(f"{args.objects}_polygons_*.parquet")
        )
        if not shards:
            print(
                f"error: no polygon shards for '{args.objects}' — run "
                "jterator with --as-polygons", file=sys.stderr,
            )
            return 1
        table = pd.concat([pd.read_parquet(p) for p in shards], ignore_index=True)
        if args.join_features:
            # join selected measurement columns onto the polygons by
            # (site, label) — reference parity: tmserver joins
            # FeatureValues / tool LabelLayers onto mapobjects for the
            # viewer's colored overlays
            wanted = [c.strip() for c in args.join_features.split(",") if c.strip()]
            keys = {"label", "site_index", "site"}
            if keys & set(wanted):
                print(f"error: --join-features cannot include the join keys "
                      f"{sorted(keys & set(wanted))}", file=sys.stderr)
                return 1
            feats = store.read_features(args.objects)
            missing = [c for c in wanted if c not in feats.columns]
            if missing:
                print(f"error: --join-features columns not in the feature "
                      f"table: {missing} (available: "
                      f"{sorted(set(feats.columns) - {'label'})[:20]}...)",
                      file=sys.stderr)
                return 1
            join = feats[["site_index", "label", *wanted]].rename(
                columns={"site_index": "site"}
            )
            table = table.merge(join, on=["site", "label"], how="left")
            # polygons with no feature row would serialize as bare NaN
            # (invalid JSON); emit null instead
            table[wanted] = table[wanted].astype(object).where(
                pd.notna(table[wanted]), None
            )
        from tmlibrary_tpu import native

        features = []
        for _, row in table.iterrows():
            contour = np.stack([row["contour_y"], row["contour_x"]], axis=1)
            if args.simplify > 0:
                contour = native.simplify_polygon_host(contour, args.simplify)
            ring = [[float(x), float(y)] for y, x in contour]
            if ring and ring[0] != ring[-1]:
                ring.append(ring[0])  # GeoJSON rings are closed
            props = {
                k: (row[k].item() if hasattr(row[k], "item") else row[k])
                for k in table.columns
                if k not in ("contour_y", "contour_x")
            }
            features.append(
                {
                    "type": "Feature",
                    "geometry": {"type": "Polygon", "coordinates": [ring]},
                    "properties": props,
                }
            )
        out.write_text(
            json.dumps({"type": "FeatureCollection", "features": features})
        )
        print(f"wrote {len(features)} polygon features to {out}")
        return 0
    table = store.read_features(args.objects)
    if fmt == "csv":
        table.to_csv(out, index=False)
    else:
        table.to_parquet(out, index=False)
    print(f"wrote {len(table)} rows x {len(table.columns)} cols to {out}")
    return 0


def cmd_metrics(args) -> int:
    """Export run metrics as Prometheus textfile format or JSON.

    Sources: the registry snapshot the last ``workflow submit`` wrote
    (``workflow/metrics.json``), or a ledger→metrics derivation that works
    on any ledger — including runs that predate telemetry."""
    from tmlibrary_tpu import telemetry

    if getattr(args, "merge", None):
        pairs = telemetry.load_fleet_snapshots(Path(args.merge))
        if not pairs:
            print(f"error: no workflow/metrics*.json snapshots under "
                  f"{args.merge}", file=sys.stderr)
            return 1
        merged = telemetry.merge_snapshots(pairs)
        if args.format == "json":
            text = telemetry.render_json(merged) + "\n"
        else:
            text = telemetry.render_prometheus(merged)
        if args.out:
            Path(args.out).write_text(text)
            print(f"wrote merged {args.format} metrics for "
                  f"{len(pairs)} host(s) to {args.out}")
        else:
            sys.stdout.write(text)
        return 0
    if not args.root:
        print("error: --root is required (or use --merge RUN_ROOT)",
              file=sys.stderr)
        return 1
    store = _open_store(args)
    snapshot = None
    snap_path = store.workflow_dir / "metrics.json"
    if args.source in ("auto", "snapshot") and snap_path.exists():
        try:
            snapshot = json.loads(snap_path.read_text())
        except ValueError:
            print(f"warning: ignoring corrupt snapshot {snap_path}",
                  file=sys.stderr)
    if snapshot is None:
        if args.source == "snapshot":
            print(f"error: no metrics snapshot at {snap_path} (run "
                  "`tmx workflow submit` first, or use --source ledger)",
                  file=sys.stderr)
            return 1
        ledger = RunLedger(store.workflow_dir / "ledger.jsonl")
        events = ledger.events()
        if not events:
            print("no metrics snapshot and no run ledger — nothing to "
                  "export", file=sys.stderr)
            return 1
        snapshot = telemetry.registry_from_ledger(events).snapshot()
    if args.format == "json":
        text = telemetry.render_json(snapshot) + "\n"
    else:
        text = telemetry.render_prometheus(snapshot)
    if args.out:
        Path(args.out).write_text(text)
        print(f"wrote {args.format} metrics to {args.out}")
    else:
        sys.stdout.write(text)
    return 0


def cmd_top(args) -> int:
    """Live fleet dashboard (``tmx top``): poll heartbeats + per-host
    metrics snapshots under the run root and repaint a terminal view —
    throughput, pipeline depth, bucket occupancy, per-device utilization,
    straggler skew, QC state, degradation state."""
    from tmlibrary_tpu import top

    return top.run_top(Path(args.root), interval=args.interval,
                       once=args.once,
                       as_json=getattr(args, "as_json", False))


def cmd_timeline(args) -> int:
    """Metric history (``tmx timeline``): merge every per-host
    ``tsdb.<host>.jsonl`` segment under the root and render one sparkline
    per series.  Roots that predate the time-series layer fall back to
    replaying their ledgers into synthetic samples, so the verb answers
    on seed-era runs too."""
    from tmlibrary_tpu import timeseries, traceexport

    root = Path(args.root)
    segments = timeseries.load_tsdb(root)
    source = "tsdb"
    records = timeseries.merge_tsdb(segments)
    if not records:
        source = "ledger"
        try:
            events = traceexport.collect_events(root)
        except Exception:
            events = []
        records = timeseries.synthesize_from_ledger(events)
    series = timeseries.series_index(records)
    if args.metric:
        series = {k: v for k, v in series.items() if args.metric in k[0]}
    if getattr(args, "as_json", False):
        doc = {
            "root": str(root), "source": source,
            "series": [
                {
                    "name": name, "labels": dict(labels),
                    "points": [[ts, v] for ts, v in points],
                    "last": points[-1][1] if points else None,
                    "rate_per_s": timeseries.rate(points, args.window),
                    "p95": timeseries.quantile_over_time(points, 0.95),
                }
                for (name, labels), points in sorted(series.items())
            ],
        }
        print(json.dumps(doc, indent=2, sort_keys=True))
        return 0
    if not series:
        print(f"no time-series data under {root}")
        return 1
    print(f"timeline {root} [{source}] — {len(series)} series")
    for (name, labels), points in sorted(series.items()):
        label_txt = ("{" + ",".join(f"{k}={v}" for k, v in labels) + "}"
                     if labels else "")
        spark = timeseries.sparkline([v for _, v in points],
                                     width=args.width)
        last = points[-1][1]
        r = timeseries.rate(points, args.window)
        rate_txt = "" if r is None else f"  rate {r:.3g}/s"
        print(f"  {name}{label_txt}")
        print(f"    {spark}  last {last:g}{rate_txt}  n={len(points)}")
    return 0


def cmd_trace(args) -> int:
    """Dump the span tree (run > step > batch > phase > the spans inside
    it, nested by ``parent``) with the critical path marked ``*`` at every
    level — the chain the run's wall time actually went to — and a table
    of every span by step, parent and name.  Accepts serve roots too (the spooled job specs
    point at their experiment ledgers), and ``--export chrome`` writes
    the whole thing as Trace Event Format JSON."""
    from tmlibrary_tpu import serve as serve_mod
    from tmlibrary_tpu import telemetry, traceexport

    root = Path(args.root)
    if getattr(args, "export", None) == "chrome":
        out = Path(args.out or "trace.json")
        try:
            doc = traceexport.export_chrome_trace(
                root, out, trace_id=getattr(args, "trace_id", None))
        except ValueError as exc:
            print(f"error: {exc}", file=sys.stderr)
            return 1
        n = len(doc.get("traceEvents", []))
        print(f"wrote {n} trace events -> {out}")
        return 0 if n else 1
    if serve_mod.is_serve_root(root):
        # a serve root has no single span tree — merge every reachable
        # ledger so the text view still answers "where did time go"
        events = traceexport.collect_events(root)
    else:
        store = _open_store(args)
        events = RunLedger(store.workflow_dir / "ledger.jsonl").events()
    if not events:
        print("no run ledger — nothing to trace", file=sys.stderr)
        return 1
    tid = getattr(args, "trace_id", None)
    if tid:
        events = [ev for ev in events if ev.get("trace_id") == tid]
    tree = telemetry.annotate_critical_path(
        telemetry.build_span_tree(events)
    )
    if args.as_json:
        print(json.dumps(tree, indent=2))
        return 0
    print(telemetry.render_span_tree(tree))
    totals = telemetry.phase_totals(events)
    if totals:
        phases = "  ".join(f"{k}={v:.3f}s"
                           for k, v in sorted(totals.items(),
                                              key=lambda kv: -kv[1]))
        print(f"\nphase totals (critical resource): {phases}")
    table = traceexport.span_table(events)
    if table:
        print("\nspans (step, parent, span, count, seconds):")
        for row in table:
            print(f"  {row['step']:<12} {row['parent']:<12} "
                  f"{row['span']:<16} {row['count']:6d} "
                  f"{row['total_s']:10.4f}")
    return 0


def cmd_slo(args) -> int:
    """Per-tenant SLO report over a serve root's ledger: p50/p95 job
    latency vs the latency objective, availability vs the availability
    objective, and multi-window burn rates.

    Exit codes (pinned, same discipline as qc/bench_regression):
    0 ok · 1 some tenant's burn >= 1 · 3 no job-completion data."""
    from tmlibrary_tpu import serve as serve_mod
    from tmlibrary_tpu import slo as slo_mod

    root = Path(args.root)
    if not serve_mod.serve_ledger_paths(root):
        # experiment roots have no job completions — say so with the
        # pinned no-data code rather than a generic error
        print(f"no serve ledger under {root} — `tmx slo` reads a serve "
              "root", file=sys.stderr)
        return slo_mod.EXIT_NO_DATA
    # merged per-host history: fleet burn is one report, not N
    view = slo_mod.report(serve_mod.serve_ledger_events(root))
    if getattr(args, "as_json", False):
        print(json.dumps(view, indent=2))
    else:
        print(slo_mod.render(view), end="")
    return slo_mod.exit_code(view)


def cmd_qc(args) -> int:
    """Data-quality report for a run: per-step table, per-channel image
    stats, numerics guards, worst-focus sites, flagged sites — plus the
    drift-sentinel verdict vs a reference profile.

    Exit codes (pinned, same discipline as scripts/bench_regression.py):
    0 ok · 1 drift · 2 stale reference · 3 no reference."""
    from tmlibrary_tpu import qc as qc_mod

    root = Path(args.root)
    wf = _open_store(args).workflow_dir
    pairs = qc_mod.load_run_profiles(wf)
    if pairs:
        profile = (qc_mod.merge_profiles(pairs) if len(pairs) > 1
                   else pairs[0][1])
        source = (f"qc.json x{len(pairs)} host(s)" if len(pairs) > 1
                  else "qc.json")
    else:
        events = RunLedger(wf / "ledger.jsonl").events()
        profile = qc_mod.qc_from_ledger(events) if events else {}
        source = "ledger"
    if not (profile.get("steps") or profile.get("channels")):
        print("no QC evidence for this run — submit with --qc (or "
              "TMX_QC=1) to collect it", file=sys.stderr)
        return 1

    kind = getattr(args, "profile_kind", "run")
    if kind == "model":
        # the model deploy gate: only the __model__.* sketches count,
        # judged against the committed checkpoint baseline
        ref_path = args.reference or os.environ.get("TMX_QC_DL_BASELINE")
        if not ref_path and Path("tuning/QC_DL_BASELINE.json").exists():
            ref_path = "tuning/QC_DL_BASELINE.json"
        if not qc_mod.filter_profile_kind(profile, "model").get("features"):
            print("no model-output sketches in this run's profile — the "
                  "pipeline has no DL modules or ran without --qc",
                  file=sys.stderr)
            return 1
    else:
        ref_path = args.reference or os.environ.get("TMX_QC_BASELINE")
        if not ref_path and Path("tuning/QC_BASELINE.json").exists():
            ref_path = "tuning/QC_BASELINE.json"
    profile = qc_mod.filter_profile_kind(profile, kind)
    reference = qc_mod.load_profile(Path(ref_path)) if ref_path else None
    reference = qc_mod.filter_profile_kind(reference, kind)
    verdict = qc_mod.compare_profiles(
        profile, reference, threshold=args.threshold,
        stale_hours=args.stale_hours,
    )

    if getattr(args, "as_json", False):
        print(json.dumps({"root": str(root), "source": source,
                          "profile": profile, "reference": ref_path,
                          "verdict": verdict},
                         indent=2, default=float))
        return verdict["exit_code"]

    print(f"tmx qc — {root}  (source: {source})")
    steps = profile.get("steps") or {}
    if steps:
        print(f"  {'step':<16} {'batches':>7} {'sites':>7} {'flagged':>7}")
        for name, e in sorted(steps.items()):
            print(f"  {name:<16} {e.get('batches', 0):>7} "
                  f"{e.get('sites', 0):>7} {e.get('flagged', 0):>7}")
    channels = profile.get("channels") or {}
    if channels:
        print("channels:")
        for ch, metrics in sorted(channels.items()):
            foc = metrics.get("focus_tenengrad") or {}
            sat = metrics.get("saturation_frac") or {}
            bg = metrics.get("background") or {}
            bits = [f"  {ch:<12}"]
            if foc.get("min") is not None:
                bits.append(f"focus min {foc['min']:.4g}")
            if sat.get("max") is not None:
                bits.append(f"saturation max {sat['max']:.2%}")
            if bg.get("mean") is not None:
                bits.append(f"background {bg['mean']:.1f}")
            print("  ".join(bits))
    if kind == "model":
        feats = profile.get("features") or {}
        if feats:
            print("model output sketches:")
            for name, s in sorted(feats.items()):
                print(f"  {name:<28} n {int(s.get('count') or 0):>8}  "
                      f"p50 {float(s.get('p50') or 0.0):.4g}  "
                      f"p95 {float(s.get('p95') or 0.0):.4g}")
    guards = profile.get("guards") or {}
    nan_cols = guards.get("nan_columns") or []
    line = (f"guards: nan columns {len(nan_cols)}  nan/inf values "
            f"{int(guards.get('nan_values') or 0) + int(guards.get('inf_values') or 0)}"
            f"  count z max {float(guards.get('count_z_max') or 0.0):.2f}")
    if guards.get("capacity_saturated_batches"):
        line += (f"  capacity-saturated batches "
                 f"{guards['capacity_saturated_batches']}")
    print(line)
    if nan_cols:
        print(f"  non-finite feature columns: {', '.join(nan_cols[:8])}"
              + (" ..." if len(nan_cols) > 8 else ""))
    worst = (profile.get("worst_sites") or [])[:max(args.worst, 0)]
    if worst:
        print(f"worst {len(worst)} site(s) by focus:")
        for w in worst:
            print(f"  site {w.get('site', '?'):>5}  "
                  f"{str(w.get('channel', '?')):<12} "
                  f"focus {w.get('focus', 0.0):.4g}")
    flagged_total = int(profile.get("flagged_total") or 0)
    if flagged_total:
        print(f"flagged: {flagged_total} site(s)")
        for f in (profile.get("flagged") or [])[:max(args.worst, 0)]:
            bits = [f"  site {f.get('site', '?'):>5}",
                    str(f.get('reason', '?'))]
            if f.get("channel"):
                bits.append(f"[{f['channel']}]")
            if f.get("value") is not None:
                bits.append(f"value {f['value']:.4g}")
            if f.get("z") is not None:
                bits.append(f"z {f['z']:+.1f}")
            print("  ".join(bits))

    line = f"drift verdict: {verdict['status']} (exit {verdict['exit_code']})"
    if reference is not None:
        line += f"  vs {ref_path}  checked {verdict.get('checked', 0)}"
    if verdict.get("age_hours") is not None:
        line += f"  reference age {verdict['age_hours']:.1f}h"
    print(line)
    for d in verdict.get("drifted", [])[:10]:
        if d.get("kind") == "median_shift":
            print(f"  DRIFT {d['feature']}: p50 "
                  f"{d['reference_p50']:.4g} -> {d['current_p50']:.4g} "
                  f"(|Δ| {d['delta']:.4g} > allowed {d['allowed']:.4g})")
        elif d.get("kind") == "new_nan":
            print(f"  DRIFT {d['feature']}: {d['current_nan']} non-finite "
                  "value(s) not present in the reference")
        elif d.get("kind") == "saturation":
            print(f"  DRIFT channel {d['channel']}: saturation max "
                  f"{d['reference_max']:.2%} -> {d['current_max']:.2%}")
    return verdict["exit_code"]


def cmd_weights(args) -> int:
    """DL checkpoint inventory / digests (``tmlibrary_tpu.nn``).

    ``tmx weights list`` — one row per ``.npz`` in the weights
    directory; ``tmx weights digest SPEC`` — resolve any weight spec
    (named checkpoint, path, or ``seed:N``) and print the content
    digest that keys the compiled-program cache and the bench
    sentinel's provenance."""
    from tmlibrary_tpu import nn

    if args.verb == "list":
        rows = nn.list_weights(args.dir)
        if getattr(args, "as_json", False):
            print(json.dumps(rows, indent=2, default=str))
            return 0
        if not rows:
            print(f"no checkpoints in {args.dir or nn.weights_dir()}")
            return 0
        print(f"{'name':<24} {'digest':<14} {'arrays':>7} {'params':>10}")
        for r in rows:
            print(f"{r['name']:<24} {r['digest']:<14} "
                  f"{r['n_arrays']:>7} {r['n_params']:>10}")
        return 0
    # digest
    _params, digest, config = nn.resolve_weights(args.spec)
    if getattr(args, "as_json", False):
        print(json.dumps({"spec": args.spec, "digest": digest,
                          "config": dataclasses.asdict(config)}))
        return 0
    print(f"{args.spec}  digest {digest}  "
          f"(in={config.in_channels}, base={config.base_channels}, "
          f"depth={config.depth})")
    return 0


def _snapshot_gauge(snapshot: dict, name: str) -> "float | None":
    for entry in snapshot.get("gauges", []):
        if entry.get("name") == name:
            return entry.get("value")
    return None


def _snapshot_counter(snapshot: dict, name: str) -> float:
    total = 0.0
    for entry in snapshot.get("counters", []):
        if entry.get("name") == name:
            total += float(entry.get("value") or 0)
    return total


def _perf_schedule_summary(events: list) -> list:
    """Per-step packing readout from the ledger alone: the recorded
    ``schedule_plan`` provenance (digest, predicted occupancy/skew for
    packed vs the directory-order counterfactual) joined with what the
    run actually delivered (mean ``batch_done`` slot occupancy, mean
    actual shard-work spread from ``shard_objects``, plan hit rate from
    escalation-free planned batches)."""
    plans: dict[str, dict] = {}
    actual: dict[str, dict] = {}
    for ev in events:
        step = str(ev.get("step", "")) or "unknown"
        if ev.get("event") == "schedule_plan":
            plans[step] = ev  # last plan wins (resume re-appends the same)
        if ev.get("event") != "batch_done":
            continue
        res = ev.get("result")
        if not isinstance(res, dict):
            continue
        agg = actual.setdefault(step, {
            "occ": [], "spread": [], "pred_skew": [],
            "planned": 0, "hits": 0,
        })
        if isinstance(res.get("slot_occupancy"), (int, float)):
            agg["occ"].append(float(res["slot_occupancy"]))
        shard = res.get("shard_objects")
        if isinstance(shard, list) and len(shard) > 1:
            vals = [float(v) for v in shard]
            agg["spread"].append(max(vals) - min(vals))
        if isinstance(res.get("predicted_skew"), (int, float)):
            agg["pred_skew"].append(float(res["predicted_skew"]))
        if res.get("schedule_rung"):
            agg["planned"] += 1
            if not res.get("bucket_escalations"):
                agg["hits"] += 1
    out = []
    mean = lambda xs: round(sum(xs) / len(xs), 4) if xs else None  # noqa: E731
    for step in sorted(set(plans) | set(actual)):
        plan = plans.get(step, {})
        agg = actual.get(step, {})
        if not plan and not agg.get("planned"):
            continue
        out.append({
            "step": step,
            "mode": plan.get("mode"),
            "source": plan.get("source"),
            "plan_digest": plan.get("plan_digest"),
            "n_batches": plan.get("n_batches"),
            "pred_occupancy_packed": plan.get("pred_occupancy_packed"),
            "pred_occupancy_unpacked": plan.get("pred_occupancy_unpacked"),
            "pred_skew_packed": plan.get("pred_skew_packed"),
            "pred_skew_unpacked": plan.get("pred_skew_unpacked"),
            "mean_slot_occupancy": mean(agg.get("occ", [])),
            "mean_shard_object_spread": mean(agg.get("spread", [])),
            "mean_predicted_skew": mean(agg.get("pred_skew", [])),
            "planned_batches": agg.get("planned", 0),
            "plan_hit_rate": (
                round(agg["hits"] / agg["planned"], 4)
                if agg.get("planned") else None
            ),
        })
    return out


def cmd_perf(args) -> int:
    """Performance attribution: the per-program roofline table the last
    run recorded (``workflow/perf.json``), the pipelined phase device/host
    breakdown from the ledger, padding-waste gauges — and under the
    ``history`` verb, the bench history + regression-sentinel verdict."""
    from tmlibrary_tpu import perf, tuning

    if getattr(args, "verb", None) == "history":
        return _perf_history(args, perf, tuning)
    if not args.root:
        print("error: --root is required (or use `tmx perf history`)",
              file=sys.stderr)
        return 2
    store = _open_store(args)

    programs: list = []
    perf_path = store.workflow_dir / "perf.json"
    if perf_path.exists():
        try:
            programs = json.loads(perf_path.read_text()).get("programs") or []
        except ValueError:
            print(f"warning: ignoring corrupt perf snapshot {perf_path}",
                  file=sys.stderr)
    if not programs:
        # same-process embedding (tests, notebooks): the live store
        programs = perf.perf_profiles()
    programs = programs[: max(int(args.top), 0) or len(programs)]

    # phase breakdown (device/host split) from the ledger's step events;
    # pre-perf ledgers lack device_s/host_s, so re-derive from the phase
    # resource map when absent
    from tmlibrary_tpu.profiling import PHASE_RESOURCE

    phases_out = []
    events = RunLedger(store.workflow_dir / "ledger.jsonl").events()
    for ev in events:
        if ev.get("event") not in ("step_done", "step_partial"):
            continue
        ps = ev.get("pipeline_stats")
        if not isinstance(ps, dict):
            continue
        phases = ps.get("phases") or {}
        device_s = ps.get("device_s")
        host_s = ps.get("host_s")
        if device_s is None or host_s is None:
            device_s = sum(v.get("total_s", 0.0) for p, v in phases.items()
                           if PHASE_RESOURCE.get(p) == "device")
            host_s = sum(v.get("total_s", 0.0) for p, v in phases.items()
                         if PHASE_RESOURCE.get(p) == "host")
        phases_out.append({
            "step": str(ev.get("step", "")) or "unknown",
            "depth": ps.get("depth"),
            "phases": {p: v.get("total_s", 0.0) for p, v in phases.items()},
            "device_s": round(device_s, 4),
            "host_s": round(host_s, 4),
        })

    # padding-waste gauges from the metrics snapshot (live registry of the
    # last run), falling back to the ledger derivation
    snapshot = {}
    snap_path = store.workflow_dir / "metrics.json"
    if snap_path.exists():
        try:
            snapshot = json.loads(snap_path.read_text())
        except ValueError:
            snapshot = {}
    if not snapshot and events:
        from tmlibrary_tpu import telemetry

        snapshot = telemetry.registry_from_ledger(events).snapshot()
    avoided = _snapshot_gauge(snapshot,
                              "tmx_jterator_padded_flops_avoided_frac")
    occupancy = _snapshot_gauge(snapshot, "tmx_jterator_slot_occupancy")
    schedule_rows = _perf_schedule_summary(events)

    history = tuning.load_bench_history()
    measured = [r for r in history
                if isinstance(r.get("value"), (int, float))
                and r.get("value") and not r.get("error")]
    latest = measured[-1] if measured else None

    if args.as_json:
        print(json.dumps({
            "programs": programs,
            "phases": phases_out,
            "padded_flops_avoided_frac": avoided,
            "slot_occupancy": occupancy,
            "schedule": schedule_rows,
            "latest_bench": latest,
        }, indent=2))
        return 0

    if programs:
        print(f"{'program':<24} {'cap':>5} {'backend':<8} "
              f"{'compiles':>8} {'recomp':>6} {'compile_s':>9} "
              f"{'gflops':>9} {'mbytes':>9} {'flops/B':>8} bound-by")
        for e in programs:
            flops = e.get("flops")
            nbytes = e.get("bytes")
            print(
                f"{str(e.get('program', '?')):<24} "
                f"{str(e.get('capacity') or '-'):>5} "
                f"{str(e.get('backend') or '?'):<8} "
                f"{e.get('compiles', 0):>8} "
                f"{e.get('recompiles', 0):>6} "
                f"{round(e.get('compile_seconds_total', 0.0), 2):>9} "
                f"{(round(flops / 1e9, 3) if flops else '-'):>9} "
                f"{(round(nbytes / 1e6, 2) if nbytes else '-'):>9} "
                f"{(e.get('arithmetic_intensity') or '-'):>8} "
                f"{e.get('bound_by') or '-'}"
            )
        print("(roofline verdict vs the v5e reference ridge "
              f"{perf.ridge_point():.0f} FLOPs/byte; MFU/HBM fractions are "
              "runtime numbers — see the bench line below)")
    else:
        print("no perf attribution recorded — run `tmx workflow submit` "
              "with telemetry enabled (workflow/perf.json)")
    for row in phases_out:
        parts = "  ".join(f"{p}={s}s" for p, s in row["phases"].items())
        total = row["device_s"] + row["host_s"]
        frac = row["device_s"] / total if total else 0.0
        print(f"phases: {row['step']} depth {row['depth']}: {parts}  "
              f"device={row['device_s']}s host={row['host_s']}s "
              f"({frac:.0%} device)")
    if avoided is not None:
        occ = f" (slot occupancy {occupancy:.2f})" if occupancy else ""
        print(f"padded-FLOPs-avoided: {avoided:.1%}{occ}")
    if schedule_rows:
        print()
        print("schedule packing (workflow/schedule.py — predicted vs "
              "delivered):")
        print(f"{'step':<10} {'mode':<5} {'plan':<16} {'batches':>7} "
              f"{'occ':>6} {'occ-unpacked':>12} {'skew':>8} "
              f"{'skew-unpacked':>13} {'hit-rate':>8}")
        fmt = lambda v, spec=".2f": (  # noqa: E731
            format(float(v), spec) if isinstance(v, (int, float)) else "-"
        )
        for row in schedule_rows:
            occ_actual = (row["mean_slot_occupancy"]
                          if row["mean_slot_occupancy"] is not None
                          else row["pred_occupancy_packed"])
            skew_actual = (row["mean_shard_object_spread"]
                           if row["mean_shard_object_spread"] is not None
                           else row["pred_skew_packed"])
            print(
                f"{row['step']:<10} {str(row['mode'] or '-'):<5} "
                f"{str(row['plan_digest'] or '-'):<16} "
                f"{str(row['n_batches'] or row['planned_batches']):>7} "
                f"{fmt(occ_actual):>6} "
                f"{fmt(row['pred_occupancy_unpacked']):>12} "
                f"{fmt(skew_actual, '.1f'):>8} "
                f"{fmt(row['pred_skew_unpacked'], '.1f'):>13} "
                f"{fmt(row['plan_hit_rate']):>8}"
            )
    if latest:
        print(f"latest bench: {latest.get('metric')} = {latest.get('value')}"
              f" ({latest.get('backend')})"
              f"  mfu_vs_v5e_bf16_peak={latest.get('mfu_vs_v5e_bf16_peak')}"
              f"  hbm_frac={latest.get('hbm_frac_vs_v5e_peak')}")
    return 0


def _perf_history(args, perf, tuning) -> int:
    path = getattr(args, "history", None) or tuning.bench_history_path()
    history = tuning.load_bench_history(path)
    if not history:
        print(f"no bench history at {path} — every bench.py run/sweep "
              "appends one record", file=sys.stderr)
        return 1
    tail = max(int(getattr(args, "tail", 10)), 0)
    print(f"bench history: {len(history)} records at {path}")
    for rec in history[-tail:]:
        bits = [
            str(rec.get("recorded_at", "?")),
            f"config={rec.get('config')}",
            f"backend={rec.get('backend')}",
            f"value={rec.get('value')}",
        ]
        if rec.get("sweep"):
            bits.append("sweep")
        if rec.get("error"):
            bits.append("ERROR")
        qc_rec = rec.get("qc")
        if isinstance(qc_rec, dict):
            if qc_rec.get("worst_focus") is not None:
                bits.append(f"qc_focus={qc_rec['worst_focus']:.4g}")
            if qc_rec.get("nan_columns"):
                bits.append(f"qc_nan_cols={qc_rec['nan_columns']}")
        print("  " + "  ".join(bits) + f"  {rec.get('metric')}")
    stale_hours = getattr(args, "stale_hours", None)
    verdict = perf.compare_history(
        history,
        config=getattr(args, "config", None),
        metric=getattr(args, "metric", None),
        threshold=getattr(args, "threshold", 0.05),
        stale_hours=stale_hours if stale_hours is not None
        else perf.stale_hours(),
    )
    line = f"verdict: {verdict['status']}"
    if verdict.get("delta_frac") is not None:
        line += (f"  delta {verdict['delta_frac']:+.1%} vs best baseline "
                 f"{verdict['baseline'].get('value')}")
    if verdict.get("age_hours") is not None:
        line += f"  age {verdict['age_hours']}h"
    if verdict.get("recapture"):
        line += f"  recapture -> {', '.join(verdict['recapture'])}"
    print(line)
    return 0


def cmd_cache(args) -> int:
    """``tmx cache list|gc`` — inspect and prune the serialized-
    executable store (DESIGN.md §28)."""
    import json as json_mod

    from tmlibrary_tpu import aotstore

    directory = getattr(args, "store_dir", None)
    if args.verb == "list":
        rows = aotstore.list_entries(directory)
        stats = aotstore.store_stats(directory)
        if args.as_json:
            print(json_mod.dumps({"stats": stats, "entries": rows},
                                 indent=2, sort_keys=True))
            return 0
        print(f"store: {stats['dir']}  "
              f"({'enabled' if stats['enabled'] else 'DISABLED'})")
        print(f"fingerprint: {stats['fingerprint']}  entries: "
              f"{stats['entries']}  bytes: {stats['total_bytes']}  "
              f"stale: {stats['stale_entries']}")
        if rows:
            print(f"{'digest':<18} {'program':<24} {'cap':>5} "
                  f"{'size':>9} {'age':>8} fp")
            for m in rows:
                age = m.get("age_s")
                age_txt = "-" if age is None else (
                    f"{age / 3600:.1f}h" if age >= 3600 else f"{age:.0f}s")
                fp = str(m.get("fingerprint") or "?")[:8]
                if m.get("stale"):
                    fp += " STALE"
                print(f"{str(m.get('digest'))[:16]:<18} "
                      f"{str(m.get('program'))[:24]:<24} "
                      f"{str(m.get('capacity') if m.get('capacity') is not None else '-'):>5} "
                      f"{int(m.get('size_bytes') or 0):>9} "
                      f"{age_txt:>8} {fp}")
        return 0
    if args.verb == "gc":
        max_age_s = (None if args.max_age_days is None
                     else float(args.max_age_days) * 86400.0)
        result = aotstore.prune(
            directory,
            max_bytes=args.max_bytes,
            max_age_s=max_age_s,
            drop_stale_fingerprint=not args.keep_stale,
        )
        if args.as_json:
            print(json_mod.dumps(result, indent=2, sort_keys=True))
            return 0
        print(f"removed {len(result['removed'])} entr"
              f"{'y' if len(result['removed']) == 1 else 'ies'}, "
              f"kept {result['kept']} "
              f"({result['total_bytes']} bytes)")
        for digest in result["removed"]:
            print(f"  - {digest}")
        return 0
    print(f"unknown cache verb: {args.verb}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    configure_logging(getattr(args, "verbosity", 0))
    from tmlibrary_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    try:
        if args.command == "create":
            return cmd_create(args)
        if args.command == "workflow":
            return cmd_workflow(args)
        if args.command == "serve":
            return cmd_serve(args)
        if args.command == "enqueue":
            return cmd_enqueue(args)
        if args.command == "query":
            return cmd_query(args)
        if args.command == "index":
            return cmd_index(args)
        if args.command == "tool":
            return cmd_tool(args)
        if args.command == "project":
            return cmd_project(args)
        if args.command == "inspect":
            return cmd_inspect(args)
        if args.command == "log":
            return cmd_log(args)
        if args.command == "export":
            return cmd_export(args)
        if args.command == "metrics":
            return cmd_metrics(args)
        if args.command == "top":
            return cmd_top(args)
        if args.command == "timeline":
            return cmd_timeline(args)
        if args.command == "trace":
            return cmd_trace(args)
        if args.command == "slo":
            return cmd_slo(args)
        if args.command == "qc":
            return cmd_qc(args)
        if args.command == "weights":
            return cmd_weights(args)
        if args.command == "perf":
            return cmd_perf(args)
        if args.command == "cache":
            return cmd_cache(args)
        return cmd_step(args)
    except Exception as e:
        print(f"error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
