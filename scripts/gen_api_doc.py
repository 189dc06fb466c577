#!/usr/bin/env python
"""Generate docs/API.md from the live registries: workflow steps (with
their argument schemas), jterator modules (with signatures), analysis
tools, and the ops library index.  Run after adding steps/modules:

    python scripts/gen_api_doc.py
"""
from __future__ import annotations

import inspect
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = Path(__file__).resolve().parent.parent


def step_section() -> list[str]:
    from tmlibrary_tpu.workflow.registry import get_step, list_steps

    out = ["## Workflow steps", "",
           "Each step exposes the CLI verbs `init / run / collect / "
           "cleanup / info / args` under `tmx <step>`; the tables below "
           "are the `args` schemas (the reference rendered the same "
           "metadata as UI forms).", ""]
    for name in sorted(list_steps()):
        cls = get_step(name)
        doc = (inspect.getdoc(sys.modules[cls.__module__]) or "").split("\n")[0]
        out += [f"### `{name}`", "", doc, "",
                "| argument | type | default | help |", "|---|---|---|---|"]
        for a in cls.batch_args.to_schema():
            default = "required" if a["required"] else repr(a["default"])
            help_ = a["help"]
            if a["choices"]:
                help_ += f" (choices: {', '.join(map(str, a['choices']))})"
            out.append(f"| `{a['name']}` | {a['type']} | {default} | {help_} |")
        out.append("")
    return out


def module_section() -> list[str]:
    from tmlibrary_tpu.jterator.modules import get_module, list_modules

    out = ["## jterator modules", "",
           "Registered JAX module implementations (`backend: tpu`); the "
           "signature's keyword arguments are the handles-file inputs.", "",
           "A pipeline's `input.channels` entry is `{name, correct, align, "
           "zstack, cycle}`.  `cycle` (an integer, optional) is the "
           "acquisition cycle the channel's planes, illumination "
           "statistics and alignment shifts are read from; without it the "
           "channel comes from the jterator step's `cycle` argument, as "
           "every channel of a one-cycle experiment does.  A multiplexed "
           "plate segments on the first cycle's DAPI and measures a later "
           "cycle's stain in those objects with `{name: Mito, align: true, "
           "cycle: 1}`: each aligned channel goes through the batch "
           "program under its own cycle's shift and every channel is "
           "cropped to the window the `align` step stored.  A channel "
           "asked from a cycle that holds no plane of it is a "
           "`PipelineError` before anything is launched.", "",
           "| module | signature | reference |", "|---|---|---|"]
    for name in list_modules():
        fn = get_module(name)
        sig = str(inspect.signature(fn))
        doc = (inspect.getdoc(fn) or "").split("\n")[0]
        out.append(f"| `{name}` | `{sig}` | {doc} |")
    out.append("")
    return out


def tool_section() -> list[str]:
    from tmlibrary_tpu.tools.base import get_tool, list_tools

    out = ["## Analysis tools", "",
           "`tmx tool submit --name <tool> --payload '{...}'`", "",
           "| tool | description |", "|---|---|"]
    for name in sorted(list_tools()):
        doc = (inspect.getdoc(get_tool(name)) or "").split("\n")[0]
        out.append(f"| `{name}` | {doc} |")
    out.append("")
    return out


def ops_section() -> list[str]:
    import importlib
    import pkgutil

    import tmlibrary_tpu.ops as ops_pkg

    out = ["## Ops library", "",
           "Device-side building blocks under `tmlibrary_tpu.ops` "
           "(each module's docstring names its reference analogue).", "",
           "| module | role |", "|---|---|"]
    for info in sorted(pkgutil.iter_modules(ops_pkg.__path__),
                       key=lambda m: m.name):
        mod = importlib.import_module(f"tmlibrary_tpu.ops.{info.name}")
        doc = (inspect.getdoc(mod) or "").split("\n")[0]
        out.append(f"| `ops.{info.name}` | {doc} |")
    out.append("")
    return out


def nn_section() -> list[str]:
    import importlib
    import pkgutil

    import tmlibrary_tpu.nn as nn_pkg

    out = ["## Deep-learning segmentation (`nn/`)", "",
           (inspect.getdoc(nn_pkg) or "").split("\n")[0],
           "",
           "Registered as the `segment_dl_primary` / `segment_dl_"
           "secondary` jterator modules (DESIGN.md §23).  Weight specs: "
           "`seed:N[:base=C][:depth=D][:in=C]` (deterministic init), a "
           "bare checkpoint name resolved in `TMX_WEIGHTS_DIR`, or a "
           "path to an `.npz`; the checkpoint content digest joins the "
           "compiled-program cache key via `program_digest_extras` and "
           "the bench/sweep provenance (`model_digest`, "
           "`+model=<digest>` methodology).  `tmx qc --profile-kind "
           "model` gates the `__model__` output sketches against "
           "`tuning/QC_DL_BASELINE.json`.",
           ""]
    for info in sorted(pkgutil.iter_modules(nn_pkg.__path__),
                       key=lambda m: m.name):
        mod = importlib.import_module(f"tmlibrary_tpu.nn.{info.name}")
        doc = (inspect.getdoc(mod) or "").split("\n")[0]
        out += [f"### `nn.{info.name}`", "", doc, "",
                "| symbol | role |", "|---|---|"]
        for name in sorted(n for n in dir(mod) if not n.startswith("_")):
            obj = getattr(mod, name)
            if not (inspect.isclass(obj) or inspect.isfunction(obj)):
                continue
            if getattr(obj, "__module__", "") != mod.__name__:
                continue
            doc_ = (inspect.getdoc(obj) or "").split("\n")[0]
            out.append(f"| `{info.name}.{name}` | {doc_} |")
        out.append("")
    return out


def telemetry_section() -> list[str]:
    from tmlibrary_tpu import telemetry

    out = ["## Telemetry", "",
           (inspect.getdoc(telemetry) or "").split("\n")[0],
           "",
           "Exported via `tmx metrics --root DIR [--format prom|json] "
           "[--source auto|snapshot|ledger]` and `tmx trace --root DIR "
           "[--json]`; disable with `--no-telemetry` / `TM_TELEMETRY=0`. "
           "Fleet runs additionally get `tmx metrics --merge RUN_ROOT` "
           "(one view over every per-host `metrics.<host>.json`) and the "
           "live dashboard `tmx top --root DIR [--once] "
           "[--interval SECS]`.",
           "",
           "A `span` ledger event: `span` (its name), `parent` (the span "
           "that enclosed it on its thread; absent at the top of a "
           "thread), `t0` (wall clock), `elapsed`, `step`, `batch`, and "
           "numeric attributes (`bytes`, `pixels`, `files`, `tiles`, "
           "`capacity`). Names: `run`, `step`, `batch`; the executor's "
           "phases `prefetch_wait`, `dispatch`, `device_block`, "
           "`persist`; imextract `decode`, `write`; corilla `read_wait`, "
           "`scan`, `finalize`, `write`; illuminati `stats_read`, `read`, "
           "`prep`, `mosaic`, `percentile`, `pyramid`, `level_fetch`, "
           "`encode`; jterator `load`, `upload`, `escalate` (children "
           "`load`, `upload`, `device_wait`), `fetch`, `solidity`, "
           "`write_labels`, `write_features`, `write_polygons`; JAX's "
           "compile path `jit_trace`, `jit_lower`, `jit_compile`, "
           "`cache_load`; `store_import`. `tmx ... --profile DIR` traces "
           "the run for XProf: every span is a `TraceAnnotation` there, "
           "and device operations carry `jax.named_scope` names (pipeline "
           "module, then op stage).",
           "",
           "| symbol | role |", "|---|---|"]
    for name in sorted(getattr(telemetry, "__all__", None) or
                       (n for n in dir(telemetry) if not n.startswith("_"))):
        obj = getattr(telemetry, name)
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        if getattr(obj, "__module__", "") != telemetry.__name__:
            continue
        doc = (inspect.getdoc(obj) or "").split("\n")[0]
        out.append(f"| `telemetry.{name}` | {doc} |")
    out.append("")
    return out


def top_section() -> list[str]:
    from tmlibrary_tpu import top

    out = ["## Fleet dashboard (`tmx top`)", "",
           (inspect.getdoc(top) or "").split("\n")[0],
           "",
           "| symbol | role |", "|---|---|"]
    for name in sorted(n for n in dir(top) if not n.startswith("_")):
        obj = getattr(top, name)
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        if getattr(obj, "__module__", "") != top.__name__:
            continue
        doc = (inspect.getdoc(obj) or "").split("\n")[0]
        out.append(f"| `top.{name}` | {doc} |")
    out.append("")
    return out


def qc_section() -> list[str]:
    from tmlibrary_tpu import qc

    out = ["## Quality control", "",
           (inspect.getdoc(qc) or "").split("\n")[0],
           "",
           "Collected when `tmx workflow submit --qc` (or `TMX_QC=1` / "
           "`TM_QC=1`) is set; reported via `tmx qc --root DIR "
           "[--reference PATH] [--threshold F] [--stale-hours H] "
           "[--worst N] [--json]` with the drift-sentinel exit codes "
           "0 ok / 1 drift / 2 stale / 3 no reference.",
           "",
           "| symbol | role |", "|---|---|"]
    for name in sorted(n for n in dir(qc) if not n.startswith("_")):
        obj = getattr(qc, name)
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        if getattr(obj, "__module__", "") != qc.__name__:
            continue
        doc = (inspect.getdoc(obj) or "").split("\n")[0]
        out.append(f"| `qc.{name}` | {doc} |")
    out.append("")
    return out


def resilience_section() -> list[str]:
    from tmlibrary_tpu import resilience

    out = ["## Resilience & survivability", "",
           (inspect.getdoc(resilience) or "").split("\n")[0],
           "",
           "Retry/breaker/CPU-degradation knobs ride `tmx workflow "
           "submit` (`--retry-attempts`, `--retry-delay`, "
           "`--max-batch-failures`, `--probe-timeout`).  SIGTERM/SIGINT "
           "drain the run and exit with the pinned code 75 so wrappers "
           "re-launch `tmx workflow submit --resume`; phase watchdogs "
           "arm with `TMX_WATCHDOG=1` + "
           "`TMX_WATCHDOG_{LAUNCH,BLOCK,PERSIST}_S` (DESIGN.md §19).",
           "",
           "| symbol | role |", "|---|---|"]
    for name in sorted(n for n in dir(resilience) if not n.startswith("_")):
        obj = getattr(resilience, name)
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        if getattr(obj, "__module__", "") != resilience.__name__:
            continue
        doc = (inspect.getdoc(obj) or "").split("\n")[0]
        out.append(f"| `resilience.{name}` | {doc} |")
    out.append("")
    return out


def perf_section() -> list[str]:
    from tmlibrary_tpu import perf

    out = ["## Performance attribution", "",
           (inspect.getdoc(perf) or "").split("\n")[0],
           "",
           "Surfaced via `tmx perf --root DIR [--top N] [--json]`, "
           "`tmx perf history`, `tmx_perf_*` metrics in `tmx metrics`, "
           "and the CI sentinel `scripts/bench_regression.py` "
           "(exit 0 ok / 1 regression / 2 stale / 3 no baseline).",
           "",
           "| symbol | role |", "|---|---|"]
    for name in sorted(n for n in dir(perf) if not n.startswith("_")):
        obj = getattr(perf, name)
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        if getattr(obj, "__module__", "") != perf.__name__:
            continue
        doc = (inspect.getdoc(obj) or "").split("\n")[0]
        out.append(f"| `perf.{name}` | {doc} |")
    out.append("")
    return out


def schedule_section() -> list[str]:
    from tmlibrary_tpu.workflow import schedule

    out = ["## Work-aware site scheduling (`workflow/schedule.py`)", "",
           (inspect.getdoc(schedule) or "").split("\n")[0],
           "",
           "Per-site object counts (harvested from prior runs' feature "
           "shards, refined by a live EWMA over every completed batch) "
           "feed a deterministic packing plan: sites sorted by "
           "predicted work into rung-homogeneous batches (the same "
           "batch-size multiset directory order produces, so no new "
           "compiled signatures), each batch's sites permuted so every "
           "device shard carries near-equal predicted work.  The plan "
           "is recorded as a `schedule_plan` ledger event + side file "
           "so `--resume` re-derives identical batch boundaries.  Knobs "
           "(precedence order): `--schedule pack|off|auto`, "
           "`TMX_SCHEDULE`, install config `schedule`, the swept "
           "TUNING.json `schedule` verdict, default packing on.  "
           "Surfaced by the PACK row in `tmx top`, the packing table "
           "in `tmx perf`, and the `tmx_schedule_*` / "
           "`tmx_device_predicted_work` series (DESIGN.md §29).",
           "",
           "| symbol | role |", "|---|---|"]
    for name in sorted(n for n in dir(schedule) if not n.startswith("_")):
        obj = getattr(schedule, name)
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        if getattr(obj, "__module__", "") != schedule.__name__:
            continue
        doc = (inspect.getdoc(obj) or "").split("\n")[0]
        out.append(f"| `schedule.{name}` | {doc} |")
    out.append("")
    return out


def aotstore_section() -> list[str]:
    from tmlibrary_tpu import aotstore

    out = ["## Cold-start elimination (`aotstore`, `tmx cache`)", "",
           (inspect.getdoc(aotstore) or "").split("\n")[0],
           "",
           "perf.py's AOT compile path exports every executable into a "
           "content-addressed on-disk store (digest = program identity "
           "+ capacity rung + input signature + "
           "jax/jaxlib/backend fingerprint) and imports it back on the "
           "next process — or the next fleet host, via the shared "
           "serve-root store — instead of compiling.  Compile-ahead "
           "speculation (`perf.speculate_compile`) precompiles likely "
           "next capacity rungs off the critical path.  Operator "
           "surface: `tmx cache list|gc [--dir D] [--json]`, the WARM "
           "row in `tmx top` / `tmx serve status`, and the "
           "`tmx_compile_{cold,warm,import_hit,export}_total` / "
           "`tmx_compile_seconds_saved_total` series (DESIGN.md §28).",
           "",
           "| symbol | role |", "|---|---|"]
    for name in sorted(n for n in dir(aotstore) if not n.startswith("_")):
        obj = getattr(aotstore, name)
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        if getattr(obj, "__module__", "") != aotstore.__name__:
            continue
        doc = (inspect.getdoc(obj) or "").split("\n")[0]
        out.append(f"| `aotstore.{name}` | {doc} |")
    out.append("")
    return out


def serve_section() -> list[str]:
    from tmlibrary_tpu import serve
    from tmlibrary_tpu.workflow import admission

    out = ["## Serving (`tmx serve`)", "",
           (inspect.getdoc(serve) or "").split("\n")[0],
           "",
           "Driven by `tmx serve run --root DIR [--max-queue N] "
           "[--tenant-quota N] [--retry-budget N] "
           "[--tenant-weights T=W,...] [--max-jobs N] [--idle-exit S]`, "
           "`tmx serve status [--json]` and `tmx enqueue --root DIR "
           "--experiment EXP [--tenant T] [--priority P] "
           "[--deadline SECS]`.  Every rejection reason carries a "
           "pinned `retry_after_s` (DESIGN.md §20 policy table); a "
           "SIGTERM'd daemon re-spools and exits the pinned code 75.",
           "",
           "| symbol | role |", "|---|---|"]
    for mod, prefix in ((serve, "serve"), (admission, "admission")):
        for name in sorted(n for n in dir(mod) if not n.startswith("_")):
            obj = getattr(mod, name)
            if not (inspect.isclass(obj) or inspect.isfunction(obj)):
                continue
            if getattr(obj, "__module__", "") != mod.__name__:
                continue
            doc = (inspect.getdoc(obj) or "").split("\n")[0]
            out.append(f"| `{prefix}.{name}` | {doc} |")
    out.append("")
    return out


def slo_section() -> list[str]:
    from tmlibrary_tpu import slo, traceexport

    out = ["## Request-level observability (`tmx slo`, "
           "`tmx trace --export chrome`)", "",
           (inspect.getdoc(slo) or "").split("\n")[0],
           "",
           "`tmx enqueue` stamps a `trace_id` into every job spec; "
           "`tmx slo --root DIR [--json]` reports per-tenant p50/p95 "
           "latency, availability and multi-window burn (exit 0 ok / "
           "1 burn / 3 no data; objectives from `TM_SLO_*` config with "
           "`TMX_SLO_*` / per-tenant `TMX_SLO_<KNOB>_<TENANT>` env "
           "overrides), and `tmx trace --root DIR --export chrome OUT "
           "[--trace-id ID]` renders the ledger span trees as validated "
           "Trace Event Format JSON (DESIGN.md §21).",
           "",
           "| symbol | role |", "|---|---|"]
    for mod, prefix in ((slo, "slo"), (traceexport, "traceexport")):
        for name in sorted(n for n in dir(mod) if not n.startswith("_")):
            obj = getattr(mod, name)
            if not (inspect.isclass(obj) or inspect.isfunction(obj)):
                continue
            if getattr(obj, "__module__", "") != mod.__name__:
                continue
            doc = (inspect.getdoc(obj) or "").split("\n")[0]
            out.append(f"| `{prefix}.{name}` | {doc} |")
    out.append("")
    return out


def timeseries_section() -> list[str]:
    from tmlibrary_tpu import canary, timeseries

    out = ["## Continuous observability (`tmx timeline`, canary probes)",
           "",
           (inspect.getdoc(timeseries) or "").split("\n")[0],
           "",
           "Every registry snapshot flush also lands as timestamped "
           "samples in an append-only per-host `tsdb.<host>.jsonl` "
           "segment (raw ring -> 1m -> 15m rollups, retention "
           "compaction); `tmx timeline --root DIR [--metric SUB] "
           "[--json]` merges the per-host segments into per-series "
           "sparklines, falling back to ledger replay for seed-era "
           "roots.  `tmx serve run --canary SECONDS` arms per-host "
           "self-probes whose latency feeds an EWMA/z-score anomaly "
           "detector — a pure function of the ledger window, so replay "
           "reproduces the live anomaly sequence bit-identically "
           "(DESIGN.md §27).",
           "",
           "| symbol | role |", "|---|---|"]
    for mod, prefix in ((timeseries, "timeseries"), (canary, "canary")):
        for name in sorted(n for n in dir(mod) if not n.startswith("_")):
            obj = getattr(mod, name)
            if not (inspect.isclass(obj) or inspect.isfunction(obj)):
                continue
            if getattr(obj, "__module__", "") != mod.__name__:
                continue
            doc = (inspect.getdoc(obj) or "").split("\n")[0]
            out.append(f"| `{prefix}.{name}` | {doc} |")
    out.append("")
    return out


def analytics_section() -> list[str]:
    import importlib

    import tmlibrary_tpu.analytics as analytics_pkg

    out = ["## Analytics (`tmx query`)", "",
           (inspect.getdoc(analytics_pkg) or "").split("\n")[0],
           "",
           "`tmx query --root EXP --tool T --objects NAME "
           "[--payload '{...}'] [--no-cache]` answers one query in "
           "process; `tmx enqueue --kind query --tool T --objects NAME` "
           "routes the same payload through the serve daemon "
           "(admission, WDRR, trace spans, SLO).  Results cache under "
           "`tools/queries/<key>/` keyed by the feature-store content "
           "digest + the canonical payload (DESIGN.md §24).  `tmx index "
           "build|list --root EXP --objects NAME` manages the persisted "
           "IVF kNN index; `--index auto|ivf|brute` routes a query "
           "(DESIGN.md §26), and concurrent fusable kNN jobs in the "
           "daemon share one batched sweep.",
           "",
           "| symbol | role |", "|---|---|"]
    for modname, prefix in (("store", "analytics.store"),
                            ("ops", "analytics.ops"),
                            ("index", "analytics.index"),
                            ("spatial", "analytics.spatial"),
                            ("query", "analytics.query")):
        mod = importlib.import_module(f"tmlibrary_tpu.analytics.{modname}")
        for name in sorted(n for n in dir(mod) if not n.startswith("_")):
            obj = getattr(mod, name)
            if not (inspect.isclass(obj) or inspect.isfunction(obj)):
                continue
            if getattr(obj, "__module__", "") != mod.__name__:
                continue
            doc = (inspect.getdoc(obj) or "").split("\n")[0]
            out.append(f"| `{prefix}.{name}` | {doc} |")
    out.append("")
    return out


def main() -> None:
    lines = [
        "# tmlibrary_tpu API reference",
        "",
        "Generated by `scripts/gen_api_doc.py` from the live registries —",
        "regenerate after adding steps/modules/tools.",
        "",
        *step_section(),
        *module_section(),
        *tool_section(),
        *ops_section(),
        *nn_section(),
        *telemetry_section(),
        *top_section(),
        *qc_section(),
        *perf_section(),
        *schedule_section(),
        *aotstore_section(),
        *resilience_section(),
        *serve_section(),
        *slo_section(),
        *timeseries_section(),
        *analytics_section(),
    ]
    # optional output override so a freshness check can generate into a
    # scratch path without clobbering the committed file
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else REPO / "docs" / "API.md"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text("\n".join(lines), encoding="utf-8")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
