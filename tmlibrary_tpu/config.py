"""Framework configuration.

Reference parity: ``tmlib/config.py`` — the reference reads a ``tmaps.cfg``
INI file (``LibraryConfig``) holding DB connection, storage paths and the
cluster resource definition.  The TPU rebuild has no database and no cluster
scheduler, so configuration shrinks to: storage root, device/mesh settings,
and logging.  Values come from (highest priority first) explicit kwargs, the
``TM_*`` environment, an INI file (``$TM_CONFIG_FILE`` or
``~/.tmlibrary.cfg``, section ``[tmlibrary]``), then defaults.
"""

from __future__ import annotations

import configparser
import dataclasses
import functools
import os
from pathlib import Path


def _ini_values() -> dict:
    """Read the ``[tmlibrary]`` section of the config INI, if present
    (reference ``tmaps.cfg`` mechanism).  Cached per (path, mtime) so a
    ``LibraryConfig()`` construction doesn't re-parse the file once per
    field; a malformed file degrades to defaults with a warning instead
    of crashing package import (``cfg`` is built at module level)."""
    path = os.environ.get(
        "TM_CONFIG_FILE", os.path.expanduser("~/.tmlibrary.cfg")
    )
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return {}
    return _parse_ini(path, mtime)


@functools.lru_cache(maxsize=8)
def _parse_ini(path: str, _mtime_ns: int) -> dict:
    # no interpolation: '%' is common in paths/date patterns and the
    # reference INI has no interpolation semantics either
    parser = configparser.ConfigParser(interpolation=None)
    try:
        parser.read(path)
        if not parser.has_section("tmlibrary"):
            return {}
        return dict(parser.items("tmlibrary"))
    except configparser.Error as exc:
        import warnings

        warnings.warn(f"ignoring malformed config file {path}: {exc}")
        return {}


def _setting(name: str, default: str) -> str:
    """One install-level setting: ``TM_<NAME>`` env beats the INI file
    beats the built-in default."""
    env = os.environ.get(f"TM_{name.upper()}")
    if env is not None:
        return env
    return _ini_values().get(name, default)


@dataclasses.dataclass
class LibraryConfig:
    """Install-level configuration.

    Attributes
    ----------
    storage_home:
        Root directory under which experiment stores live
        (reference analogue: ``tmaps.cfg`` ``storage_home``).
    mesh_shape:
        Default device mesh shape for multi-chip runs, as a dict of
        axis name → size.  ``None`` means "one axis named 'sites' over all
        visible devices".
    compute_dtype:
        dtype for display-only device math (the viewer pyramid's
        downsample chain — ``ops/pyramid.py``); ``bfloat16`` halves that
        path's HBM traffic at the cost of possible banding on channels
        displayed over a narrow clip window (see ``_display_dtype``).
        The analysis path (segmentation/measurement/statistics)
        deliberately ignores this knob: it is fp32 with
        HIGHEST-precision convs because bit-identical goldens gate it
        (DESIGN.md).
    """

    storage_home: Path = dataclasses.field(
        default_factory=lambda: Path(
            _setting("storage_home", os.path.expanduser("~/tm_storage"))
        )
    )
    mesh_shape: dict | None = None
    compute_dtype: str = dataclasses.field(
        default_factory=lambda: _setting("compute_dtype", "float32")
    )
    verbosity: int = dataclasses.field(
        default_factory=lambda: int(_setting("verbosity", "0"))
    )
    # ----------------------------------------------------- fault tolerance
    # (resilience.py / workflow engine; env: TM_RETRY_ATTEMPTS etc.)
    #: total tries per batch (1 = no retry) for transient faults
    retry_attempts: int = dataclasses.field(
        default_factory=lambda: int(_setting("retry_attempts", "3"))
    )
    #: first backoff delay in seconds (doubles per retry, jittered)
    retry_base_delay: float = dataclasses.field(
        default_factory=lambda: float(_setting("retry_base_delay", "0.25"))
    )
    #: quarantine budget per step — fraction of batches if < 1, else count
    max_batch_failures: float = dataclasses.field(
        default_factory=lambda: float(_setting("max_batch_failures", "0.5"))
    )
    #: device health probe deadline (an unreachable device can hang; this
    #: bounds it)
    device_probe_timeout: float = dataclasses.field(
        default_factory=lambda: float(_setting("device_probe_timeout", "30"))
    )
    #: fsync the run ledger on every append (crash-safe, slower)
    ledger_fsync: bool = dataclasses.field(
        default_factory=lambda: _setting("ledger_fsync", "0").lower()
        in ("1", "true", "yes")
    )
    #: phase-watchdog master switch (resilience.PhaseWatchdog): deadlines
    #: over the pipelined launch/block/persist phases that classify a
    #: wedged device call as transient instead of hanging forever.  Off
    #: by default (off = no monitor thread, no arming, no events); the
    #: TMX_WATCHDOG env set by operators beats this setting
    watchdog: bool = dataclasses.field(
        default_factory=lambda: _setting("watchdog", "0").lower()
        in ("1", "true", "yes")
    )
    #: per-phase watchdog deadlines in seconds (0 disarms a phase);
    #: deliberately generous — these catch *wedged* calls, not slow ones.
    #: TMX_WATCHDOG_{LAUNCH,BLOCK,PERSIST}_S env knobs beat these fields
    watchdog_launch_s: float = dataclasses.field(
        default_factory=lambda: float(_setting("watchdog_launch_s", "300"))
    )
    watchdog_block_s: float = dataclasses.field(
        default_factory=lambda: float(_setting("watchdog_block_s", "600"))
    )
    watchdog_persist_s: float = dataclasses.field(
        default_factory=lambda: float(_setting("watchdog_persist_s", "600"))
    )
    # ------------------------------------------------------- pipelining
    #: in-flight batch window for the pipelined executor; 0 = auto
    #: (tuning/TUNING.json best_pipeline on device backends, else a safe
    #: per-backend default — see workflow/pipelined.resolve_pipeline_depth)
    pipeline_depth: int = dataclasses.field(
        default_factory=lambda: int(_setting("pipeline_depth", "0"))
    )
    #: serialized AOT executable store master switch (aotstore.py): the
    #: perf AOT path exports every compiled executable and imports it
    #: back on the next process/host instead of compiling cold.  The
    #: TMX_AOT_STORE env (set by tests/operators) beats this setting
    aot_store: str = dataclasses.field(
        default_factory=lambda: _setting("aot_store", "1")
    )
    #: store directory; "" = the resolution chain in aotstore.store_dir
    #: (TMX_AOT_STORE_DIR env > this > process default — serve daemons
    #: point the default at the shared serve root > beside the compile
    #: cache, ``<checkout>/.cache/aot``)
    aot_store_dir: str = dataclasses.field(
        default_factory=lambda: _setting("aot_store_dir", "")
    )
    #: LRU cap on the store's total payload bytes (<=0 = uncapped);
    #: TMX_AOT_STORE_MAX_BYTES env beats this setting
    aot_store_max_bytes: str = dataclasses.field(
        default_factory=lambda: _setting("aot_store_max_bytes", "")
    )
    #: compile-ahead speculation switch: a background warm thread
    #: precompiles the likely next capacity rungs during prefetch idle
    #: so bucket escalation stops paying compile on the critical path.
    #: The TMX_AOT_SPECULATE env beats this setting
    aot_speculate: str = dataclasses.field(
        default_factory=lambda: _setting("aot_speculate", "1")
    )
    #: work-aware site scheduling mode for the jterator dispatch plane
    #: ("auto" | "pack" | "off"); "auto" falls through to the tuned
    #: TUNING.json verdict, then packing on (workflow/schedule.py
    #: documents the full resolution order — the TMX_SCHEDULE env set by
    #: the CLI --schedule knob beats this setting).  Packing is
    #: bit-identical per site; the knob is purely a performance decision
    schedule: str = dataclasses.field(
        default_factory=lambda: _setting("schedule", "auto")
    )
    #: donate raw-image/stats buffers to engine-built batch programs so
    #: XLA reuses their device memory for outputs
    donate_buffers: bool = dataclasses.field(
        default_factory=lambda: _setting("donate_buffers", "1").lower()
        in ("1", "true", "yes")
    )
    # ------------------------------------------------------- telemetry
    #: master switch for the metrics registry + span tracing
    #: (telemetry.py); off hands out null instruments — zero cost
    telemetry: bool = dataclasses.field(
        default_factory=lambda: _setting("telemetry", "1").lower()
        in ("1", "true", "yes")
    )
    #: resource sampler period in seconds (RSS/fds/device memory gauges +
    #: heartbeat file); 0 disables the sampler thread
    resource_sample_period: float = dataclasses.field(
        default_factory=lambda: float(
            _setting("resource_sample_period", "5")
        )
    )
    # ------------------------------------------------------- data quality
    #: QC subsystem gate (qc.py): fused on-device image stats, numerics
    #: guards, feature sketches.  Off by default; the TMX_QC env var
    #: (set by `tmx workflow submit --qc`) beats this setting because
    #: the gate is part of the compiled-program cache key
    qc: bool = dataclasses.field(
        default_factory=lambda: _setting("qc", "0").lower()
        in ("1", "true", "yes")
    )
    #: fraction of a step's planned sites QC may flag before the engine
    #: logs a qc_budget_exceeded ledger event (warn-only)
    qc_flag_budget: float = dataclasses.field(
        default_factory=lambda: float(_setting("qc_flag_budget", "0.5"))
    )
    # ---------------------------------------------------------- serving
    # (serve.py / workflow/admission.py; env: TM_SERVE_* — CLI flags on
    # `tmx serve run` beat these)
    #: admission-queue high watermark: at this depth new jobs are shed
    serve_max_queue: int = dataclasses.field(
        default_factory=lambda: int(_setting("serve_max_queue", "64"))
    )
    #: low watermark shedding hysteresis re-admits below; 0 = max/2
    serve_low_watermark: int = dataclasses.field(
        default_factory=lambda: int(_setting("serve_low_watermark", "0"))
    )
    #: per-tenant cap on queued jobs (fairness floor for everyone else)
    serve_tenant_quota: int = dataclasses.field(
        default_factory=lambda: int(_setting("serve_tenant_quota", "16"))
    )
    #: per-tenant retry budget: resubmissions (attempt > 0) spend one
    #: token each; an exhausted budget converts a retry storm into
    #: early rejection.  A successful job refunds one token.
    serve_retry_budget: int = dataclasses.field(
        default_factory=lambda: int(_setting("serve_retry_budget", "8"))
    )
    #: spool poll period for the serve daemon, seconds
    serve_poll_s: float = dataclasses.field(
        default_factory=lambda: float(_setting("serve_poll_s", "0.5"))
    )
    #: admission-phase watchdog deadline, seconds (0 disarms; only armed
    #: when the watchdog master switch is on)
    serve_admission_deadline_s: float = dataclasses.field(
        default_factory=lambda: float(
            _setting("serve_admission_deadline_s", "60")
        )
    )
    #: multi-query fusion in the serve loop: concurrent `kind: query`
    #: jobs against one store digest coalesce into one batched device
    #: sweep (serve.py; per-job caches and attribution preserved)
    serve_query_fusion: bool = dataclasses.field(
        default_factory=lambda: _setting("serve_query_fusion", "1").lower()
        in ("1", "true", "yes")
    )
    #: max jobs folded into one fused query sweep
    serve_fusion_window: int = dataclasses.field(
        default_factory=lambda: int(_setting("serve_fusion_window", "8"))
    )
    # --------------------------------------------------------- analytics
    #: kNN index mode for the analytics tier ("auto" | "ivf" | "brute");
    #: "auto" falls through to the tuned TUNING.json verdict, then a
    #: size cutover (analytics/index.py documents the full resolution
    #: order — the TMX_ANALYTICS_INDEX env beats this setting)
    analytics_index: str = dataclasses.field(
        default_factory=lambda: _setting("analytics_index", "auto")
    )
    #: fleet spool lease duration, seconds: how long one host's claim on
    #: an admitted job stays valid without renewal.  A peer's reaper may
    #: reclaim the job once the lease is expired AND the claiming host's
    #: heartbeat has gone stale — so this bounds how long a dead host can
    #: sit on a job.  Renewal rides the heartbeat cadence (lease/3).
    serve_lease_s: float = dataclasses.field(
        default_factory=lambda: float(_setting("serve_lease_s", "15"))
    )
    # -------------------------------------------------- observability
    # (timeseries.py / canary.py; DESIGN.md §27)
    #: canary probe period, seconds; 0 disables probes (the default —
    #: probes are an always-on-service feature, opt-in per daemon)
    serve_canary_period_s: float = dataclasses.field(
        default_factory=lambda: float(_setting("serve_canary_period_s",
                                               "0"))
    )
    #: how often the daemon re-runs the anomaly detector over the merged
    #: fleet ledger (the detector itself is pure; this only throttles
    #: the ledger re-read)
    serve_anomaly_check_s: float = dataclasses.field(
        default_factory=lambda: float(_setting("serve_anomaly_check_s",
                                               "5"))
    )
    #: minimum seconds between time-series flushes of a live registry
    #: snapshot into the tsdb segment
    tsdb_flush_s: float = dataclasses.field(
        default_factory=lambda: float(_setting("tsdb_flush_s", "10"))
    )
    #: raw samples older than this are dropped at compaction (rollups
    #: summarize them first — see timeseries.compact_records)
    tsdb_retention_s: float = dataclasses.field(
        default_factory=lambda: float(_setting("tsdb_retention_s",
                                               "86400"))
    )
    #: segment size that triggers a compaction pass (an O(1) stat per
    #: flush, so the hot path never pays for downsampling)
    tsdb_segment_bytes: int = dataclasses.field(
        default_factory=lambda: int(_setting("tsdb_segment_bytes",
                                             "1048576"))
    )
    # ---------------------------------------------------------- SLO
    # (slo.py; env: TM_SLO_* here, with TMX_SLO_* runtime overrides —
    # including per-tenant TMX_SLO_<KNOB>_<TENANT> — taking precedence)
    #: per-tenant latency objective: p95 job latency must stay at or
    #: under this many seconds
    slo_latency_p95_s: float = dataclasses.field(
        default_factory=lambda: float(_setting("slo_latency_p95_s", "600"))
    )
    #: per-tenant availability objective: the fraction of jobs that must
    #: complete ok (failed + expired spend the error budget)
    slo_availability: float = dataclasses.field(
        default_factory=lambda: float(_setting("slo_availability", "0.99"))
    )
    #: comma-separated burn-rate windows, seconds (multi-window per the
    #: usual fast-burn/slow-burn alerting split)
    slo_windows: str = dataclasses.field(
        default_factory=lambda: _setting("slo_windows", "3600,21600")
    )

    def experiment_location(self, experiment_name: str) -> Path:
        return Path(self.storage_home) / "experiments" / experiment_name


#: Global default config instance, mirroring the reference's module-level
#: ``tmlib.cfg``.
cfg = LibraryConfig()
