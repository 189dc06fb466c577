"""What every cell shares: the process's environment, the device it
reports, the ``tmx`` console script called in this process, the compile
meter, the profiler window, the per-layer metric readers found by name,
and the one result line.  ``CompileMeter`` and ``ReturnedArrays`` are
copied from ``chip_smoke.py`` (proven on the chip, PR 21)."""

import glob
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

#: the real standard output: everything the program prints goes to
#: stderr, so stdout carries the benchmark's own lines and ends with the
#: result
_OUT = sys.stdout


def take_stdout() -> None:
    """Send every later ``print`` of the process — the program's, from any
    thread — to stderr.  ``emit`` keeps the real stdout."""
    sys.stdout = sys.stderr


def emit(record: dict) -> None:
    _OUT.write(json.dumps(record, sort_keys=True, default=str) + "\n")
    _OUT.flush()


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    """A module of the benchmark by file path (names carry dots and
    dashes, so the import system cannot find them by name)."""
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def prepare_environment() -> str:
    """Before JAX is imported: the persistent compile cache and the
    executable store live at a fixed path inside the checkout, whatever
    the machine's environment says (only the checkout outlasts a run, and
    the path is part of the cache's key), and no size cap evicts the
    small programs around the large ones."""
    cache = os.path.join(CHECKOUT, ".cache", "xla")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    # the daemon would otherwise keep a store of its own under its spool
    os.environ["TMX_AOT_STORE_DIR"] = os.path.join(cache, "aot")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if CHECKOUT not in sys.path:
        sys.path.insert(0, CHECKOUT)
    return cache


def device_record() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device; 0 where the backend keeps
    no such statistic (the CPU rehearsal)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks, default=0))


def tmx(argv: list) -> None:
    """The ``tmx`` console script, in this process."""
    from tmlibrary_tpu import cli

    rc = cli.main([str(a) for a in argv])
    if rc != 0:
        raise RuntimeError(f"tmx {' '.join(map(str, argv[:2]))} exited {rc}")


class CompileMeter:
    """Counts what JAX's own monitoring events report: persistent-cache
    hits and misses, backend compiles and the seconds spent in them (a
    cache hit is a short one)."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def mark(self) -> tuple:
        return (self.hits, self.misses, self.compiles, self.compile_s)

    def since(self, mark) -> dict:
        return {"cache_hits": self.hits - mark[0],
                "cache_misses": self.misses - mark[1],
                "compiles": self.compiles - mark[2],
                "compile_s": self.compile_s - mark[3]}


class ReturnedArrays:
    """Watches ``ImageAnalysisRunner.block_batch`` — the one place every
    launched batch's device arrays pass through — and records on which
    platform each array lived."""

    def __init__(self):
        from tmlibrary_tpu.workflow.steps.jterator import ImageAnalysisRunner

        self.cls = ImageAnalysisRunner
        self.original = ImageAnalysisRunner.block_batch
        self.platforms: set = set()
        self.n_arrays = 0

    def __enter__(self):
        import jax

        watcher = self

        def block_batch(step, ctx):
            kind, payload = ctx
            tree = payload[0] if kind == "sites" else \
                [payload["labels_dev"], payload["count_dev"]]
            for leaf in jax.tree_util.tree_leaves(tree):
                if isinstance(leaf, jax.Array):
                    watcher.n_arrays += 1
                    for shard in leaf.addressable_shards:
                        watcher.platforms.add(shard.device.platform)
            return watcher.original(step, ctx)

        self.cls.block_batch = block_batch
        return self

    def __exit__(self, *exc):
        self.cls.block_batch = self.original
        return False


class TraceWindow:
    """One profiler trace, started and stopped by the benchmark.  The
    anchor annotation is written at a known wall-clock instant, so the
    reduction can put the trace and the ledgers' ``t0`` on one clock."""

    ANCHOR = "bench_anchor"

    def __init__(self, directory: str):
        self.directory = directory
        self.wall_stop = self.anchor_wall = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # the ledger's spans name the host
        options.host_tracer_level = 1
        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.anchor_wall = time.time()
        with jax.profiler.TraceAnnotation(self.ANCHOR):
            time.sleep(0.001)

    def stop(self) -> None:
        import jax

        self.wall_stop = time.time()
        jax.profiler.stop_trace()

    def file(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"no trace written under {self.directory}")
        return found[-1]


def read_metrics(names: list, run) -> dict:
    """Each per-layer metric through its own reader,
    ``metrics/<name>.py``: ``read(run)`` gives the value or None, ``UNIT``
    its unit.  A reader that finds nothing to read returns nothing and
    the metric is left out of the line."""
    out = {}
    for name in names:
        reader = load_module(os.path.join(HERE, "metrics", name + ".py"))
        value = reader.read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": reader.UNIT}
    return out


def at_size(data: dict, on_chip: bool) -> dict:
    """A configuration or traffic mix as it is run: as written on the
    chip, with its ``rehearsal`` keys laid over it anywhere else."""
    return data if on_chip else {**data, **data.get("rehearsal", {})}


def real_compiles(counts: dict) -> int:
    """Backend compiles that JAX's persistent cache did not serve, of a
    ``CompileMeter.since()`` record: real compilations."""
    return counts["compiles"] - counts["cache_hits"]


class Run:
    """What the per-layer readers see of a run; the drivers' runs add
    their own fields.  ``trace``, ``trace_window`` and ``busy_s`` are set
    by ``run.py`` once a ``--trace 1`` run's trace has been read."""

    def __init__(self, kind: str, config: dict, device: dict):
        self.kind, self.config, self.device = kind, config, device
        self.compile: dict = {}     # "setup", "window": CompileMeter.since()
        self.tracer = None          # the TraceWindow of a --trace 1 run
        self.trace = None
        self.trace_window = None    # (lo, hi) seconds on the trace's clock
        self.busy_s = 0.0
        self.memory_peak_bytes = 0
