#!/usr/bin/env python
"""One-command TPU tuning sweep (run when the chip is available):

1. bench batch-size sweep (64/128/256/512) for the default config,
   pinned at pipeline depth ``PIPELINE`` so points stay comparable;
2. pipeline-depth sweep (4/8/16) at the winning batch — the measured
   default for ``bench._pipeline_depth`` on device backends;
3. XLA vs pallas kernel timing for CC labeling, watershed and the
   distance transform;
4. GLCM accumulation shootout: one-hot matmul (MXU) vs scatter-add;
5. writes every number to ``tuning/TUNING.json`` (committed — it is the
   data-driven default for ``pallas_enabled()``, the GLCM method, the
   batch and the pipeline depth) and prints the recommended defaults.

One process holds the chip at a time: this parent never imports JAX.
The bench stages run ``bench.py`` (whose own child holds the chip) and
the kernel stages run :func:`stage_child_main` of this module in a child
interpreter, one after another; each child merges its numbers into
``TUNING.json``.

Usage: python scripts/tune_tpu.py
"""
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import tuning_json_path  # noqa: E402  (one shared definition)

TUNING_PATH = tuning_json_path()
RESULTS: dict = {}

# Timing methodology marker.  Each kernel timing enqueues PIPELINE
# executions and fences them once, so the per-fence host cost lands once
# per rep instead of once per execution and few-ms kernel deltas stop
# drowning in it.  TUNING.json files written under a different
# methodology are not merged with this run's.
PIPELINE = max(1, int(os.environ.get("TUNE_PIPELINE", "8")))
# derived from PIPELINE so a TUNE_PIPELINE override can never stamp its
# (incomparable) numbers with the default methodology marker; same rule
# for the dry-run workload shrinkers — smoke-scale numbers must never
# be mistaken for (or merged into) full-workload hardware results
METHODOLOGY = f"pipelined-depth{PIPELINE}"
_SMOKE = [
    f"{k}={os.environ[k]}" for k in ("TUNE_BATCH", "TUNE_SITE_SIZE")
    if os.environ.get(k)
]
if _SMOKE:
    METHODOLOGY += " SMOKE(" + ",".join(_SMOKE) + ")"


def run_bench(env_overrides):
    env = dict(os.environ, **{k: str(v) for k, v in env_overrides.items()})
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, timeout=2400,
    )
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            backend = rec.get("backend", "")
            if "error" in rec:
                raise RuntimeError(f"bench errored: {rec['error']}")
            # a sweep point must be an on-hardware measurement.  The ONE
            # exception is the forced-CPU rehearsal (backend cpu_forced),
            # whose methodology marker says SMOKE or whose artifacts are
            # redirected by TMX_TUNING_JSON
            if backend.startswith("cpu") and not (
                os.environ.get("BENCH_FORCE_CPU") and backend == "cpu_forced"
            ):
                raise RuntimeError(
                    f"bench ran on {backend} — refusing to record it as "
                    "a tuning point"
                )
            return rec
    raise RuntimeError(f"bench failed: {out.stderr[-500:]}")


def _bench_fn(name, fn, *args, batch=None):
    """Best-of-3 timing; a kernel that fails to compile on the hardware
    records inf (and the error in RESULTS) instead of killing the sweep."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    wrapped = jax.jit(
        lambda *a: sum(jnp.sum(jnp.asarray(l, jnp.float32))
                       for l in jax.tree_util.tree_leaves(fn(*a)))
    )
    try:
        np.asarray(wrapped(*args))
    except Exception as exc:  # Mosaic/XLA compile or runtime failure
        # f-string is never empty (type name), so splitlines()[0] is safe
        msg = f"{type(exc).__name__}: {exc}".splitlines()[0][:200]
        print(f"  {name:32s} FAILED: {msg}")
        RESULTS.setdefault("kernel_errors", {})[name] = msg
        return float("inf")
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        # PIPELINE executions, ONE fetch: see METHODOLOGY note at top
        np.asarray(jnp.stack([wrapped(*args) for _ in range(PIPELINE)]))
        best = min(best, (time.perf_counter() - t0) / PIPELINE)
    rate = f" ({batch/best:7.1f} sites/s)" if batch else ""
    print(f"  {name:32s} {best*1e3:8.2f} ms{rate}")
    return best


def kernel_shootout():
    import jax
    import jax.numpy as jnp

    from tmlibrary_tpu.benchmarks import synthetic_cell_painting_batch
    from tmlibrary_tpu.ops import threshold as thr
    from tmlibrary_tpu.ops.label import connected_components
    from tmlibrary_tpu.ops.segment_primary import distance_transform_approx
    from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds
    from tmlibrary_tpu.ops.smooth import gaussian_smooth

    # TUNE_BATCH/TUNE_SITE_SIZE shrink the workload so the stage's
    # plumbing can be dry-run off-hardware (interpret-mode pallas) —
    # a stage bug must surface in a rehearsal, not burn chip time
    B = int(os.environ.get("TUNE_BATCH", "64"))
    size = int(os.environ.get("TUNE_SITE_SIZE", "256"))
    data = synthetic_cell_painting_batch(B, size=size)
    dapi = jnp.asarray(data["DAPI"])
    actin = jnp.asarray(data["Actin"])
    v = jax.vmap
    interp = jax.default_backend() == "cpu"

    sm = jax.jit(v(lambda im: gaussian_smooth(im, 1.5)))(dapi)
    masks = jax.jit(v(thr.threshold_otsu))(sm)

    # convergence-check interval sweep (kernel-level, CC is the dominant
    # VMEM kernel): chunk is output-invariant — the fixpoint is
    # idempotent — so this is purely a trip-count/check-cost trade the
    # hardware must pick.  The winner is committed as ``pallas_chunk``
    # and both VMEM kernels read it at dispatch time.
    from tmlibrary_tpu.ops.pallas_kernels import cc_min_propagate

    bool_masks = masks != 0
    best_chunk, best_ct = None, float("inf")
    chunk_ms = {}
    for c in (4, 8, 16, 32):
        t_c = _bench_fn(
            f"cc_chunk{c}",
            v(lambda m, _c=c: cc_min_propagate(
                m, 8, interpret=interp, chunk=_c)),
            bool_masks, batch=B,
        )
        chunk_ms[str(c)] = t_c * 1e3
        if t_c < best_ct:
            best_chunk, best_ct = c, t_c
    RESULTS["pallas_chunk"] = best_chunk
    RESULTS["pallas_chunk_ms"] = chunk_ms
    print(f"best pallas chunk: {best_chunk}")

    print("CC labeling:")
    t_x = _bench_fn("cc_xla", v(lambda m: connected_components(m, method='xla')[0]), masks, batch=B)
    t_p = _bench_fn(
        "cc_pallas",
        v(lambda m: connected_components(m, method='pallas', chunk=best_chunk)[0]),
        masks, batch=B)
    nuclei = jax.jit(v(lambda m: connected_components(m, method='xla')[0]))(masks)
    print("watershed (16 levels):")
    w_x = _bench_fn(
        "ws_xla",
        v(lambda l, im: watershed_from_seeds(
            im, l, thr.threshold_otsu(im, correction_factor=0.8),
            n_levels=16, method='xla')),
        nuclei, actin, batch=B,
    )
    w_p = _bench_fn(
        "ws_pallas",
        v(lambda l, im: watershed_from_seeds(
            im, l, thr.threshold_otsu(im, correction_factor=0.8),
            n_levels=16, method='pallas', chunk=best_chunk)),
        nuclei, actin, batch=B,
    )
    print("distance transform:")
    d_x = _bench_fn("dt_xla", v(lambda m: distance_transform_approx(m, method='xla')), masks, batch=B)
    d_p = _bench_fn("dt_pallas", v(lambda m: distance_transform_approx(m, method='pallas')), masks, batch=B)

    print("fill holes:")
    from tmlibrary_tpu.ops.label import fill_holes
    from tmlibrary_tpu.ops.pallas_kernels import fill_holes_flood

    f_x = _bench_fn(
        "fill_xla", v(lambda m: fill_holes(m, method='xla')), masks, batch=B)
    f_p = _bench_fn(
        "fill_pallas",
        v(lambda m, _c=best_chunk: fill_holes_flood(
            m, interpret=interp, chunk=_c)),
        masks, batch=B)

    # 3-D twins (volume config), timed at this run's freshly-swept chunk
    # so the committed verdict matches what production will dispatch.
    # The whole section is guarded: a 3-D-only failure must not discard
    # the five 2-D verdicts measured above (inf → null on write).
    print("3-D CC / watershed (volume):")
    c3_x = c3_p = w3_x = w3_p = float("inf")
    try:
        from tmlibrary_tpu.benchmarks import synthetic_volume_batch
        from tmlibrary_tpu.ops.volume import (
            connected_components_3d,
            watershed_from_seeds_3d,
        )

        B3 = max(2, B // 8)
        vol = jnp.asarray(synthetic_volume_batch(B3, size=size // 2)["DAPI"])
        vmask = vol > jnp.median(vol) + 0.5 * vol.std()
        c3_x = _bench_fn(
            "cc3d_xla",
            v(lambda m: connected_components_3d(m, 26, method='xla')[0]),
            vmask, batch=B3)
        c3_p = _bench_fn(
            "cc3d_pallas",
            v(lambda m: connected_components_3d(
                m, 26, method='pallas', chunk=best_chunk)[0]),
            vmask, batch=B3)
        seeds3 = jax.jit(
            v(lambda m: connected_components_3d(m, 26, method='xla')[0])
        )(vmask)
        w3_x = _bench_fn(
            "ws3d_xla",
            v(lambda s, im, m: watershed_from_seeds_3d(
                im, s, m, 8, method='xla')),
            seeds3, vol, vmask, batch=B3)
        w3_p = _bench_fn(
            "ws3d_pallas",
            v(lambda s, im, m: watershed_from_seeds_3d(
                im, s, m, 8, method='pallas', chunk=best_chunk)),
            seeds3, vol, vmask, batch=B3)
    except Exception as e:  # noqa: BLE001 - hardware shootout guard
        print(f"  3-D section failed ({e}); 2-D verdicts kept")

    RESULTS["kernels_ms"] = {
        "cc_xla": t_x * 1e3, "cc_pallas": t_p * 1e3,
        "fill_xla": f_x * 1e3, "fill_pallas": f_p * 1e3,
        "cc3d_xla": c3_x * 1e3, "cc3d_pallas": c3_p * 1e3,
        "watershed3d_xla": w3_x * 1e3, "watershed3d_pallas": w3_p * 1e3,
        "watershed_xla": w_x * 1e3, "watershed_pallas": w_p * 1e3,
        "distance_xla": d_x * 1e3, "distance_pallas": d_p * 1e3,
    }
    return (t_p + w_p + d_p) < (t_x + w_x + d_x)


def glcm_shootout():
    """Measured matmul-vs-scatter GLCM numbers (round-1 VERDICT item #7)."""
    import jax
    import jax.numpy as jnp

    from tmlibrary_tpu.benchmarks import synthetic_cell_painting_batch
    from tmlibrary_tpu.ops import threshold as thr
    from tmlibrary_tpu.ops.label import connected_components
    from tmlibrary_tpu.ops.measure import haralick_features
    from tmlibrary_tpu.ops.smooth import gaussian_smooth

    B, M, L = 64, 64, 32
    data = synthetic_cell_painting_batch(B, size=256)
    dapi = jnp.asarray(data["DAPI"])
    actin = jnp.asarray(data["Actin"])
    v = jax.vmap
    sm = jax.jit(v(lambda im: gaussian_smooth(im, 1.5)))(dapi)
    labels = jax.jit(v(lambda im: connected_components(
        thr.threshold_otsu(im), method='xla')[0]))(sm)

    print(f"GLCM haralick (batch {B}, {M} objects, {L} levels):")
    g_m = _bench_fn(
        "glcm_matmul", v(lambda l, im: haralick_features(
            l, im, M, levels=L, glcm_method="matmul")), labels, actin, batch=B)
    g_s = _bench_fn(
        "glcm_scatter", v(lambda l, im: haralick_features(
            l, im, M, levels=L, glcm_method="scatter")), labels, actin, batch=B)
    RESULTS["glcm_ms"] = {"matmul": g_m * 1e3, "scatter": g_s * 1e3}
    return g_m < g_s


def run_stage_child(name):
    """Run one stage that times kernels in-process (kernels, glcm) in a
    child, and take over the numbers it merged into TUNING.json.  The
    child starts from this parent's flushed results and owns the chip for
    its lifetime."""
    write_results()
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]); import tune_tpu; "
         "tune_tpu.stage_child_main(sys.argv[2])",
         os.path.dirname(os.path.abspath(__file__)), name],
        text=True, timeout=2400,
    )
    with open(_results_path()) as f:
        fresh = json.load(f)
    RESULTS.clear()
    RESULTS.update(fresh)
    if proc.returncode != 0:
        raise RuntimeError(f"stage child exited {proc.returncode}")


def stage_child_main(name):
    """The child body of one in-process stage (``kernels`` | ``glcm``).
    It takes the parent's flushed results, times the stage on the default
    backend (or the CPU under ``BENCH_FORCE_CPU``) and writes them back."""
    import jax

    from tmlibrary_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    with open(_results_path()) as f:
        RESULTS.update(json.load(f))
    RESULTS["backend"] = jax.default_backend()
    RESULTS["device"] = str(jax.devices()[0])
    if name == "kernels":
        RESULTS["pallas_wins"] = bool(kernel_shootout())
        print(f"pallas wins: {RESULTS['pallas_wins']}")
    elif name == "glcm":
        # a record (``glcm_ms``), not a verdict: the program's choice is
        # the backend's (ops/measure.py _resolve_glcm_method)
        print(f"glcm matmul faster: {bool(glcm_shootout())}")
    else:
        raise SystemExit(f"unknown in-process stage '{name}'")
    write_results()


def main():
    """Each stage is guarded and results are flushed to TUNING.json after
    every stage, so a failure mid-sweep does not lose the stages that DID
    complete.  ``TUNE_SKIP=<stage,stage>`` (sweep | pipeline | kernels |
    glcm | pallas_bench) reruns the rest; pre-existing committed values
    for skipped stages are preserved."""
    skip = set(filter(None, os.environ.get("TUNE_SKIP", "").split(",")))
    prior = {}
    if os.path.exists(TUNING_PATH):
        with open(TUNING_PATH) as f:
            prior = json.load(f)
        # only merge results that write_results() itself produced: merging
        # a hand-transcribed file and then stamping it written_by would
        # launder hand numbers into machine provenance.  Numbers timed
        # under a different methodology are likewise not merged — they
        # are not comparable to this run's and the skipped-stage logic
        # would otherwise mix the two in one file.
        if (
            "written_by" in prior
            and prior.get("timing_methodology") == METHODOLOGY
        ):
            RESULTS.update(prior)

    # stale-failure hygiene: a stage that is about to rerun must not
    # inherit its previous failure records from the committed file
    for name in ("sweep", "pipeline", "kernels", "glcm", "pallas_bench"):
        if name not in skip:
            RESULTS.get("stage_errors", {}).pop(name, None)
    # the pipeline sweep is parameterized by best_batch: a sweep rerun
    # invalidates any committed pipeline verdict measured at the old
    # (or fallback) batch
    if "sweep" not in skip:
        RESULTS.pop("pipeline_sweep", None)
        RESULTS.pop("best_pipeline", None)
    elif (
        "best_batch" not in RESULTS
        and prior.get("written_by") == "scripts/tune_tpu.py write_results"
        and isinstance(prior.get("best_batch"), int)
    ):
        # parameter carry, NOT a result: a stage-limited run still needs
        # the best KNOWN batch.  The previous methodology's sweep winner
        # is the best estimate; the flag marks it un-measured under this
        # methodology, and do_sweep clears it when the real sweep reruns.
        RESULTS["best_batch"] = prior["best_batch"]
        RESULTS["best_batch_carried"] = True
    # kernel_errors entries belong to the kernels stage (cc_/ws_/dt_*)
    # or the glcm stage (glcm_*) — keep only the skipped stage's
    keep = {
        k: v for k, v in RESULTS.pop("kernel_errors", {}).items()
        if ("glcm" if k.startswith("glcm") else "kernels") in skip
    }
    if keep:
        RESULTS["kernel_errors"] = keep
    if not RESULTS.get("stage_errors"):
        RESULTS.pop("stage_errors", None)

    RESULTS["timing_methodology"] = METHODOLOGY

    def stage(name, fn):
        if name in skip:
            print(f"== {name}: skipped (TUNE_SKIP) ==")
            return
        print(f"== {name} ==", flush=True)
        try:
            fn()
        except Exception as exc:
            msg = f"{type(exc).__name__}: {exc}".splitlines()[0][:200]
            print(f"  {name} FAILED: {msg}")
            RESULTS.setdefault("stage_errors", {})[name] = msg
        write_results()

    def do_sweep():
        best = None
        sweep = {}
        for batch in (64, 128, 256, 512):
            # BENCH_PIPELINE pinned: the children would otherwise read
            # whatever best_pipeline is committed at that moment, mixing
            # depths across points and across runs of one methodology
            r = run_bench({"BENCH_BATCH": batch,
                           "BENCH_PIPELINE": PIPELINE})
            print(f"  batch={batch}: {r['value']} sites/s")
            sweep[batch] = r["value"]
            if best is None or r["value"] > best[1]:
                best = (batch, r["value"])
        RESULTS["batch_sweep"] = sweep
        RESULTS["best_batch"] = best[0]
        RESULTS.pop("best_batch_carried", None)
        print(f"best batch: {best[0]} ({best[1]} sites/s)")

    def do_pipeline():
        # in-flight depth sweep at the winning batch: the depth is a
        # methodology default (bench._pipeline_depth), so it must be
        # measured, not guessed
        best = None
        sweep = {}
        for depth in (4, 8, 16):
            r = run_bench({
                "BENCH_BATCH": RESULTS.get("best_batch", 64),
                "BENCH_PIPELINE": depth,
            })
            print(f"  pipeline={depth}: {r['value']} sites/s")
            sweep[depth] = r["value"]
            if best is None or r["value"] > best[1]:
                best = (depth, r["value"])
        RESULTS["pipeline_sweep"] = sweep
        RESULTS["best_pipeline"] = best[0]
        print(f"best pipeline depth: {best[0]} ({best[1]} sites/s)")

    def do_pallas_bench():
        if not RESULTS.get("pallas_wins"):
            return
        r = run_bench({"BENCH_BATCH": RESULTS.get("best_batch", 64),
                       "BENCH_PIPELINE": PIPELINE,
                       "TMX_PALLAS": "1"})
        RESULTS["bench_with_pallas"] = r["value"]
        print(f"bench with TMX_PALLAS=1: {r['value']} sites/s")

    stage("sweep", do_sweep)
    stage("pipeline", do_pipeline)
    stage("kernels", lambda: run_stage_child("kernels"))
    stage("glcm", lambda: run_stage_child("glcm"))
    stage("pallas_bench", do_pallas_bench)


def _results_path():
    """TUNING.json, or its ``.smoke`` sibling for a shrunk dry run so the
    artifacts never shadow the production defaults file (every
    tuned-default loader reads TUNING_PATH; loaders also reject SMOKE
    methodology as a second line of defense)."""
    if _SMOKE and not os.environ.get("TMX_TUNING_JSON"):
        return TUNING_PATH + ".smoke"
    return TUNING_PATH


def write_results():
    """Write TUNING.json with inf (failed kernels) mapped to null so the
    committed file stays strict JSON."""

    def clean(o):
        if isinstance(o, dict):
            return {k: clean(v) for k, v in o.items()}
        if isinstance(o, float) and (o != o or o in (float("inf"), float("-inf"))):
            return None
        return o

    RESULTS["written_by"] = "scripts/tune_tpu.py write_results"
    RESULTS["written_at"] = time.strftime("%Y-%m-%dT%H:%M:%S+00:00", time.gmtime())
    path = _results_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(clean(RESULTS), f, indent=2, sort_keys=True, allow_nan=False)
    if path == TUNING_PATH:
        print(f"wrote {path} — commit it to make these the defaults")
    else:
        print(f"wrote {path} (SMOKE dry run — never production defaults)")


if __name__ == "__main__":
    main()
