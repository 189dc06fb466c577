#!/usr/bin/env python3
"""Time the config-4 measure families on the attached chip at acquisition
geometry (one 2160x2160 field, capacity 1024) under both grouped-reduction
strategies and both GLCM methods, and check the per-object stretch against
integer arithmetic.  One JSON line a case on stdout and all of them in
``chiprun_out/tune_measure.json``; the times go into ``tuning/TUNING.json``
by hand, as a record with this script named as their provenance (the
program reads none of them: its choice is the backend's,
``ops/reduction.py``).

    chiprun -- python scripts/tune_measure_tpu.py            # run on the chip
    python scripts/tune_measure_tpu.py --describe             # compile only,
        for a described v5e, here (compile seconds, no times)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SIZE, CAPACITY, CELLS = 2160, 1024, 500


def synth(seed, size, cells):
    """Labels (nuclei-like discs and cell-like discs) and a uint16-valued
    float32 plane, from the benchmark's own field recipe."""
    import numpy as np

    from benchmark import plate

    rng = np.random.default_rng(seed)
    planes = plate.synth_field(rng, size, cells, ["DAPI", "Actin"])
    # the same stream again, past the two noise planes: the cells' own
    # positions, so every disc sits on a cell body
    rng = np.random.default_rng(seed)
    rng.normal(300.0, 25.0, (size, size))
    rng.normal(300.0, 25.0, (size, size))
    margin = max(4, size // 20)
    ys = rng.integers(margin, size - margin, cells)
    xs = rng.integers(margin, size - margin, cells)
    lab = np.zeros((size, size), np.int32)
    yy, xx = np.mgrid[0:size, 0:size]
    for i, (y, x) in enumerate(zip(ys, xs)):
        r = 14
        y0, y1, x0, x1 = max(0, y - r), min(size, y + r + 1), \
            max(0, x - r), min(size, x + r + 1)
        sel = (yy[y0:y1, x0:x1] - y) ** 2 + (xx[y0:y1, x0:x1] - x) ** 2 <= r * r
        lab[y0:y1, x0:x1][sel] = i + 1
    return lab, planes["Actin"].astype(np.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--size", type=int, default=SIZE)
    ap.add_argument("--capacity", type=int, default=CAPACITY)
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        jax.default_backend = lambda: "tpu"
    from tmlibrary_tpu.ops import measure

    size, cap = args.size, args.capacity
    results = []

    def emit(rec):
        results.append(rec)
        print(json.dumps(rec), flush=True)

    if args.describe:
        lab = jax.ShapeDtypeStruct((size, size), jnp.int32, sharding=one)
        img = jax.ShapeDtypeStruct((size, size), jnp.float32, sharding=one)
    else:
        lab_h, img_h = synth(7, size, CELLS)
        lab, img = jnp.asarray(lab_h), jnp.asarray(img_h)
        emit({"case": "device", "kind": jax.devices()[0].device_kind,
              "platform": jax.devices()[0].platform})

    def timed(name, fn, *a, reps=5):
        if args.only and args.only not in name:
            return None
        t0 = time.perf_counter()
        try:
            compiled = jax.jit(fn).lower(*a).compile()
        except Exception as exc:  # a refusal is a result
            emit({"case": name, "error": f"{type(exc).__name__}: "
                  f"{str(exc)[:300]}"})
            return None
        rec = {"case": name, "compile_s": round(time.perf_counter() - t0, 2)}
        mem = compiled.memory_analysis()
        rec["temp_mb"] = round(mem.temp_size_in_bytes / 1e6, 1)
        rec["callbacks"] = compiled.as_text().count("callback")
        out = None
        if not args.describe:
            out = compiled(*a)
            jax.block_until_ready(out)
            times = []
            for _ in range(reps):
                t = time.perf_counter()
                out = compiled(*a)
                jax.block_until_ready(out)
                times.append(time.perf_counter() - t)
            rec["ms"] = round(1e3 * sorted(times)[len(times) // 2], 3)
            rec["ms_all"] = [round(1e3 * t, 2) for t in times]
        emit(rec)
        return out

    def scoped(strategy, fn):
        """``fn`` traced with ``strategy`` wherever a measure function asks
        the resolver: morphology and Zernike take no strategy argument."""
        resolve = measure.resolve_reduction_strategy

        def run(*a):
            measure.resolve_reduction_strategy = lambda method="auto": (
                resolve(strategy if method == "auto" else method))
            try:
                return fn(*a)
            finally:
                measure.resolve_reduction_strategy = resolve
        return run

    # ---- the stretch: floor of a TPU division against integer arithmetic
    if not args.describe and (not args.only or "stretch" in args.only):
        def stretch_plain(l, v):
            lo, hi = measure.grouped_minmax(l, v, cap)
            present = hi >= lo
            lo = jnp.where(present, lo, 0.0)
            span = jnp.where(present, hi - lo, 1.0)
            tab = jnp.stack([jnp.concatenate([jnp.zeros(1), lo]),
                             jnp.concatenate([jnp.ones(1), span])], -1)
            pp = measure.lookup_by_label(l, tab)
            q = jnp.floor((v - pp[..., 0]) * 15 / jnp.maximum(pp[..., 1], 1e-6))
            return jnp.clip(q, 0, 15).astype(jnp.int32)

        lo = np.full(cap + 1, 0, np.int64)
        hi = np.full(cap + 1, 1, np.int64)
        vi = img_h.astype(np.int64)
        for i in range(1, int(lab_h.max()) + 1):
            sel = vi[lab_h == i]
            if sel.size:
                lo[i], hi[i] = sel.min(), sel.max()
        span = np.maximum(hi - lo, 1)
        want = np.clip((vi - lo[lab_h]) * 15 // span[lab_h], 0, 15)
        fg = lab_h > 0
        for name, fn in (("stretch.plain_division", stretch_plain),
                         ("stretch.quantize_per_object",
                          lambda l, v: measure.quantize_per_object(l, v, cap, 16))):
            got = timed(name, fn, lab, img, reps=2)
            if got is not None:
                got = np.asarray(got)
                emit({"case": name + ".check",
                      "pixels_in_objects": int(fg.sum()),
                      "bins_wrong": int((got[fg] != want[fg]).sum()),
                      "bins_low": int((got[fg] < want[fg]).sum())})

    # ---- grouped reductions, by strategy
    for strategy in ("onehot", "scatter"):
        timed(f"intensity.{strategy}", scoped(
            strategy, lambda l, v: measure.intensity_features(l, v, cap)),
            lab, img)
        timed(f"morphology.{strategy}", scoped(
            strategy, lambda l: measure.morphology_features(l, cap)), lab)
        timed(f"zernike.{strategy}", scoped(
            strategy, lambda l: measure.zernike_features(
                l, cap, degree=6, method="xla")), lab)
    # ---- Haralick, by GLCM method (reductions at the default strategy)
    for method in ("matmul", "scatter"):
        timed(f"texture.{method}", lambda l, v, m=method:
              measure.haralick_features(l, v, cap, levels=16, glcm_method=m),
              lab, img)
    # the contraction alone, and the 13 features alone
    def glcm_only(l, v):
        q = measure.quantize_per_object(l, v, cap, 16)
        return measure._glcm_matmul_all(l, q, cap, 16,
                                        [(0, 1), (1, 0), (1, 1), (1, -1)])
    timed("texture.parts.quantize",
          lambda l, v: measure.quantize_per_object(l, v, cap, 16), lab, img)
    timed("texture.parts.quantize+glcm_matmul", glcm_only, lab, img)
    # ---- where texture.matmul's time goes: its operations in a trace
    if not args.describe and (not args.only or "trace" in args.only):
        import glob
        import shutil

        from benchmark import stages

        fn = jax.jit(lambda l, v: measure.haralick_features(
            l, v, cap, levels=16, glcm_method="matmul"))
        jax.block_until_ready(fn(lab, img))
        tdir = os.path.join("chiprun_out", "tune_trace")
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
        for _ in range(3):
            jax.block_until_ready(fn(lab, img))
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            tdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        ops: dict = {}
        for plane in stages.device_planes(path):
            for self_ns, mid in stages.self_times(
                    plane.lines.get(stages.OPS_LINE, [])):
                key = (plane.names.get(mid, "")[:48],
                       plane.stats.get(mid, {}).get("tf_op", "")[-90:])
                ops[key] = ops.get(key, 0.0) + self_ns * 1e-6 / 3
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
        emit({"case": "texture.matmul.trace", "ms_a_call_by_operation": [
            {"op": k[0], "tf_op": k[1], "ms": round(v, 3)} for k, v in top]})
        shutil.rmtree(tdir, ignore_errors=True)
    out = os.path.join("chiprun_out", "tune_measure.json")
    os.makedirs("chiprun_out", exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
