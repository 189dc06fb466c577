"""Subprocess body for the cross-process warm-start tests
(``tests/test_aotstore.py``) and ``scripts/ci_warmstart_smoke.py``.

Runs the jterator Cell Painting batch program at one or more capacity
rungs through the perf-instrumented ``cached_batch_fn`` path with the
serialized-executable store armed (the parent sets ``TMX_AOT_STORE=1``
and ``TMX_AOT_STORE_DIR``), then dumps:

- every result leaf to an ``.npz`` (bit-identity evidence),
- the process's compile-plane tallies (cold compiles, store imports,
  exports) and the ``tmx_perf_compiles_total`` counter to a JSON file.

Process A populates the store (cold compiles + exports); process B run
against the same store must show zero compiles and import hits, with
byte-identical features and labels.
"""
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Host-callback programs embed process-local PyCapsule pointers and can
# never serialize; force the portable pure-XLA op path so the compiled
# executable is exportable on the cpu backend (a real TPU never routes
# through the native cpu fallbacks in the first place).
os.environ.setdefault("TMX_NATIVE", "0")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    out_json = sys.argv[1]
    out_npz = sys.argv[2]
    capacities = [int(c) for c in (sys.argv[3] if len(sys.argv) > 3
                                   else "16,64").split(",")]

    import jax.numpy as jnp
    import numpy as np

    from tmlibrary_tpu import aotstore, telemetry
    from tmlibrary_tpu.benchmarks import (
        cell_painting_description,
        synthetic_cell_painting_batch,
    )
    from tmlibrary_tpu.jterator.pipeline import cached_batch_fn

    desc = cell_painting_description()
    data = synthetic_cell_painting_batch(2, size=64, n_cells=4, seed=3)

    import jax

    arrays: dict = {}
    # time-to-first-batch: build + (compile|import) + execute of the
    # first capacity rung, to the first materialized leaf — the
    # cold-vs-warm comparison the store exists to win
    t0 = time.perf_counter()
    time_to_first_batch_s = None
    for cap in capacities:
        fn = cached_batch_fn(desc, cap)
        # the program donates its inputs: fresh device buffers per call
        raw = {k: jnp.asarray(v) for k, v in data.items()}
        shifts = jnp.asarray(np.zeros((2, 2), np.float32))
        result = fn(raw, {}, shifts)
        for i, leaf in enumerate(jax.tree.leaves(result)):
            arrays[f"c{cap}_{i}"] = np.asarray(leaf)
        if time_to_first_batch_s is None:
            time_to_first_batch_s = time.perf_counter() - t0
    np.savez(out_npz, **arrays)

    counts = aotstore.counts_snapshot()
    perf_compiles = sum(
        c.get("value", 0.0)
        for c in telemetry.get_registry().snapshot().get("counters", [])
        if c.get("name") == "tmx_perf_compiles_total"
    )
    with open(out_json, "w") as f:
        json.dump({
            "capacities": capacities,
            "perf_compiles": perf_compiles,
            "cold": int(counts.get("cold", 0)),
            "warm": int(counts.get("warm", 0)),
            "import_hit": int(counts.get("import_hit", 0)),
            "export": int(counts.get("export", 0)),
            "seconds_saved": aotstore.seconds_saved(),
            "store_entries": aotstore.store_stats()["entries"],
            "time_to_first_batch_s": time_to_first_batch_s,
        }, f)
    print("WARMSTART_WORKER_DONE", flush=True)


if __name__ == "__main__":
    main()
