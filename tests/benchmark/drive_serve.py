"""Drives one whole rehearsal run of a serve cell with a fault planted in
the program underneath, for ``test_serve_correct.py``: the harness's own
``run.main`` from its first line to its last, at the configuration's
rehearsal size on whatever platform JAX finds.

    python tests/benchmark/drive_serve.py <fault> <run.py's arguments>

``none``: the program as it is.  ``answer_altered``: every kNN answer
leaves ``analytics.ops.knn`` — where the program produces it — with the
nearest neighbour of its first row replaced by that row's farthest.
``half_left_out``: ``analytics.ops.knn`` answers the first half of its
rows and hands the second half the first half's answers.
``rows_misordered``: the store's standardized matrix comes out with its
objects one row out of place, so every answer is worked out in full on
operands the program prepared wrongly."""

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def plant(fault: str) -> None:
    if fault == "none":
        return
    if fault not in ("answer_altered", "half_left_out", "rows_misordered"):
        raise SystemExit(f"unknown fault {fault!r}")
    import numpy as np

    if fault == "rows_misordered":
        from tmlibrary_tpu.analytics.store import FeatureStore

        prepared = FeatureStore.standardized

        def standardized(self, features=None):
            ids, x, names = prepared(self, features)
            return ids, np.roll(x, 1, axis=0), names

        FeatureStore.standardized = standardized
        return

    from tmlibrary_tpu.analytics import ops

    sound = ops.knn

    def knn(x, k, queries=None, tile=None):
        idx, dist = sound(x, k, queries=queries, tile=tile)
        idx, dist = np.array(idx), np.array(dist)
        if fault == "half_left_out":
            half = len(idx) // 2
            idx[half:2 * half], dist[half:2 * half] = idx[:half], dist[:half]
            return idx, dist
        x = np.asarray(x, np.float64)
        idx[0, 0] = int(np.argmax(((x - x[0]) ** 2).sum(1)))
        return idx, dist

    ops.knn = knn


if __name__ == "__main__":
    from benchmark import harness, run

    harness.prepare_environment()     # before JAX is imported
    plant(sys.argv[1])
    sys.exit(run.main(sys.argv[2:]))
