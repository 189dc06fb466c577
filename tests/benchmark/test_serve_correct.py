"""How ``correct`` is decided in ``cp3-serve.query``, shown to fail: the
control (the reference in the program's place, one precision down) reads
over the limit at a size a test can hold, a float32 evaluation reads under
it, an altered answer is caught by the comparison — and a whole run of the
harness over a program with the fault planted where the answer is
produced ends with ``correct`` false, where the sound program's ends true,
and the reference makes its matrix from the raw shards itself.
The chip readings the limit stands between are in PERF.md section 2."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402

PENDING = "benchmark/pending/cp3-serve.entries.json"

reference = harness.load_module(str(
    REPO / "benchmark" / "configs" / "cp3-serve.reference.py"))


def matrix(seed: int, rows: int = 600) -> np.ndarray:
    """A standardized feature matrix with a heavy-tailed column, as
    intensities are: float64, three features."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, 3))
    x[:, 1] = np.exp(x[:, 1])
    return (x - x.mean(0)) / x.std(0)


def float32_answer(x: np.ndarray, k: int) -> dict:
    """What the configuration states: the expansion with its product in
    float32 (the control's own code, given that product)."""
    x32 = x.astype(np.float32)
    return reference.control_answer(x, k, cross=x32 @ x32.T)


@pytest.mark.parametrize("seed", [3000000901, 3000000902, 3000000903])
def test_control_reads_over_the_limit_and_float32_under_it(seed):
    x = matrix(seed)
    control = reference.check_answer(reference.control_answer(x, 10), x, 10)
    stated = reference.check_answer(float32_answer(x, 10), x, 10)
    assert not control["equal"] and stated["equal"]
    assert control["worst_distance_units"] > 3.0 * reference.LIMIT_UNITS
    assert stated["worst_distance_units"] < reference.LIMIT_UNITS / 3.0
    assert stated["worst_index_units"] < reference.LIMIT_UNITS / 3.0


def test_control_is_three_bfloat16_passes():
    x = np.array([[1.0 + 2.0 ** -10, 3.0], [0.1, -7.0]], np.float32)
    hi, lo = reference._bf16_parts(x)
    assert np.array_equal(hi[0], [1.0, 3.0])           # 8 bits kept
    assert lo[0, 0] == np.float32(2.0 ** -10) and lo[0, 1] == 0.0
    assert abs(hi[1, 0] + lo[1, 0] - np.float32(0.1)) < 2.0 ** -19


@pytest.mark.parametrize("fault", ["neighbour", "distance"])
def test_an_altered_answer_is_not_equal(fault):
    x = matrix(3000000904, rows=200)
    answer = float32_answer(x, 5)
    assert reference.check_answer(answer, x, 5)["equal"]
    if fault == "neighbour":
        answer["nn0"] = answer["nn0"].copy()
        answer["nn0"][0] = int(np.argmax(((x - x[0]) ** 2).sum(1)))
    else:
        answer["nnd4"] = answer["nnd4"] * np.float32(1.001)
    verdict = reference.check_answer(answer, x, 5)
    assert not verdict["equal"]
    assert max(verdict["worst_distance_units"],
               verdict["worst_index_units"]) > 10.0


def test_brute_force_is_nearest_first_with_ties_by_row_order():
    x = matrix(3000000905, rows=50)
    x[7] = x[3]                       # a tie: rows 3 and 7 coincide
    d2, idx = reference.brute_knn(x, 4)
    full = np.argsort(d2, axis=1, kind="stable")[:, :4]
    assert np.array_equal(np.take_along_axis(d2, idx, 1),
                          np.take_along_axis(d2, full, 1))
    assert idx[3, 0] == 7 and idx[7, 0] == 3


def raw_table(seed: int, rows: int) -> pd.DataFrame:
    """Raw intensities as a shard holds them: large offsets over small
    spreads, float64, with a NaN and an infinite cell."""
    rng = np.random.default_rng(seed)
    table = pd.DataFrame({
        "site_index": 0, "plate": "plate00", "well_row": 0, "well_col": 0,
        "label": np.arange(1, rows + 1),
        "Intensity_mean_DAPI": rng.normal(3000.0, 40.0, rows),
        "Intensity_max_DAPI": np.exp(rng.normal(8.0, 0.3, rows)),
        "Intensity_sum_DAPI": rng.normal(2.0e6, 1.0e5, rows)})
    table.loc[3, "Intensity_mean_DAPI"] = np.nan
    table.loc[5, "Intensity_max_DAPI"] = np.inf
    return table


def test_the_reference_reads_the_shards_in_name_order(tmp_path):
    """``feature_matrix`` concatenates ``features/<objects>/*.parquet`` by
    name, takes the query's columns in the query's order and z-scores the
    float32 values it finds there in float64."""
    shards = tmp_path / "features" / "nuclei"
    shards.mkdir(parents=True)
    first, second = raw_table(3000000907, 40), raw_table(3000000908, 30)
    second.to_parquet(shards / "batch_001.parquet", index=False)
    first.to_parquet(shards / "batch_000.parquet", index=False)
    names = ["Intensity_sum_DAPI", "Intensity_mean_DAPI"]
    x = reference.feature_matrix(tmp_path, "nuclei", names)
    raw = pd.concat([first, second], ignore_index=True)[names]
    assert x.dtype == np.float64 and x.shape == (70, 2)
    assert np.array_equal(x, reference.standardize(
        raw.to_numpy(np.float32)))
    with pytest.raises(FileNotFoundError):
        reference.feature_matrix(tmp_path, "cells", names)


def test_the_reference_standardizes_as_the_store_states(tmp_path):
    """The reference's z-scores agree with the store's float32 ones to
    float32 resolution (the same imputation, mean and population
    deviation), and a store whose mean, scale, columns or object order
    is wrong lies far off them."""
    from tmlibrary_tpu.analytics.store import FeatureStore
    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.models.store import ExperimentStore

    exp = grid_experiment(name="serve-reference", well_rows=1, well_cols=1,
                          sites_per_well=(1, 1), site_shape=(8, 8))
    store = ExperimentStore.create(tmp_path / "exp", exp)
    store.append_features("nuclei", raw_table(3000000909, 300), "batch_000")
    store.append_features("nuclei", raw_table(3000000910, 200), "batch_001")
    names = ["Intensity_max_DAPI", "Intensity_mean_DAPI",
             "Intensity_sum_DAPI"]
    _, program, _ = FeatureStore.ensure(store, "nuclei").standardized(names)
    x = reference.feature_matrix(store.root, "nuclei", names)
    assert np.isfinite(x).all()
    assert abs(x[3, 1]) < 1e-12              # imputed at the finite mean
    np.testing.assert_allclose(x.mean(0), 0.0, atol=1e-12)
    np.testing.assert_allclose(x.std(0), 1.0, rtol=1e-12)
    gap = np.abs(program - x).max()
    assert gap < 1e-5
    for wrong in (program + 1e-3, program * 1.001, program[:, ::-1],
                  np.roll(program, 1, axis=0)):
        assert np.abs(wrong - x).max() > 100.0 * gap


@pytest.mark.parametrize("fault, correct", [("none", True),
                                            ("answer_altered", False),
                                            ("half_left_out", False),
                                            ("rows_misordered", False)])
def test_a_whole_run_is_correct_only_without_the_fault(fault, correct,
                                                       tmp_path):
    """``run.main`` from argument parsing to the result line, in a
    process of its own (it takes the process's standard output)."""
    # one CPU device, few threads and a low priority: the run shares the
    # machine with the other workers of the test run and must not starve
    # their collectives
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               OMP_NUM_THREADS="2", OPENBLAS_NUM_THREADS="2")
    done = subprocess.run(
        ["nice", "-n", "10", sys.executable,
         str(Path(__file__).with_name("drive_serve.py")),
         fault, "--workload", "cp3-serve.query", "--seed", "3000000906",
         "--seconds", "2", "--trace", "0", "--entries", PENDING],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(line) for line in done.stdout.splitlines()
             if line.startswith("{")]
    assert done.returncode == 1, done.stderr[-2000:]   # a rehearsal
    result = lines[-1]["would_have_printed"]
    assert result["correct"] is correct, lines[-2]
    assert list(result)[-1] == "numbers_compared"
    compared = result["numbers_compared"]
    assert compared["jobs_not_done"] == [0, 0] and result["failed"] == 0
    over = [name for name, (number, limit) in compared.items()
            if number is None or number > limit]
    # the altered slot names a far row, and its distance is no longer that
    # row's: both numbers read over the limit
    assert over == ([] if correct else ["knn_distance_units",
                                        "knn_index_units"])
    # the last lines of standard error say the same numbers
    tail = done.stderr.splitlines()[-9:]
    for name, (number, limit) in compared.items():
        assert f"compared {name}: {number} limit {limit}" in tail
    assert set(result["metrics"]) == {"setup_s"} | {
        m["name"] for m in json.loads(
            (REPO / PENDING).read_text())["end_to_end"]}
