"""CPU rehearsal of ``chip_smoke.py`` and of the rules it rests on.

On the CPU the smoke runs every phase at a tiny size (64x64 fields, one
well of four, interpret-mode kernels) and must still end ``"ok": false``
with a non-zero exit BECAUSE the platform is not ``tpu`` — while every
phase record says passed.  A pass from a CPU is not a chip result.
"""

import ast
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run_smoke(*argv, n_devices=1, cwd=REPO, script=SMOKE, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    # production defaults: telemetry on, executable store on (conftest
    # turns the store off for the suite's compile-count pins)
    for name in ("TMX_AOT_STORE", "TMX_AOT_SPECULATE", "TMX_FAULT_PLAN"):
        env.pop(name, None)
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, str(script), *argv], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=900)
    records = []
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            records.append(json.loads(line))
    return proc, records


def test_cpu_rehearsal_runs_every_phase_and_fails_on_the_platform():
    proc, records = _run_smoke()
    assert proc.returncode != 0, proc.stdout[-2000:]
    phases = {r["phase"]: r for r in records if "phase" in r}
    assert list(phases) == ["start", "workflow", "kernels", "serve"]
    for name in ("workflow", "kernels", "serve"):
        assert phases[name]["passed"], (name, phases[name])
        assert all(phases[name]["checks"].values()), phases[name]["checks"]
    assert phases["start"]["rehearsal"] and phases["start"]["field"] == [64, 64]
    wf = phases["workflow"]
    assert wf["sites"] == 4 and wf["returned"]["platforms"] == ["cpu"]
    assert wf["object_counts"] == wf["reference_counts"]
    assert wf["native_library"] not in (None, "", "absent")
    assert phases["kernels"]["interpret_mode"] is True
    assert len(phases["kernels"]["kernels_compiled"]) == 7
    assert phases["serve"]["jobs_done"] == ["knn-a", "plate-b"]
    # the last line, exactly: failed, and only because of where it ran
    assert json.loads(proc.stdout.splitlines()[-1]) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }


def test_chips_4_runs_only_the_sharded_paths_on_four_virtual_devices():
    proc, records = _run_smoke("--chips", "4", n_devices=4)
    assert proc.returncode != 0, proc.stdout[-2000:]
    phases = {r["phase"]: r for r in records if "phase" in r}
    assert list(phases) == ["start", "spatial", "sharded"]
    for name in ("sharded", "spatial"):
        assert phases[name]["passed"], (name, phases[name])
        assert all(phases[name]["checks"].values()), phases[name]["checks"]
    assert phases["spatial"]["mosaic"] == [128, 128]  # 2x2 whole fields
    assert sorted(
        phases["spatial"]["spatial_shards"]["image_shards_per_device"]
    ) == ["0", "1", "2", "3"]
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["ok"] is False and last["device"]["count"] == 4


def test_chips_4_without_four_devices_fails_at_once():
    proc, records = _run_smoke("--chips", "4", n_devices=1)
    assert proc.returncode != 0
    assert [r.get("phase") for r in records if "phase" in r] == ["start"]
    assert records[-1]["ok"] is False


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo:
    the program is not there, so there is nothing to report."""
    lonely = tmp_path / "chip_smoke.py"
    lonely.write_bytes(SMOKE.read_bytes())
    proc, records = _run_smoke(cwd=tmp_path, script=lonely,
                               extra_env={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not any("ok" in r for r in records)


def test_the_smoke_starts_no_second_process():
    """One process holds the chip, so the smoke starts none: it neither
    imports a process-spawning module nor calls one of os's spawners."""
    tree = ast.parse(SMOKE.read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            imported.add(node.module.split(".")[0])
    assert not imported & {"subprocess", "multiprocessing", "concurrent",
                           "pty", "pexpect"}
    spawners = {"system", "popen", "fork", "forkpty", "posix_spawn",
                "posix_spawnp", "startfile"}
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
                and node.value.id == "os":
            assert node.attr not in spawners
            assert not node.attr.startswith(("exec", "spawn"))


# ------------------------------------------- one cache, placed from outside
@pytest.fixture
def config_updates(monkeypatch):
    """Every ``jax.config.update`` the helper makes, without applying it
    (the suite's own cache settings stay as they are)."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_directory_is_the_environments_when_it_names_one(
        monkeypatch, tmp_path, config_updates):
    from tmlibrary_tpu import aotstore
    from tmlibrary_tpu.utils import enable_compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    monkeypatch.delenv("TMX_AOT_STORE_DIR", raising=False)
    assert enable_compilation_cache() == str(tmp_path / "jaxcache")
    # JAX read the variable itself: no directory is set in code
    assert "jax_compilation_cache_dir" not in dict(config_updates)
    assert dict(config_updates)["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert aotstore.store_dir() == str(tmp_path / "jaxcache" / "aot")


def test_cache_directory_is_fixed_inside_the_checkout_otherwise(
        monkeypatch, config_updates):
    from tmlibrary_tpu import aotstore
    from tmlibrary_tpu.utils import enable_compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("TMX_AOT_STORE_DIR", raising=False)
    path = enable_compilation_cache()
    assert path == str(REPO / ".cache" / "xla")
    assert dict(config_updates)["jax_compilation_cache_dir"] == path
    assert aotstore.store_dir() == str(REPO / ".cache" / "aot")
    # the same path on every call: it is part of the cache's key
    assert enable_compilation_cache() == path
    assert not path.startswith(os.path.expanduser("~/.cache"))


def test_the_retired_cache_knobs_are_gone(monkeypatch, tmp_path,
                                          config_updates):
    """TMX_COMPILE_CACHE_DIR, TM_COMPILE_CACHE_DIR / compile_cache_dir and
    TMX_NO_COMPILE_CACHE were three knobs for one directory."""
    from tmlibrary_tpu.config import LibraryConfig
    from tmlibrary_tpu.utils import enable_compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("TMX_COMPILE_CACHE_DIR", str(tmp_path / "a"))
    monkeypatch.setenv("TM_COMPILE_CACHE_DIR", str(tmp_path / "b"))
    monkeypatch.setenv("TMX_NO_COMPILE_CACHE", "1")
    assert enable_compilation_cache() == str(REPO / ".cache" / "xla")
    assert not hasattr(LibraryConfig(), "compile_cache_dir")
    assert "TMX_PLATFORM" not in (REPO / "tmlibrary_tpu" / "cli.py").read_text()


# ------------------------------------------------- the engine's own resolver
@pytest.mark.parametrize("side,tuned,n_devices,expected", [
    (256, 128, 1, 128),   # the site the sweep measured: its own verdict
    (2160, 128, 1, 1),    # an acquisition-geometry field: one per batch
    (1080, 128, 1, 7),    # the 2x2-binned field
    (2160, None, 1, 1),   # no sweep: the static 32 is scaled the same way
    (64, None, 1, 32),    # never above the batch that was swept
    (2160, 128, 4, 4),    # a mesh: one field per device, no padded copies
    (1080, 128, 4, 8),    # the budget of 7 rounded up to 2 per device
    (256, 128, 4, 128),   # already a multiple of the mesh
    (2160, 128, 0, 8),    # 0 = every device (conftest's 8 virtual ones)
])
def test_auto_batch_size_is_a_pixel_budget_on_device(
        tmp_path, monkeypatch, side, tuned, n_devices, expected):
    from tmlibrary_tpu import tuning
    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.models.store import ExperimentStore
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment("b", well_rows=1, well_cols=1, sites_per_well=(1, 1),
                          channel_names=("DAPI",), site_shape=(side, side))
    step = get_step("jterator")(ExperimentStore.create(tmp_path / "e", exp))
    monkeypatch.setattr(tuning, "tuned_batch_size", lambda: tuned)
    # the CPU keeps its static default
    assert step._auto_batch_size(n_devices) == 32
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert step._auto_batch_size(n_devices) == expected
