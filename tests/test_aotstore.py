"""Cold-start elimination plane (``aotstore.py`` + perf.py hooks):
store round-trips, fingerprint/corruption loud-fallbacks, LRU pruning,
compile-ahead speculation, and the cross-process warm-start pin —
subprocess A compiles and exports, subprocess B imports with ZERO new
compiles and bit-identical features/labels.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tmlibrary_tpu import aotstore, perf, telemetry
from tmlibrary_tpu.capacity import likely_next_rungs

WORKER = os.path.join(os.path.dirname(__file__), "warmstart_worker.py")


@pytest.fixture
def jax_cache():
    """JAX's persistent compilation cache as these tests need it: off
    (an earlier test of the same worker may have switched it on, and an
    executable it serves is not one a compile produced), or on in a
    directory of the test's own — ``jax_cache(path)``."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}

    def switch(directory=None):
        jax.config.update("jax_enable_compilation_cache", bool(directory))
        if directory:
            jax.config.update("jax_compilation_cache_dir", str(directory))
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()

    switch()
    yield switch
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


@pytest.fixture
def store(tmp_path, monkeypatch, jax_cache):
    """Armed store in a fresh directory + fresh registry/profiles."""
    monkeypatch.setenv("TMX_AOT_STORE", "1")
    monkeypatch.setenv("TMX_AOT_STORE_DIR", str(tmp_path / "aot"))
    telemetry.reset_registry(enabled=True)
    perf.reset_profiles()
    aotstore.reset_counts()
    aotstore.reset_seconds_saved()
    yield str(tmp_path / "aot")
    telemetry.reset_registry()
    perf.reset_profiles()


def _compiled_toy():
    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.arange(8, dtype=jnp.float32)
    return fn.lower(x).compile(), x


def _counter(name: str) -> float:
    return sum(c.get("value", 0.0)
               for c in telemetry.get_registry().snapshot()["counters"]
               if c.get("name") == name)


# ------------------------------------------------------------- round trip
def test_export_import_roundtrip(store):
    compiled, x = _compiled_toy()
    digest = aotstore.export_entry(
        compiled, program="toy", capacity=8,
        signature="sig0", compile_s=0.5)
    assert digest is not None
    rows = aotstore.list_entries(store)
    assert len(rows) == 1 and rows[0]["digest"] == digest
    assert rows[0]["capacity"] == 8 and "strategy" not in rows[0]
    assert not rows[0]["stale"]

    hit = aotstore.import_entry(program="toy", capacity=8,
                                signature="sig0")
    assert hit is not None
    compiled2, meta = hit
    np.testing.assert_array_equal(
        np.asarray(compiled2(x)), np.asarray(compiled(x)))
    assert meta["digest"] == digest
    assert aotstore.counts_snapshot() == {"export": 1.0, "import_hit": 1.0}
    assert aotstore.seconds_saved() == pytest.approx(0.5)


def test_import_misses_on_any_key_component(store):
    compiled, _ = _compiled_toy()
    aotstore.export_entry(compiled, program="toy", capacity=8,
                          signature="sig0")
    for kw in ({"program": "other"}, {"capacity": 16},
               {"signature": "sig1"}):
        probe = {"program": "toy", "capacity": 8,
                 "signature": "sig0", **kw}
        assert aotstore.import_entry(**probe) is None


def test_store_off_is_inert(store, monkeypatch):
    monkeypatch.setenv("TMX_AOT_STORE", "0")
    compiled, _ = _compiled_toy()
    assert aotstore.export_entry(compiled, program="toy",
                                 signature="s") is None
    assert aotstore.import_entry(program="toy", capacity=None,
                                 signature="s") is None
    assert aotstore.list_entries(store) == []


# ----------------------------------------------------- loud fallbacks
def test_fingerprint_mismatch_refuses_loudly(store, caplog):
    compiled, _ = _compiled_toy()
    digest = aotstore.export_entry(compiled, program="toy", capacity=8,
                                   signature="sig0")
    meta_path = os.path.join(store, f"{digest}.json")
    meta = json.loads(open(meta_path).read())
    meta["fingerprint"] = "deadbeefdeadbeef"
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with caplog.at_level("WARNING"):
        assert aotstore.import_entry(program="toy", capacity=8,
                                     signature="sig0") is None
    assert any("fingerprint" in r.message for r in caplog.records)
    assert aotstore.counts_snapshot().get("import_hit", 0) == 0


def test_corrupt_artifact_falls_back_loudly_and_evicts(store, caplog):
    compiled, _ = _compiled_toy()
    digest = aotstore.export_entry(compiled, program="toy", capacity=8,
                                   signature="sig0")
    with open(os.path.join(store, f"{digest}.bin"), "wb") as f:
        f.write(b"not a serialized executable")
    with caplog.at_level("WARNING"):
        assert aotstore.import_entry(program="toy", capacity=8,
                                     signature="sig0") is None
    assert any("corrupt" in r.message.lower() for r in caplog.records)
    # the bad entry is evicted so every later lookup is a clean miss,
    # not a repeated deserialize failure
    assert aotstore.list_entries(store) == []


def test_an_edit_to_the_package_changes_the_fingerprint(monkeypatch):
    """The program name digests the pipeline description, not ``ops/``:
    without the package's own digest in the fingerprint, a store written
    before an edit to an op hands the edited code its old executable."""
    before = aotstore.backend_fingerprint()
    assert aotstore.fingerprint_info()["package"] == aotstore.package_digest()
    monkeypatch.setattr(aotstore, "_PACKAGE_DIGEST", "0" * 16)
    assert aotstore.backend_fingerprint() != before


def test_stale_fingerprint_never_loads():
    # the fingerprint is INSIDE the entry digest: a store written by a
    # different jax/backend resolves to different file names, so a
    # stale artifact can never even be found
    a = aotstore.entry_digest("p", 8, "sig", fingerprint="aaaa")
    b = aotstore.entry_digest("p", 8, "sig", fingerprint="bbbb")
    assert a != b


# ------------------------------------------------------------- pruning
def test_prune_lru_cap_and_orphans(store):
    compiled, _ = _compiled_toy()
    digests = []
    for i in range(4):
        digests.append(aotstore.export_entry(
            compiled, program=f"p{i}", capacity=8,
            signature="s"))
    # orphan payload with no meta sidecar
    with open(os.path.join(store, "feedface" * 5 + ".bin"), "wb") as f:
        f.write(b"x" * 64)
    per_entry = os.path.getsize(os.path.join(store, f"{digests[0]}.bin"))
    result = aotstore.prune(store, max_bytes=2 * per_entry + 1)
    assert result["kept"] == 2
    kept = {m["digest"] for m in aotstore.list_entries(store)}
    # LRU: the two most recent exports survive
    assert kept == set(digests[2:])
    assert not os.path.exists(os.path.join(store, "feedface" * 5 + ".bin"))


@pytest.mark.parametrize("rungs_that_fit,imported", [
    (4, 4),   # the cap holds the ladder: the second walk compiles nothing
    (3, 0),   # one rung short: NOT 3 hits — each export evicts the least
              # recently used rung, which is the one asked for next
])
def test_second_walk_of_a_ladder_hits_only_if_the_cap_holds_all_of_it(
        store, monkeypatch, rungs_that_fit, imported):
    """What happened on the chip in PR 21 at scaled-down sizes: 8 rungs of
    166 MB against a 1 GiB cap, and a second process that imported
    nothing.  ``DEFAULT_MAX_BYTES`` is sized from this."""
    compiled, _ = _compiled_toy()
    ladder = (8, 16, 32, 64)
    probe = aotstore.export_entry(compiled, program="size", signature="s",
                                  directory=store + "_probe")
    per_entry = os.path.getsize(os.path.join(store + "_probe", f"{probe}.bin"))
    monkeypatch.setenv("TMX_AOT_STORE_MAX_BYTES",
                       str(rungs_that_fit * per_entry + 1))

    def walk():
        hits = 0
        for cap in ladder:   # the engine's order: import, else compile + export
            key = dict(program="ladder", capacity=cap,
                       signature="s")
            if aotstore.import_entry(**key) is not None:
                hits += 1
            else:
                aotstore.export_entry(compiled, **key)
        return hits

    assert walk() == 0
    assert walk() == imported
    total = sum(m["size_bytes"] for m in aotstore.list_entries(store))
    assert total <= rungs_that_fit * per_entry + 1


def test_default_cap_holds_a_real_fields_ladder():
    # 8 rungs x 166 MB, the largest executable measured (PERF.md, PR 21)
    assert aotstore.DEFAULT_MAX_BYTES >= 2 * 8 * 166_000_000


def _toy_in_a_fresh_process(x):
    """What a new process does with the toy program: nothing in memory,
    the store and JAX's cache as the disk has them."""
    perf.reset_profiles()
    jax.clear_caches()

    def stored_toy(v):
        return v * 3.0 - 1.0

    return np.asarray(perf.instrument_batch_fn(
        jax.jit(stored_toy), program="stored_toy", capacity=8)(x))


def _in_jax_cache(directory) -> int:
    return sum("stored_toy" in name and name.endswith("-cache")
               for name in os.listdir(directory))


def test_an_executable_the_store_takes_is_not_written_to_jaxs_cache_too(
        store, tmp_path, jax_cache, monkeypatch):
    """A real field's executable is 91 MB: a second copy in a capped JAX
    cache (192 MiB on the chip machine) evicted every small program a
    second process would have hit (PERF.md, PR 21)."""
    jax_cache(tmp_path / "jaxcache")
    x = jnp.arange(8, dtype=jnp.float32)
    want = _toy_in_a_fresh_process(x)
    assert aotstore.counts_snapshot()["export"] == 1.0
    assert _in_jax_cache(tmp_path / "jaxcache") == 0
    # the next process gets it from the store
    np.testing.assert_array_equal(_toy_in_a_fresh_process(x), want)
    assert aotstore.counts_snapshot()["import_hit"] == 1.0
    # with the store off the cache takes it, as for any program
    monkeypatch.setenv("TMX_AOT_STORE", "0")
    _toy_in_a_fresh_process(x)
    assert _in_jax_cache(tmp_path / "jaxcache") == 1


def test_an_executable_the_persistent_cache_served_stays_out_of_the_store(
        store, tmp_path, jax_cache, monkeypatch):
    """With jaxlib 0.9.0 an XLA:CPU executable that was itself loaded from
    JAX's persistent cache serializes into a payload that loads and then
    fails to run ("Function ... not found").  The store takes only what
    a compile produced; the cache keeps serving the rest."""
    jax_cache(tmp_path / "jaxcache")
    x = jnp.arange(8, dtype=jnp.float32)
    monkeypatch.setenv("TMX_AOT_STORE", "0")
    want = _toy_in_a_fresh_process(x)      # store off: the cache takes it
    assert _in_jax_cache(tmp_path / "jaxcache") == 1
    monkeypatch.setenv("TMX_AOT_STORE", "1")
    np.testing.assert_array_equal(_toy_in_a_fresh_process(x), want)
    assert "export" not in aotstore.counts_snapshot()
    assert not os.path.isdir(store) or os.listdir(store) == []


# ----------------------------------------------- speculation unit tests
def test_likely_next_rungs():
    ladder = (8, 16, 32, 64)
    assert likely_next_rungs(8, ladder) == (16,)
    assert likely_next_rungs(8, ladder, count=2) == (16, 32)
    assert likely_next_rungs(64, ladder) == ()
    # an observed peak above the next rung jumps speculation forward
    assert likely_next_rungs(8, ladder, observed=20) == (32,)
    assert likely_next_rungs(8, ladder, observed=3) == (16,)


def test_speculate_compile_then_warm_hit(store, monkeypatch):
    monkeypatch.setenv("TMX_AOT_SPECULATE", "1")
    calls = []

    def raw_fn(x):
        calls.append(1)
        return x + 1.0

    wrapped = perf.instrument_batch_fn(
        jax.jit(raw_fn), program="spec_toy", capacity=8)
    x = jnp.arange(4, dtype=jnp.float32)
    abs_args, abs_kwargs = perf.abstract_args((x,), {})
    # skeleton args produce the same signature as real arrays → the
    # speculative compile is adopted for the real call
    assert perf.speculate_compile(wrapped, abs_args, abs_kwargs) == "compiled"
    assert _counter("tmx_perf_compiles_total") == 0  # not a critical-path compile
    assert aotstore.counts_snapshot().get("export") == 1

    out = wrapped(x)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.arange(1, 5, dtype=np.float32))
    assert _counter("tmx_compile_warm_total") == 1
    assert _counter("tmx_compile_cold_total") == 0
    assert _counter("tmx_perf_compiles_total") == 0
    # second speculation on a known signature is a no-op
    assert perf.speculate_compile(wrapped, abs_args, abs_kwargs) == "known"


def test_instrumented_call_imports_across_registry_reset(store):
    """The in-process proxy for a daemon restart: same store, fresh
    registry/profiles — the call imports instead of compiling."""
    x = jnp.arange(4, dtype=jnp.float32)
    wrapped = perf.instrument_batch_fn(
        jax.jit(lambda v: v * 3.0), program="restart_toy", capacity=8)
    first = np.asarray(wrapped(x))
    assert _counter("tmx_compile_cold_total") == 1
    assert _counter("tmx_compile_export_total") == 1

    # "restart": drop every in-process cache, keep the store
    # (reset_profiles also clears the _RUNTIME executable cache)
    telemetry.reset_registry(enabled=True)
    perf.reset_profiles()
    aotstore.reset_counts()
    wrapped2 = perf.instrument_batch_fn(
        jax.jit(lambda v: v * 3.0), program="restart_toy", capacity=8)
    second = np.asarray(wrapped2(x))
    np.testing.assert_array_equal(first, second)
    assert _counter("tmx_compile_import_hit_total") == 1
    assert _counter("tmx_perf_compiles_total") == 0
    assert _counter("tmx_compile_cold_total") == 0


# ------------------------------------------- cross-process warm start
def test_cross_process_warmstart_bit_identical(store, tmp_path):
    """Subprocess A cold-compiles both bucket rungs (a mid-ladder rung
    and the single-bucket ceiling) and exports; subprocess B against the
    same store imports both with ZERO new compiles and byte-identical
    features/labels."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "TMX_AOT_STORE": "1",
        "TMX_AOT_STORE_DIR": store,
        "TMX_AOT_SPECULATE": "0",
        # pure-XLA ops: host-callback (pure_callback) programs embed
        # process-local pointers and refuse to serialize on cpu
        "TMX_NATIVE": "0",
    })

    def run(tag):
        out_json = tmp_path / f"{tag}.json"
        out_npz = tmp_path / f"{tag}.npz"
        proc = subprocess.run(
            [sys.executable, WORKER, str(out_json), str(out_npz), "16,64"],
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(out_json.read_text()), np.load(out_npz)

    a, arrays_a = run("a")
    assert a["cold"] == 2 and a["export"] == 2 and a["import_hit"] == 0
    assert a["perf_compiles"] == 2
    assert a["store_entries"] == 2

    b, arrays_b = run("b")
    # THE pin: a fresh process against a warm store never compiles
    assert b["perf_compiles"] == 0
    assert b["cold"] == 0
    assert b["import_hit"] == 2
    assert b["seconds_saved"] > 0

    assert set(arrays_a.files) == set(arrays_b.files) and arrays_a.files
    for name in arrays_a.files:
        np.testing.assert_array_equal(arrays_a[name], arrays_b[name])
