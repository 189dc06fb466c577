"""The performance-attribution layer (tmlibrary_tpu.perf): XLA cost-model
reads hardened against raising backends, the AOT compile/cost wrapper on
cached batch fns (one compile, recompile detection, bit-identical
execution), roofline verdicts, bench-record staleness gauges, and the
re-capture queue handoff."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tmlibrary_tpu import perf, telemetry, tuning


@pytest.fixture(autouse=True)
def _fresh_perf():
    telemetry.reset_registry(enabled=True)
    perf.reset_profiles()
    yield
    perf.reset_profiles()
    telemetry.reset_registry()


# ----------------------------------------------------------- cost model
def test_program_cost_reports_flops_and_bytes_on_cpu():
    fn = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64), jnp.float32)
    cost = perf.program_cost(fn, x)
    assert cost.flops and cost.flops > 0
    assert cost.bytes and cost.bytes > 0
    ai = cost.arithmetic_intensity
    assert ai == pytest.approx(cost.flops / cost.bytes)
    assert cost.bound_by() in ("memory", "compute")
    # tuple compat shim used by bench.py
    flops, nbytes = perf.cost_flops(fn, x)
    assert flops == cost.flops and nbytes == cost.bytes


def test_cost_analysis_raising_degrades_to_none():
    """Satellite: a backend/JAX version whose cost_analysis raises (or
    whose lowering fails entirely) must yield None fields, not crash."""

    class _RaisingCompiled:
        def cost_analysis(self):
            raise RuntimeError("backend does not implement cost analysis")

    assert perf.cost_from_compiled(_RaisingCompiled()) == perf.ProgramCost()

    class _Lowered:
        def compile(self):
            return _RaisingCompiled()

    class _Jitted:
        def lower(self, *a, **k):
            return _Lowered()

    cost = perf.program_cost(_Jitted(), 1)
    assert cost.flops is None and cost.bytes is None
    assert cost.arithmetic_intensity is None and cost.bound_by() is None

    class _NoLower:
        def lower(self, *a, **k):
            raise TypeError("no AOT path")

    assert perf.cost_flops(_NoLower(), 1) == (None, None)


def test_cost_analysis_dict_and_empty_shapes():
    class _DictCompiled:
        def cost_analysis(self):
            return {"flops": 12.0, "bytes accessed": 4.0}

    cost = perf.cost_from_compiled(_DictCompiled())
    assert (cost.flops, cost.bytes) == (12.0, 4.0)

    class _EmptyCompiled:
        def cost_analysis(self):
            return []

    assert perf.cost_from_compiled(_EmptyCompiled()) == perf.ProgramCost()

    class _ZeroCompiled:
        def cost_analysis(self):
            return {"flops": 0.0, "bytes accessed": 0.0}

    assert perf.cost_from_compiled(_ZeroCompiled()) == perf.ProgramCost()


def test_flops_fields_carries_roofline_verdict():
    out = perf.flops_fields(1e9, 100, 0.5, "TPU v5 lite", nbytes=1e8)
    assert out["achieved_tflops_per_sec"] == pytest.approx(0.002)
    assert out["mfu_vs_v5e_bf16_peak"] is not None
    assert out["arithmetic_intensity"] == pytest.approx(10.0)
    assert out["bound_by"] == "memory"  # 10 flops/B << v5e ridge ~240
    # off-device runs never claim device-fraction numbers
    assert perf.attached_device_kind() is None  # the suite runs on CPU
    cpu = perf.flops_fields(1e9, 100, 0.5, None, nbytes=1e8)
    assert cpu["mfu_vs_v5e_bf16_peak"] is None
    assert cpu["hbm_frac_vs_v5e_peak"] is None
    assert cpu["bound_by"] == "memory"
    # a chip without published peaks is an error, never a v5e assumed
    with pytest.raises(KeyError, match="no published peaks"):
        perf.flops_fields(1e9, 100, 0.5, "TPU v9", nbytes=1e8)


def test_device_peaks_keyed_by_device_kind():
    assert perf.device_peaks("TPU v5 lite") == (197e12, 819e9)
    for unknown in ("tpu", "cpu", "TPU v4"):
        with pytest.raises(KeyError, match="no published peaks"):
            perf.device_peaks(unknown)
    assert perf.ridge_point() == pytest.approx(197e12 / 819e9)


# ---------------------------------------- AOT executable failure reporting
class _FailingCompiled:
    """Stands in for an AOT executable that fails at call time."""

    def __init__(self, consume):
        self.consume = consume

    def cost_analysis(self):
        return {}

    def __call__(self, x):
        if self.consume:
            x.delete()  # what a donating executable does before failing
        raise RuntimeError("executable refused these inputs")


class _Lowerable:
    def __init__(self, consume):
        self.consume = consume
        self.jit_calls = 0

    def lower(self, *a, **k):
        consume = self.consume

        class _Lowered:
            def compile(self):
                return _FailingCompiled(consume)

        return _Lowered()

    def __call__(self, x):
        self.jit_calls += 1
        return x + 1.0


def test_failed_aot_executable_is_reported_and_rerouted_when_inputs_live(
        caplog):
    fn = _Lowerable(consume=False)
    wrapped = perf.instrument_batch_fn(fn, program="prog@aotfail")
    with caplog.at_level("WARNING", logger="tmlibrary_tpu.perf"):
        out = wrapped(jnp.ones(3))
    np.testing.assert_array_equal(np.asarray(out), 2.0)
    assert any("executable refused these inputs" in r.getMessage()
               for r in caplog.records)
    # dropped for good: the next call goes straight to jit, no new warning
    caplog.clear()
    wrapped(jnp.ones(3))
    assert fn.jit_calls == 2 and not caplog.records


def test_failed_aot_executable_never_retries_on_donated_buffers(caplog):
    fn = _Lowerable(consume=True)
    wrapped = perf.instrument_batch_fn(fn, program="prog@aotdonated")
    with caplog.at_level("WARNING", logger="tmlibrary_tpu.perf"):
        with pytest.raises(RuntimeError, match="refused these inputs"):
            wrapped(jnp.ones(3))
    assert fn.jit_calls == 0  # no second run on deleted inputs
    assert any("prog@aotdonated" in r.getMessage() for r in caplog.records)


# ------------------------------------------------- instrumented batch fn
def test_instrument_batch_fn_counts_compiles_and_recompiles():
    fn = jax.jit(lambda x: (x * 2.0).sum(axis=-1))
    wrapped = perf.instrument_batch_fn(
        fn, program="prog@test", capacity=16)

    a = jnp.ones((4, 8), jnp.float32)
    out1 = wrapped(a)
    out2 = wrapped(a)  # same signature: no new compile
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(fn(a)))
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    b = jnp.ones((2, 8), jnp.float32)  # new signature: recompile
    np.testing.assert_array_equal(np.asarray(wrapped(b)),
                                  np.asarray(fn(b)))

    profiles = perf.perf_profiles()
    assert len(profiles) == 1
    entry = profiles[0]
    assert entry["program"] == "prog@test"
    assert entry["capacity"] == 16 and "strategy" not in entry
    assert entry["compiles"] == 2
    assert entry["recompiles"] == 1
    assert entry["compile_seconds_total"] > 0
    assert entry["flops"] and entry["bytes"]
    assert entry["bound_by"] in ("memory", "compute")

    snap = telemetry.get_registry().snapshot()
    counters = {(c["name"], c["labels"].get("capacity")): c["value"]
                for c in snap["counters"]}
    assert counters[("tmx_perf_compiles_total", "16")] == 2.0
    assert counters[("tmx_perf_recompiles_total", "16")] == 1.0
    hist = [h for h in snap["histograms"]
            if h["name"] == "tmx_perf_compile_seconds"]
    assert hist and hist[0]["count"] == 2
    gauges = {g["name"] for g in snap["gauges"]}
    assert "tmx_perf_program_flops" in gauges
    assert "tmx_perf_program_arithmetic_intensity" in gauges


def test_instrument_batch_fn_builds_a_program_once_for_many_threads():
    """Four persist workers re-launching fields at one rung ask for the
    same unseen signature at once: one of them compiles (or imports), the
    others wait and take its executable."""
    import threading
    import time

    jitted = jax.jit(lambda x: x + 1.0)
    lowered = []

    class Slow:
        def __call__(self, x):
            return jitted(x)

        def lower(self, x):
            lowered.append(threading.current_thread().name)
            time.sleep(0.2)  # a compile takes long enough to collide
            return jitted.lower(x)

    wrapped = perf.instrument_batch_fn(Slow(), program="prog@pool",
                                       capacity=512)
    x = jnp.arange(4.0)
    outs = [None] * 4
    gate = threading.Barrier(4)

    def call(i):
        gate.wait(timeout=5)
        outs[i] = np.asarray(wrapped(x))

    threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(lowered) == 1
    for out in outs:
        np.testing.assert_array_equal(out, np.asarray(x) + 1.0)
    assert perf.perf_profiles()[0]["compiles"] == 1


def test_instrument_batch_fn_zero_cost_when_disabled():
    telemetry.reset_registry(enabled=False)
    fn = jax.jit(lambda x: x + 1.0)
    wrapped = perf.instrument_batch_fn(fn, program="prog@off")
    x = jnp.arange(4.0)
    np.testing.assert_array_equal(np.asarray(wrapped(x)),
                                  np.asarray(fn(x)))
    assert perf.perf_profiles() == []
    assert telemetry.get_registry().snapshot() == {
        "counters": [], "gauges": [], "histograms": []}


def test_instrument_batch_fn_survives_unloverable_fn():
    """A fn without an AOT path still executes through the wrapper and
    still counts its compile events (untimed cost stays None)."""
    calls = []

    def plain(x):
        calls.append(1)
        return x * 3

    wrapped = perf.instrument_batch_fn(plain, program="prog@plain")
    assert wrapped(2) == 6 and wrapped(3) == 9
    assert len(calls) == 2
    entry = perf.perf_profiles()[0]
    assert entry["compiles"] == 1  # one signature seen
    assert entry["flops"] is None and entry["bound_by"] is None


def test_cached_batch_fn_returns_raw_fn_when_disabled():
    from tmlibrary_tpu.benchmarks import smooth_threshold_description
    from tmlibrary_tpu.jterator.pipeline import cached_batch_fn

    desc = smooth_threshold_description()
    telemetry.reset_registry(enabled=False)
    raw = cached_batch_fn(desc, 8)
    assert not hasattr(raw, "perf_key")
    telemetry.reset_registry(enabled=True)
    wrapped = cached_batch_fn(desc, 8)
    assert getattr(wrapped, "perf_key", None) is not None
    assert wrapped.__wrapped__ is raw  # same cached program underneath
    # identity contract: repeated calls share ONE wrapper object
    assert cached_batch_fn(desc, 8) is wrapped


# ------------------------------------------------------ history plumbing
def test_append_and_load_bench_history(tmp_path, monkeypatch):
    path = tmp_path / "BENCH_HISTORY.jsonl"
    monkeypatch.setenv("BENCH_HISTORY", str(path))
    assert tuning.bench_history_path() == str(path)
    tuning.append_bench_history({"metric": "m", "value": 1.0, "config": "3"})
    tuning.append_bench_history({"metric": "m", "value": 2.0, "config": "3"})
    path.open("a").write("{corrupt\n")  # interrupted append
    hist = tuning.load_bench_history()
    assert [h["value"] for h in hist] == [1.0, 2.0]
    assert all(h["recorded_at_unix"] > 0 for h in hist)
    assert all("recorded_at" in h for h in hist)


def test_recapture_queue_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "RECAPTURE.json"
    monkeypatch.setenv("WATCH_RECAPTURE", str(path))
    assert perf.load_recapture() == []
    perf.write_recapture(["bench:3", "sweep:3"], reason="test")
    perf.write_recapture(["bench:3", "bench:4"])  # merge + dedupe
    assert perf.load_recapture() == ["bench:3", "sweep:3", "bench:4"]
    assert json.loads(path.read_text())["reason"] == ""
