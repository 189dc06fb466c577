"""The one span buffer (``telemetry.span`` / ``span_scope`` /
``drain_spans``): spans close on any thread and reach the ledger through
one lock-guarded process buffer that only the engine thread drains.

- parents come from the calling thread's own stack, ``step``/``batch``
  from the ambient scope the engine or the executor set on that thread;
- a drain is in ``t0`` order, a parent before its children;
- disabled telemetry appends nothing and reads no clock; a fatal injected
  fault leaves no event;
- JAX's compile-path events become child spans of whatever span the
  compiling thread is in (``jit_compile`` on the first call, none on the
  second).
"""

import sys
import threading
import time

import pytest

from tmlibrary_tpu import telemetry, traceexport
from tmlibrary_tpu.errors import FaultInjected


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    telemetry.reset_registry(enabled=True)
    telemetry.drain_spans()
    yield
    telemetry.drain_spans()
    telemetry.reset_registry()


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r["span"], []).append(r)
    return out


def test_four_threads_drain_in_t0_order_with_parent_step_batch():
    """Each worker opens ``outer`` > ``inner`` under its own ambient
    batch; the drained records carry that thread's parent and context,
    never a neighbour's, and come out ordered by ``t0``."""
    barrier = threading.Barrier(4)

    def work(batch):
        barrier.wait(timeout=10)
        with telemetry.span_scope(step="jterator", batch=batch):
            with telemetry.span("outer", capacity=8 * (batch + 1)):
                time.sleep(0.002 * (batch + 1))
                with telemetry.span("inner", bytes=100 + batch):
                    time.sleep(0.001)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)

    records = telemetry.drain_spans()
    assert len(records) == 8
    assert [r["t0"] for r in records] == sorted(r["t0"] for r in records)
    assert telemetry.drain_spans() == []          # a drain empties it
    spans = _by_name(records)
    assert sorted(r["batch"] for r in spans["outer"]) == [0, 1, 2, 3]
    for outer in spans["outer"]:
        assert "parent" not in outer              # top of its thread
        assert outer["step"] == "jterator"
        assert outer["capacity"] == 8 * (outer["batch"] + 1)
    for inner in spans["inner"]:
        assert inner["parent"] == "outer"
        assert inner["step"] == "jterator"
        assert inner["bytes"] == 100 + inner["batch"]
        (outer,) = [o for o in spans["outer"]
                    if o["batch"] == inner["batch"]]
        # the child lies inside its own parent's interval
        assert outer["t0"] <= inner["t0"] + 1e-3
        assert inner["t0"] + inner["elapsed"] \
            <= outer["t0"] + outer["elapsed"] + 1e-3
        # and a parent precedes its child in the drain
        assert records.index(outer) < records.index(inner)


def test_scope_is_per_thread_and_restored():
    with telemetry.span_scope(step="corilla", batch=2):
        with telemetry.span_scope(batch=3):
            with telemetry.span("a"):
                pass
        with telemetry.span("b"):
            pass

        # a thread that was given no scope inherits none
        def other():
            with telemetry.span("c"):
                pass
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    with telemetry.span("d"):
        pass
    spans = {r["span"]: r for r in telemetry.drain_spans()}
    assert (spans["a"]["step"], spans["a"]["batch"]) == ("corilla", 3)
    assert (spans["b"]["step"], spans["b"]["batch"]) == ("corilla", 2)
    assert "step" not in spans["c"] and "batch" not in spans["c"]
    assert "step" not in spans["d"]


def test_attrs_set_inside_the_block_are_recorded():
    with telemetry.span("encode", bytes=10) as attrs:
        attrs["tiles"] = 7
    (record,) = telemetry.drain_spans()
    assert (record["bytes"], record["tiles"]) == (10, 7)


def test_emit_bypasses_the_buffer_but_parents_what_is_inside():
    """``emit=`` is for callers on the appending thread (run, step, the
    serve daemon): the record goes to the callback, not the buffer, and
    the span still is the parent of what opens inside it."""
    emitted = []
    with telemetry.span("step", emit=lambda **kw: emitted.append(kw)):
        with telemetry.span("dispatch"):
            pass
    (buffered,) = telemetry.drain_spans()
    assert buffered["span"] == "dispatch" and buffered["parent"] == "step"
    assert [e["span"] for e in emitted] == ["step"]


def test_disabled_telemetry_appends_nothing_and_reads_no_clock(monkeypatch):
    telemetry.set_enabled(False)

    def clock(*a):
        raise AssertionError("a clock was read with telemetry disabled")

    monkeypatch.setattr(telemetry.time, "time", clock)
    monkeypatch.setattr(telemetry.time, "perf_counter", clock)
    with telemetry.span_scope(step="s", batch=0):
        with telemetry.span("work", bytes=1) as attrs:
            attrs["tiles"] = 2          # still a dict the caller may fill
    monkeypatch.undo()
    assert telemetry.drain_spans() == []
    assert telemetry._span_stack() == []


def test_fatal_injected_fault_leaves_no_event():
    """A fatal fault simulates hard process death: nothing is written —
    not the span that was open, not its parents as the fault unwinds."""
    with pytest.raises(FaultInjected):
        with telemetry.span("persist"):
            with telemetry.span("write_labels"):
                raise FaultInjected("disk gone", fatal=True)
    assert telemetry.drain_spans() == []
    assert telemetry._span_stack() == []
    # a fault that is not fatal is an ordinary error: the spans land
    with pytest.raises(FaultInjected):
        with telemetry.span("persist"):
            raise FaultInjected("retry me", fatal=False)
    assert [r["span"] for r in telemetry.drain_spans()] == ["persist"]


def test_jit_compiled_inside_a_span_yields_a_child_and_a_second_call_none(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(telemetry, "_COMPILE_SPAN_MIN_S", 0.0)
    fn = jax.jit(lambda x: jnp.cumsum(x * 3.0 + 1.0))
    x = jnp.arange(17.0)
    telemetry.drain_spans()       # the arange compiled too, outside a span
    with telemetry.span_scope(step="illuminati", batch=4):
        with telemetry.span("prep"):
            fn(x).block_until_ready()
    first = telemetry.drain_spans()
    compiles = [r for r in first if r["span"] == "jit_compile"]
    assert len(compiles) == 1
    (compile_,) = compiles
    (prep,) = [r for r in first if r["span"] == "prep"]
    assert compile_["parent"] == "prep"
    assert (compile_["step"], compile_["batch"]) == ("illuminati", 4)
    assert "jit" in compile_["program"]
    # t0 = now - duration: the child lies inside its parent
    assert prep["t0"] - 1e-3 <= compile_["t0"]
    assert compile_["t0"] + compile_["elapsed"] \
        <= prep["t0"] + prep["elapsed"] + 1e-3
    kinds = {r["span"] for r in first}
    assert {"jit_trace", "jit_lower", "jit_compile", "prep"} <= kinds
    for r in first:
        if r["span"] == "cache_load":     # only where a cache served it
            assert r["parent"] == "jit_compile"

    with telemetry.span("prep"):
        fn(x).block_until_ready()
    second = telemetry.drain_spans()
    assert [r["span"] for r in second] == ["prep"]


def test_compile_events_outside_any_span_have_no_parent_and_short_ones_drop(
        monkeypatch):
    telemetry._ensure_compile_listener()
    event = "/jax/core/compile/backend_compile_duration"
    telemetry._on_compile_event(event, 0.25, fun_name="jit(f)")
    telemetry._on_compile_event(event, telemetry._COMPILE_SPAN_MIN_S / 2)
    telemetry._on_compile_event("/jax/some/other/duration", 5.0)
    telemetry._on_compile_event(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    records = telemetry.drain_spans()
    assert [(r["span"], r.get("parent")) for r in records] == [
        ("cache_load", "jit_compile"), ("jit_compile", None)]
    assert records[1]["elapsed"] == 0.25
    telemetry.set_enabled(False)
    telemetry._on_compile_event(event, 0.25)
    assert telemetry.drain_spans() == []


def test_buffer_is_bounded_when_nobody_drains(monkeypatch):
    import collections

    monkeypatch.setattr(telemetry, "_span_buffer",
                        collections.deque(maxlen=8))
    for i in range(20):
        with telemetry.span("s", i=i):
            pass
    records = telemetry.drain_spans()
    assert [r["i"] for r in records] == list(range(12, 20))


def test_many_threads_lose_no_span():
    """More workers than cores, a short switch interval: every span of
    every thread is drained exactly once, each with its own thread's
    batch (a lost update or a shared stack would break the count)."""
    n_threads, per_thread = 16, 200
    drained = []
    stop = threading.Event()

    def work(batch):
        with telemetry.span_scope(step="s", batch=batch):
            for i in range(per_thread):
                with telemetry.span("outer", i=i):
                    with telemetry.span("inner", i=i):
                        pass

    def drainer():
        while not stop.is_set():
            drained.extend(telemetry.drain_spans())

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(b,))
                   for b in range(n_threads)]
        d = threading.Thread(target=drainer)
        d.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        stop.set()
        d.join(timeout=10)
        assert not d.is_alive() and not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    drained.extend(telemetry.drain_spans())
    assert len(drained) == 2 * n_threads * per_thread
    for batch in range(n_threads):
        mine = [r for r in drained if r["batch"] == batch]
        assert sorted(r["i"] for r in mine if r["span"] == "outer") \
            == list(range(per_thread))
        assert all(r["parent"] == "outer" for r in mine
                   if r["span"] == "inner")
        assert all("parent" not in r for r in mine if r["span"] == "outer")


# ---------------------------------------------------- readers of the spans
def _ledger():
    """A batch whose persist (on a worker, no parent) holds two escalate
    rungs, each with its own children; dispatch on the engine thread."""
    def sp(span, t0, elapsed, parent=None, **kw):
        ev = {"event": "span", "span": span, "t0": t0, "elapsed": elapsed,
              "step": "jterator", "batch": 0, **kw}
        if parent:
            ev["parent"] = parent
        return ev

    return [
        {"event": "span", "span": "run", "t0": 0.0, "elapsed": 20.0},
        {"event": "span", "span": "step", "step": "jterator", "t0": 1.0,
         "elapsed": 12.0, "parent": "run"},
        {"event": "span", "span": "batch", "step": "jterator", "batch": 0,
         "t0": 1.0, "elapsed": 11.0},
        sp("dispatch", 1.0, 0.5, parent="step"),
        sp("upload", 1.1, 0.2, parent="dispatch", bytes=10),
        sp("persist", 2.0, 9.0),
        # children recorded BEFORE their parents close: any ledger order
        sp("upload", 2.2, 0.5, parent="escalate", bytes=10),
        sp("device_wait", 2.8, 1.0, parent="escalate"),
        sp("escalate", 2.1, 2.0, parent="persist", capacity=16),
        sp("upload", 4.3, 0.6, parent="escalate", bytes=10),
        sp("escalate", 4.2, 3.0, parent="persist", capacity=32),
        sp("fetch", 7.5, 1.0, parent="persist", bytes=99),
        sp("jit_compile", 5.0, 1.0, parent="escalate", program="jit(f)"),
        # batch-less: a compile-ahead thread's, booked to the step
        {"event": "span", "span": "jit_compile", "step": "jterator",
         "t0": 3.0, "elapsed": 4.0},
    ]


def test_span_tree_nests_by_parent_under_the_right_instance():
    tree = telemetry.build_span_tree(_ledger())
    (step,) = tree["children"]
    (batch,) = step["children"]
    phases = {c["name"]: c for c in batch["children"]}
    assert set(phases) == {"phase:dispatch", "phase:persist"}
    assert [c["name"] for c in phases["phase:dispatch"]["children"]] \
        == ["upload"]
    persist = phases["phase:persist"]
    assert [c["name"] for c in persist["children"]] \
        == ["escalate", "escalate", "fetch"]
    first, second = persist["children"][:2]
    assert [c["name"] for c in first["children"]] == ["upload",
                                                      "device_wait"]
    # the second rung's children are its own, not the first's
    assert [(c["name"], c["elapsed"]) for c in second["children"]] \
        == [("upload", 0.6), ("jit_compile", 1.0)]


def test_phase_totals_count_a_child_once():
    totals = telemetry.phase_totals(_ledger())
    # dispatch and persist, and the batch-less compile (no parent): the
    # spans inside them are not added again
    assert totals == {"dispatch": 0.5, "persist": 9.0, "jit_compile": 4.0}


def test_span_table_lists_every_span_by_step_parent_and_name():
    rows = {(r["step"], r["parent"], r["span"]): r
            for r in traceexport.span_table(_ledger())}
    assert rows[("jterator", "escalate", "upload")]["count"] == 2
    assert rows[("jterator", "escalate", "upload")]["total_s"] \
        == pytest.approx(1.1)
    assert rows[("jterator", "dispatch", "upload")]["count"] == 1
    assert rows[("jterator", "", "jit_compile")]["total_s"] == 4.0
    totals = [r["total_s"] for r in traceexport.span_table(_ledger())]
    assert totals == sorted(totals, reverse=True)
