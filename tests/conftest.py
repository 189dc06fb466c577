"""Test harness configuration.

All tests run on the CPU platform with 8 virtual devices, so multi-chip
sharding (psum/shard_map paths) is exercised without TPU hardware, per
SURVEY.md §5.  Both are plain environment settings, made before jax is
first imported; nothing here touches an attached accelerator.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_enable_x64", False)

import numpy as np
import pytest

from tmlibrary_tpu import log as tm_log

# The serialized-executable store + compile-ahead speculation default ON
# in production, but the suite pins exact compile counts in several
# places (zero-compile smokes, perf attribution); a store hit or a
# background speculative compile would make those counts flaky.  Tests
# that exercise the warm path opt back in with monkeypatch.setenv.
os.environ.setdefault("TMX_AOT_STORE", "0")
os.environ.setdefault("TMX_AOT_SPECULATE", "0")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture
def pin_strategy(monkeypatch):
    """``pin_strategy(name)``: what ``auto`` resolves to inside
    ``ops/measure.py`` for the rest of the test.  Tier-1 runs on the CPU
    backend, where ``auto`` is ``scatter``; ``onehot`` is what the chip
    runs, and the measure functions that take no strategy argument
    (morphology, Zernike, everything under Haralick but the GLCM) reach
    it only through this pin."""
    from tmlibrary_tpu.ops import measure, reduction

    def pin(strategy):
        monkeypatch.setattr(
            measure, "resolve_reduction_strategy",
            lambda method="auto": reduction.resolve_reduction_strategy(
                strategy if method == "auto" else method),
        )

    return pin


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _reset_warn_once():
    """warn_once's suppression set is process-global: a warning consumed
    by one test would silently hide the assertion target of another."""
    tm_log.reset_warned()
    yield
    tm_log.reset_warned()


@pytest.fixture(autouse=True)
def _reset_routing_history():
    """Bucket-routing history is process-global (scoped per
    compiled-program key so serve jobs warm-start each other); tests
    must each start from a cold router or one test's dense plate would
    pre-route another's."""
    from tmlibrary_tpu import capacity

    capacity.reset_routing_history()
    yield
    capacity.reset_routing_history()


@pytest.fixture(autouse=True)
def _reset_trace_context():
    """The trace context is process-global on purpose (executor worker
    threads inherit the running job's labels).  Chaos tests leave
    hang-injected daemon threads parked INSIDE a job's trace scope;
    such a thread restores the empty context when its fault sleep
    expires, but until then the next test would observe the hung job's
    labels.  Clearing here is safe either way: the parked thread's
    ``finally`` restores the empty dict it captured on entry."""
    from tmlibrary_tpu import telemetry

    telemetry.set_trace_context()
    yield
    telemetry.set_trace_context()


@pytest.fixture(autouse=True)
def _reset_aotstore():
    """The executable store's process-default dir and compile tallies
    are process-global (serve daemons point the default at their spool
    root); leaking either across tests would misdirect a later test's
    store IO or skew its provenance counts."""
    from tmlibrary_tpu import aotstore

    aotstore.set_process_default_dir(None)
    aotstore.reset_counts()
    aotstore.reset_seconds_saved()
    yield
    aotstore.set_process_default_dir(None)
    aotstore.reset_counts()
    aotstore.reset_seconds_saved()


@pytest.fixture(autouse=True)
def _reset_qc():
    """The QC session singleton and its enable override are
    process-global; leak state and one test's sketches/flags bleed into
    another's profile assertions."""
    from tmlibrary_tpu import qc

    qc.set_enabled(None)
    qc.reset_session()
    yield
    qc.set_enabled(None)
    qc.reset_session()
