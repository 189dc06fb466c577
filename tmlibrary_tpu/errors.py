"""Exception hierarchy.

Reference parity: ``tmlib/errors.py`` — the reference defines a small tree of
library-specific errors (``MetadataError``, ``PipelineError``,
``JobDescriptionError``, ``NotSupportedError``, ``RegistryError``).  We keep
the same names so error-handling code written against the reference maps
directly, and add TPU-rebuild-specific errors for the store and mesh layers.
"""


class TmError(Exception):
    """Base class for all framework errors."""


class MetadataError(TmError):
    """Error in experiment/image metadata handling."""


class VendorConflictError(MetadataError):
    """Vendor files make mutually-exclusive claims (e.g. two containers on
    one well).  Unlike an unparseable sidecar, this is a data-integrity
    problem: metaconfig's ``auto`` handler loop re-raises it instead of
    falling through to the next handler."""


class PipelineError(TmError):
    """Error in the jterator pipeline description or execution."""


class PipelineDescriptionError(PipelineError):
    """Invalid ``.pipe`` pipeline description."""


class HandleError(PipelineError):
    """Invalid module handle description or binding."""


class JobDescriptionError(TmError):
    """Error in a batch/job description."""


class NotSupportedError(TmError):
    """Requested feature is not supported."""


class RegistryError(TmError):
    """Error looking up a registered step/module/tool."""


class StoreError(TmError):
    """Error in the array/feature store layer."""


class WorkflowError(TmError):
    """Error in workflow orchestration (stage/step DAG, ledger, resume)."""


class ShardingError(TmError):
    """Error constructing or using a device mesh / sharding."""


class TransientDeviceError(TmError):
    """A device-side fault that is expected to clear on its own: the
    device became unreachable, a probe timed out, a collective was preempted,
    or the backend reported UNAVAILABLE/DEADLINE_EXCEEDED.  The retry
    policy treats this class (and look-alike messages from the runtime)
    as retryable; everything data-shaped stays permanent."""


class ProbeTimeoutError(TransientDeviceError):
    """A device health probe did not answer within its deadline — an
    unreachable device can *hang* instead of erroring.  Raised
    by ``resilience.call_with_timeout``; trips the circuit breaker."""


class WatchdogTimeout(TransientDeviceError):
    """A pipeline phase (launch / device block / persist) overran its
    watchdog deadline (``resilience.PhaseWatchdog``).  Subclasses
    :class:`TransientDeviceError` so the classifier treats a hung
    ``block_until_ready`` exactly like a lost device: retryable, and
    breaker-visible."""


class PreemptedError(TmError):
    """The run was asked to stop (SIGTERM/SIGINT preemption) and has
    finished draining: every in-flight batch either persisted with its
    ledger event or was abandoned un-launched.  Deliberately NOT a
    :class:`WorkflowError` — the engine's step-failure handlers must not
    record a drained run as a failed step (the ledger boundary is clean
    and ``resume`` continues from it).

    ``in_flight`` is the pipelined window size when the drain began,
    ``drained`` how many of those persisted during the drain, and
    ``abandoned`` how many planned batches were never launched."""

    def __init__(self, message: str, step: str | None = None,
                 in_flight: int = 0, drained: int = 0, abandoned: int = 0,
                 reason: str = "signal"):
        super().__init__(message)
        self.step = step
        self.in_flight = in_flight
        self.drained = drained
        self.abandoned = abandoned
        self.reason = reason


class FaultInjected(TmError):
    """An artificial fault raised by the deterministic fault-injection
    harness (``tmlibrary_tpu.faults``).  Never raised in production —
    only when a fault plan is installed.  ``transient`` mirrors how the
    error classifier should treat it; ``fatal=True`` simulates a hard
    process crash the engine must NOT absorb into batch quarantine."""

    def __init__(self, message: str, kind: str = "injected",
                 transient: bool = True, fatal: bool = False):
        super().__init__(message)
        self.kind = kind
        self.transient = transient
        self.fatal = fatal
