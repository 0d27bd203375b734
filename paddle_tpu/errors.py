"""Typed error taxonomy (reference: platform/error_codes.proto:19-80 Code
enum, platform/enforce.h:282 EnforceNotMet, platform/errors.cc factory
functions, pybind/exception.cc:20 BindException).

The reference raises `EnforceNotMet` carrying one of 12 error codes plus
the offending op and a C++ backtrace. Here every class is an
`EnforceNotMet` subclass that ALSO inherits the natural Python builtin
(InvalidArgumentError is a ValueError, OutOfRangeError an IndexError,
UnimplementedError a NotImplementedError, ...), so callers can catch
either the framework taxonomy or the builtin they already handle — and
every pre-taxonomy `except ValueError/RuntimeError` keeps working.

Raise sites attach op provenance (op type + the user line that created
the op, the `__loc__` attr) via `op=`/`loc=`; `EnforceNotMet.op_type` and
`.user_loc` expose them for programmatic handling (the reference prints
them inside the enforce message, enforce.h:282 GetErrorSumaryString).
"""

from __future__ import annotations

import enum


class ErrorCode(enum.IntEnum):
    """platform/error_codes.proto Code enum (same numbering)."""

    LEGACY = 0
    INVALID_ARGUMENT = 1
    NOT_FOUND = 2
    OUT_OF_RANGE = 3
    ALREADY_EXISTS = 4
    RESOURCE_EXHAUSTED = 5
    PRECONDITION_NOT_MET = 6
    PERMISSION_DENIED = 7
    EXECUTION_TIMEOUT = 8
    UNIMPLEMENTED = 9
    UNAVAILABLE = 10
    FATAL = 11
    EXTERNAL = 12


class EnforceNotMet(Exception):
    """Base of the taxonomy (enforce.h:282). Carries the error code and,
    when raised from an op context, the op type and the user source line
    that created the op."""

    code = ErrorCode.LEGACY

    def __init__(self, message, op=None, loc=None):
        self.op_type = getattr(op, "type", op)
        self.user_loc = loc if loc is not None else (
            op.attr("__loc__", None) if hasattr(op, "attr") else None
        )
        parts = [str(message)]
        ctx = []
        if self.op_type:
            ctx.append(f"op {self.op_type!r}")
        if self.user_loc:
            ctx.append(f"created at {self.user_loc}")
        if ctx:
            parts.append(f"  [operator context: {', '.join(ctx)}]")
        parts.append(f"  [error code: {self.code.name} ({self.code.value})]")
        self.message = message
        super().__init__("\n".join(parts))


class EOFException(EnforceNotMet):
    """Reader/queue exhaustion (platform/enforce.h EOFException,
    pybind/exception.cc:21) — the sentinel fluid readers raise when a
    blocking queue closes."""

    code = ErrorCode.LEGACY


class InvalidArgumentError(EnforceNotMet, ValueError):
    code = ErrorCode.INVALID_ARGUMENT


class NotFoundError(EnforceNotMet, RuntimeError):
    code = ErrorCode.NOT_FOUND


class OutOfRangeError(EnforceNotMet, IndexError):
    code = ErrorCode.OUT_OF_RANGE


class AlreadyExistsError(EnforceNotMet, RuntimeError):
    code = ErrorCode.ALREADY_EXISTS


class ResourceExhaustedError(EnforceNotMet, MemoryError):
    code = ErrorCode.RESOURCE_EXHAUSTED


class PreconditionNotMetError(EnforceNotMet, RuntimeError):
    code = ErrorCode.PRECONDITION_NOT_MET


class PermissionDeniedError(EnforceNotMet, RuntimeError):
    code = ErrorCode.PERMISSION_DENIED


class ExecutionTimeoutError(EnforceNotMet, RuntimeError):
    code = ErrorCode.EXECUTION_TIMEOUT


class DeadlineExceededError(ExecutionTimeoutError):
    """A serving request's deadline expired before it was dispatched: the
    scheduler dropped it ahead of batch formation (``serving.expired``), so
    stale work never pads a bucket or burns a dispatch. Non-retryable — the
    client's latency budget is spent; re-queueing the same request can only
    produce an answer nobody is waiting for."""

    code = ErrorCode.EXECUTION_TIMEOUT
    retryable = False


class UnimplementedError(EnforceNotMet, NotImplementedError):
    code = ErrorCode.UNIMPLEMENTED


class UnavailableError(EnforceNotMet, RuntimeError):
    code = ErrorCode.UNAVAILABLE


class RequestShedError(UnavailableError):
    """The serving layer shed this request under overload: either a
    higher-priority admission evicted it from a full queue, or the brownout
    ladder is refusing its priority class outright (``serving.shed``).
    Marked non-retryable at the in-process seam — an immediate retry lands
    in the same overloaded queue; clients should back off (with jitter)
    before resubmitting."""

    code = ErrorCode.UNAVAILABLE
    retryable = False


class FatalError(EnforceNotMet, SystemError):
    code = ErrorCode.FATAL


class ExternalError(EnforceNotMet, OSError):
    code = ErrorCode.EXTERNAL


class CheckpointCorruptionError(EnforceNotMet, OSError):
    """A checkpoint failed integrity verification (torn write, CRC/shape/
    dtype mismatch vs its manifest, undecodable container). Raised by
    io.py load paths BEFORE any scope mutation — never silently-wrong
    weights. An OSError so generic IO handlers still catch it, but
    explicitly non-retryable: re-reading corrupt bytes cannot help, the
    caller must fall back to an older checkpoint (Fleet.load_check_point
    does so automatically)."""

    code = ErrorCode.EXTERNAL
    retryable = False


class StorageExhaustedError(EnforceNotMet, OSError):
    """A durable write ran out of disk: the filesystem returned ``ENOSPC``/
    ``EDQUOT``, the preflight free-space check found less room than the
    payload needs, or the storage pressure ladder is at CRITICAL and
    refusing new checkpoint/publish writes outright. An OSError so generic
    IO handlers still catch it, and retryable-after-GC by design: unlike
    :class:`CheckpointCorruptionError`, retrying CAN succeed — but only
    once space is reclaimed, so the retry policies treat it as
    non-retryable in-place (``retryable = False``) and the caller is
    expected to run (or wait for) ``resilience.storage.RetentionManager``
    GC before trying again. The failed write itself is clean: io.py's
    atomic writers unlink their temp file on every failure path, so a full
    disk never accretes ``*.tmp.*`` garbage that makes itself fuller."""

    code = ErrorCode.RESOURCE_EXHAUSTED
    retryable = False


class NonFiniteError(PreconditionNotMetError):
    """A NaN/Inf reached a numeric health check: the executor's
    FLAGS_check_nan_inf per-op scan (which names the offending op via
    `op=`/`outputs=`) and TrainGuard's always-on fused fetch check both
    raise this. A PreconditionNotMetError subclass so pre-existing
    handlers keep working; non-retryable — re-running the same step on
    the same state reproduces the same NaN."""

    code = ErrorCode.PRECONDITION_NOT_MET
    retryable = False

    def __init__(self, message, op=None, loc=None, outputs=None):
        self.outputs = list(outputs) if outputs else []
        if self.outputs:
            message = f"{message}; outputs: {self.outputs}"
        super().__init__(message, op=op, loc=loc)


class ResumeMismatchError(PreconditionNotMetError):
    """On resume, a rank's view of the checkpoint is incoherent: its
    ``rank_<i>/`` state shard carries a different checkpoint number or
    global step than the checkpoint-level commit record, or a shard the
    commit record promises is missing. Loading anyway would silently
    diverge the ranks (one replays a different data prefix than the
    others), so this is typed and non-retryable — the caller must pick a
    coherent (usually older) checkpoint; ``Fleet.load_check_point`` skips
    incomplete checkpoints automatically when no explicit
    ``checkpoint_no`` was requested."""

    code = ErrorCode.PRECONDITION_NOT_MET
    retryable = False


class ProgramVerifyError(PreconditionNotMetError):
    """The pre-compile static verifier (paddle_tpu/analysis) found ERROR
    findings under ``PADDLE_TPU_VERIFY=strict``: the Program is structurally
    malformed (use-before-def, shape/dtype desync vs the emitters, a
    rank-divergent collective schedule, ...). Raised at
    ``Executor._compile`` time BEFORE any XLA trace, so the message carries
    per-op provenance instead of an opaque trace error — and a mismatched
    collective fails here instead of deadlocking the pod. ``findings``
    holds the full, structured ``analysis.Finding`` list (errors first).
    Non-retryable: the graph itself must be fixed."""

    code = ErrorCode.PRECONDITION_NOT_MET
    retryable = False

    def __init__(self, message, findings=None, op=None, loc=None):
        self.findings = list(findings or [])
        super().__init__(message, op=op, loc=loc)


class ProgramVerifyWarning(UserWarning):
    """Category for warnings emitted by the static program verifier in its
    default ``PADDLE_TPU_VERIFY=warn`` mode (and by ``Block.create_var``
    when a name is silently redefined). Filter with
    ``warnings.filterwarnings(..., category=ProgramVerifyWarning)``."""


class CostAnalysisUnavailableWarning(UserWarning):
    """The compiled executable's ``cost_analysis()`` returned no data
    (``Executor.flops``): the backend genuinely reports nothing, which is
    NOT the same as a zero-FLOP program. Callers deriving MFU from
    ``Executor.flops`` should fall back to ``Program.estimate()``. Each
    occurrence also bumps the ``perf.cost_analysis_unavailable``
    counter."""


class TrainingDivergedError(EnforceNotMet, RuntimeError):
    """TrainGuard exhausted its recovery policy: K consecutive non-finite
    steps and no (remaining) checkpoint to roll back to. The run cannot
    make progress by retrying — a human (or an outer scheduler with a
    different initialization/LR) must intervene."""

    code = ErrorCode.FATAL
    retryable = False


def enforce(condition, error):
    """PADDLE_ENFORCE (enforce.h:282): raise `error` (an EnforceNotMet
    instance) unless `condition`."""
    if not condition:
        raise error
