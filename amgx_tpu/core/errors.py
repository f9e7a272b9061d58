"""Typed failure catalog — the guardrail subsystem's vocabulary.

Every failure mode in the library maps to one of three exception
families, each carrying the AMGX_RC code the C API boundary reports
(reference amgx_c.h:52-69, AMGX_TRIES/AMGX_CATCHES):

  * :class:`SetupError` — the operator cannot be set up: singular /
    zero diagonal (:class:`SingularDiagonalError`), non-finite
    coefficients (:class:`NonFiniteValuesError`), or a degenerate /
    malformed sparsity pattern (:class:`PatternDegeneracyError`).
  * :class:`SolveBreakdown` — an iteration broke down in a way the
    status machinery cannot express (e.g. an injected breakdown that
    escaped the monitored loop).
  * :class:`ResourceError` — overflow/OOM-class failures: buffer
    addressing limits, compile failures, deadlines.  The device-setup
    ESC overflow (:class:`amgx_tpu.amg.device_setup.DeviceSetupOverflow`)
    is a subclass, so its host-builder fallback generalizes to the
    whole family.

The RC table lives here (single source of truth; the C API layer
re-exports it) so exceptions can be minted anywhere in core/amg/solvers
without importing the API layer.  ``rc_for_exception`` maps ANY Python
exception to an RC code — the catch-all the C API entry points use so
no raw traceback ever crosses the embedded ``.so`` boundary.

Input validation (``validate_csr`` / ``validate_operator``) is cheap
host-side numpy over the index/value arrays; ``AMGX_TPU_VALIDATE=0``
disables it globally (e.g. for fault-injection tests that construct
poisoned systems on purpose).
"""

from __future__ import annotations

import os

import numpy as np

# AMGX_RC codes — exact reference values (amgx_c.h:52-69) so host apps
# compiled against the reference header interpret codes identically.
# THRUST_FAILURE / NO_MEMORY are kept as placeholders for ABI parity.
RC_OK = 0
RC_BAD_PARAMETERS = 1
RC_UNKNOWN = 2
RC_NOT_SUPPORTED_TARGET = 3
RC_NOT_SUPPORTED_BLOCKSIZE = 4
RC_CUDA_FAILURE = 5
RC_THRUST_FAILURE = 6
RC_NO_MEMORY = 7
RC_IO_ERROR = 8
RC_BAD_MODE = 9
RC_CORE = 10
RC_PLUGIN = 11
RC_BAD_CONFIGURATION = 12
RC_NOT_IMPLEMENTED = 13
RC_LICENSE_NOT_FOUND = 14
RC_INTERNAL = 15


class AMGXTPUError(RuntimeError):
    """Base of the typed failure catalog; ``rc`` is the AMGX_RC code
    the C API boundary reports for this failure class."""

    rc = RC_UNKNOWN

    def __init__(self, msg: str = "", rc: int | None = None):
        super().__init__(msg)
        if rc is not None:
            self.rc = rc


class SetupError(AMGXTPUError):
    """Operator setup cannot proceed (bad coefficients / structure)."""

    rc = RC_CORE


class SingularDiagonalError(SetupError):
    """A (block) diagonal is exactly singular where the algorithm
    requires an invertible pivot (e.g. dense-LU zero pivot)."""


class NonFiniteValuesError(SetupError):
    """NaN/Inf in matrix coefficients or right-hand side."""


class PatternDegeneracyError(SetupError):
    """Malformed sparsity structure: non-monotone row pointers,
    out-of-range column indices, value/index length mismatch."""

    rc = RC_BAD_PARAMETERS


class SolveBreakdown(AMGXTPUError):
    """Iteration breakdown that escaped the in-loop status machinery."""

    rc = RC_INTERNAL


class ResourceError(AMGXTPUError):
    """Overflow/OOM-class failure: addressing limits, compile
    failures, exhausted deadlines."""

    rc = RC_NO_MEMORY


class DeviceLostError(ResourceError):
    """A device under the serving stack failed or hung: a dispatch or
    fetch raised a device-runtime error, or the in-flight watchdog
    expired on a fetch that never completed.  Maps to the reference
    RC_CUDA_FAILURE at the C API boundary (the "a GPU died" code).

    Carries the placement ``device_label`` of the failed device when
    the failure could be attributed, so failover (the
    :mod:`amgx_tpu.serve.placement` health breakers) can quarantine
    exactly the lost failure domain.  Recoverable by design: the serve
    layer requeues the group once through the degrade chain before
    this error ever reaches a ticket."""

    rc = RC_CUDA_FAILURE

    def __init__(self, msg: str = "", rc: int | None = None,
                 device_label: str | None = None):
        super().__init__(msg, rc)
        self.device_label = device_label


class DeadlineExceededError(ResourceError):
    """A request's ``deadline_s`` passed before it could be served —
    at submit (already expired on arrival), at flush (expired while
    queued), or at fetch (the result would arrive too late to matter).
    Subclass of :class:`ResourceError` so pre-existing deadline
    handling keeps working."""


class AdmissionRejected(ResourceError):
    """The fleet front-end (:mod:`amgx_tpu.serve.gateway`) refused a
    request at the door — quota exhausted, deadline provably
    unmeetable, or the pattern's circuit breaker is open.  Carries the
    machine-actionable retry hint ``retry_after_s`` (seconds the
    client should back off before resubmitting; None when unknown)
    and a short ``reason`` slug (``quota`` / ``deadline_unmeetable``
    / ``breaker_open`` / ``draining`` / ``overloaded``).

    A shed is a *recoverable, expected* condition: the C API maps it
    to a per-system FAILED status (RC_NO_MEMORY at the RC boundary),
    never a crash."""

    def __init__(self, msg: str = "", rc: int | None = None,
                 retry_after_s: float | None = None,
                 reason: str = "rejected"):
        super().__init__(msg, rc)
        self.retry_after_s = retry_after_s
        self.reason = reason


class Overloaded(AdmissionRejected):
    """The service as a whole is past its concurrency budget (or is
    draining): no request of this lane can be admitted right now,
    regardless of tenant."""

    def __init__(self, msg: str = "", rc: int | None = None,
                 retry_after_s: float | None = None,
                 reason: str = "overloaded"):
        super().__init__(msg, rc, retry_after_s=retry_after_s,
                         reason=reason)


class StoreError(AMGXTPUError):
    """Setup-artifact persistence failure (:mod:`amgx_tpu.store`):
    unreadable/corrupt payload, schema mismatch, or a setup that
    contains non-serializable state.  The artifact STORE never raises
    this on reads — corrupt entries degrade to cache misses — but the
    explicit ``save_setup``/``load_setup`` API surfaces it typed."""

    rc = RC_IO_ERROR


def rc_for_exception(e: BaseException) -> int:
    """AMGX_RC code for an arbitrary exception — the single catch-all
    mapping used at the C API boundary.  Typed catalog errors carry
    their own code; common Python exception classes map to the nearest
    reference code; everything else is RC_UNKNOWN."""
    rc = getattr(e, "rc", None)
    if isinstance(rc, int) and RC_OK <= rc <= RC_INTERNAL:
        return rc
    if isinstance(e, MemoryError):
        return RC_NO_MEMORY
    if isinstance(e, (OSError, EOFError)):
        return RC_IO_ERROR
    if isinstance(e, NotImplementedError):
        return RC_NOT_IMPLEMENTED
    if isinstance(e, KeyError):
        # unregistered solver/parameter names surface as KeyError
        return RC_BAD_CONFIGURATION
    if isinstance(e, (ValueError, TypeError, IndexError, AssertionError)):
        return RC_BAD_PARAMETERS
    return RC_UNKNOWN


# ---------------------------------------------------------------------------
# cheap input validation


def validation_enabled() -> bool:
    """Global kill-switch: AMGX_TPU_VALIDATE=0 disables all input
    validation (fault-injection tests build poisoned systems on
    purpose)."""
    return os.environ.get("AMGX_TPU_VALIDATE", "1") != "0"


def validate_csr(row_offsets, col_indices, values, n_rows, n_cols,
                 block_size=1, where="matrix upload"):
    """Structural + numeric sanity of host CSR arrays.

    Raises :class:`PatternDegeneracyError` for malformed structure and
    :class:`NonFiniteValuesError` for NaN/Inf coefficients.  A zero
    diagonal is NOT an error here — smoother setup applies the
    identity-scaling policy (ops/diagonal.py) and direct solvers detect
    their own pivots."""
    ro = np.asarray(row_offsets)
    ci = np.asarray(col_indices)
    nnz = ci.shape[0]
    if ro.ndim != 1 or ro.shape[0] != n_rows + 1:
        raise PatternDegeneracyError(
            f"{where}: row_offsets has shape {ro.shape}, "
            f"expected ({n_rows + 1},)"
        )
    if n_rows and (ro[0] != 0 or ro[-1] != nnz):
        raise PatternDegeneracyError(
            f"{where}: row_offsets span [{ro[0]}, {ro[-1]}] does not "
            f"cover nnz={nnz}"
        )
    if n_rows and np.any(np.diff(ro) < 0):
        raise PatternDegeneracyError(
            f"{where}: row_offsets is not non-decreasing"
        )
    if nnz:
        cmin, cmax = int(ci.min()), int(ci.max())
        if cmin < 0 or cmax >= n_cols:
            raise PatternDegeneracyError(
                f"{where}: column indices span [{cmin}, {cmax}] outside "
                f"[0, {n_cols})"
            )
    vals = np.asarray(values)
    if vals.size and np.issubdtype(vals.dtype, np.inexact) \
            and not np.all(np.isfinite(vals)):
        raise NonFiniteValuesError(
            f"{where}: matrix coefficients contain NaN/Inf"
        )


def validate_operator(A, where="solver setup"):
    """Numeric sanity of an already-constructed SparseMatrix (setup
    boundary: coefficients may have been replaced since upload)."""
    from amgx_tpu.core.profiling import setup_download

    vals = setup_download(A.values)
    if vals.size and np.issubdtype(vals.dtype, np.inexact) \
            and not np.all(np.isfinite(vals)):
        raise NonFiniteValuesError(
            f"{where}: operator coefficients contain NaN/Inf "
            f"({A.n_rows}x{A.n_cols}, nnz={vals.shape[0]})"
        )


def validate_vector(v, n, where="vector upload"):
    """Finite-values check for a right-hand side / initial guess."""
    if v is None:
        return
    arr = np.asarray(v).reshape(-1)
    if arr.shape[0] != n:
        raise PatternDegeneracyError(
            f"{where}: expected length-{n} vector, got {arr.shape[0]}"
        )
    if arr.size and np.issubdtype(arr.dtype, np.inexact) \
            and not np.all(np.isfinite(arr)):
        raise NonFiniteValuesError(f"{where}: vector contains NaN/Inf")
