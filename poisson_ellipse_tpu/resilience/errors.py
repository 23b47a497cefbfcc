"""Classified solve failures: one exception classification, one exit-code contract.

The reference's failure story is a printf and a nonzero ``exit`` with no
classification (``stage0/Withoutopenmp1.cpp:128`` prints "Breakdown" and
returns); the JAX runtime's is an opaque ``XlaRuntimeError`` whose only
machine-readable content is a status-prefixed message string. A serving
stack needs the middle layer: every way a guarded solve can fail maps to
exactly one :class:`SolveError` subclass, each carrying the process exit
code the harness CLI contracts to return:

  ========  ======================  =========================================
  exit      class                   meaning
  ========  ======================  =========================================
  2         DivergedError           recovery ladder exhausted: persistent
                                    breakdown / NaN poisoning / stagnation
  3         OutOfMemoryError        RESOURCE_EXHAUSTED with no engine left to
                                    degrade to
  4         SolveTimeout            ``--timeout`` deadline passed at a chunk
                                    boundary (partial trace artifact emitted)
  5         AdmissionRejected       the serving layer shed the request at
                                    admission (queue full / projected deadline
                                    miss); carries ``retry_after_s``
  6         SilentCorruptionError   the ABFT checksum/invariant layer
                                    (``resilience.abft``) detected silent
                                    data corruption that a rollback-and-rerun
                                    could not clear (a persistent SDC source:
                                    failing HBM, a sick interconnect lane)
  7         DeviceLossError         a mesh device was lost and no degraded
                                    mesh remains to resume on (or the
                                    degradation budget is exhausted)
  8         InvalidGeometryError    the geometry admissibility gate
                                    (``geom.validate``) rejected the problem
                                    BEFORE any device loop ran: malformed
                                    spec, empty/under-resolved domain,
                                    boundary contact, or an assembled
                                    operator that fails the finite/M-matrix/
                                    SPD checks
  9         FleetUnavailableError   every scheduler replica of the serving
                                    fleet (``fleet.router``) is dead or
                                    draining: there is no admission path
                                    left, so the request is refused loudly
                                    (with ``retry_after_s``) instead of
                                    hanging on a queue nobody will drain
  ========  ======================  =========================================

(exit 0 = converged, 1 = iteration cap reached without convergence — the
pre-existing harness contract — and the argparse-conventional 2 also
covers invalid invocations, which share "the request as stated cannot
succeed" with divergence.)

:func:`classify_error` is the single place device-runtime exceptions are
sniffed: XLA surfaces OOM as a ``RuntimeError`` whose message carries the
``RESOURCE_EXHAUSTED`` absl status (or "Out of memory"/"Allocation …
exceeds" phrasings, runtime-dependent), and Mosaic compile failures on an
over-budget kernel arrive the same way. Matching on the message is the
honest option — there is no structured error code on this API surface —
and it lives here exactly once so the guard, the engine chain and the
harness cannot drift.
"""

from __future__ import annotations

EXIT_DIVERGED = 2
EXIT_OOM = 3
EXIT_TIMEOUT = 4
EXIT_SHED = 5
EXIT_SDC = 6
EXIT_DEVICE_LOSS = 7
EXIT_INVALID_GEOMETRY = 8
EXIT_FLEET_UNAVAILABLE = 9


class SolveError(RuntimeError):
    """Base of the classified solve failures.

    ``classification`` is the stable machine-readable tag (``diverged`` /
    ``oom`` / ``timeout``) used in trace events and JSON reports;
    ``exit_code`` the contracted process exit. ``iters`` is the last
    healthy iteration count the guard reached, so a caller can report
    how far the solve got before it was given up on.
    """

    classification = "error"
    exit_code = 1

    def __init__(self, message: str, iters: int | None = None):
        super().__init__(message)
        self.iters = iters


class DivergedError(SolveError):
    """Recovery ladder exhausted: the solve keeps producing breakdown,
    non-finite iterates, or no progress past ``max_recoveries``."""

    classification = "diverged"
    exit_code = EXIT_DIVERGED


class OutOfMemoryError(SolveError):
    """RESOURCE_EXHAUSTED at compile or run time with no smaller engine
    left on the capacity ladder to degrade to."""

    classification = "oom"
    exit_code = EXIT_OOM


class SolveTimeout(SolveError):
    """The per-solve deadline passed. Raised only at chunk boundaries —
    the in-flight chunk is allowed to complete, so the carry the guard
    holds (and any trace events already flushed) stay consistent."""

    classification = "timeout"
    exit_code = EXIT_TIMEOUT


class AdmissionRejected(SolveError):
    """The serving layer refused the request at admission: the bounded
    queue is full, or the projected wait already overruns the request's
    deadline (``serve.queue``). This is backpressure, not failure — the
    request was never dispatched and is safe to resubmit after
    ``retry_after_s`` (the load-shedding contract: reject loudly now
    rather than time out silently later)."""

    classification = "shed"
    exit_code = EXIT_SHED

    def __init__(self, message: str, retry_after_s: float | None = None):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class SilentCorruptionError(SolveError):
    """The ABFT layer (``resilience.abft``) caught silent data corruption
    — a checksum/invariant violation in the sharded solve's own algebra
    (Huang–Abraham stencil checksum, residual/iterate sum recurrences,
    ⟨r, z⟩ positivity) — and the rollback-and-rerun recovery did not
    clear it: the corruption re-fired from the same clean carry, which is
    the signature of a *persistent* SDC source (failing HBM bank, sick
    interconnect lane), not a transient flip. Raised instead of returning
    an iterate the corruption may have laundered into; the guard NEVER
    applies residual replacement to an SDC-flagged carry for exactly that
    reason."""

    classification = "sdc"
    exit_code = EXIT_SDC


class DeviceLossError(SolveError):
    """A mesh device was lost (or declared lost by the straggler
    deadline) and the degraded-mesh ladder has nowhere left to go: no
    surviving devices, or ``max_degrades`` successive shrinks already
    spent. Anything short of this is *recovered*, not raised — the mesh
    guard rebuilds a smaller mesh from the last durable checkpoint and
    resumes (``resilience.meshguard``)."""

    classification = "device-loss"
    exit_code = EXIT_DEVICE_LOSS


class InvalidGeometryError(SolveError):
    """The geometry admissibility gate (``geom.validate``) classified the
    *problem* — not the solver — as unsolvable as stated, before any
    device loop ran. ``reason`` is the stable machine-readable sub-tag:

      ``malformed-spec``        the JSON geometry spec does not parse into
                                an SDF tree (unknown kind, wrong arity,
                                non-finite parameter)
      ``sdf-nonfinite``         the SDF itself evaluates to NaN/Inf on Ω
      ``empty-domain``          no sample of Ω lies inside the domain
      ``under-resolved``        the domain exists but a feature is thinner
                                than the grid spacing h — invisible to the
                                node lattice, so the discrete solve would
                                silently answer a different question
      ``boundary-contact``      the domain touches the Dirichlet ring of Ω
                                (the fictitious-domain method needs the
                                penalty band strictly around D)
      ``operator-nonfinite``    assembled coefficients carry NaN/Inf
      ``operator-not-m-matrix`` a face coefficient is <= 0 where the
                                5-point M-matrix sign structure needs > 0
      ``operator-asymmetric``   <Au, v> != <u, Av> beyond f64 round-off
      ``operator-not-spd``      the host Lanczos probe (``obs.spectrum``
                                over a short f64 diag-PCG) found a
                                non-positive Ritz value / indefinite pivot

    Serving maps it to the terminal ``invalid`` outcome at ADMISSION —
    a bad geometry is rejected before it can poison a lane mid-batch."""

    classification = "invalid-geometry"
    exit_code = EXIT_INVALID_GEOMETRY

    def __init__(self, message: str, reason: str = "invalid"):
        super().__init__(message)
        self.reason = reason


class FleetUnavailableError(SolveError):
    """Every scheduler replica of the serving fleet is down (dead lease,
    fenced, or draining): the router has no admission path left. This is
    the fleet-wide analog of :class:`AdmissionRejected` — refused loudly
    NOW with a ``retry_after_s`` hint, never a request parked on a queue
    no surviving replica will ever drain. Anything short of total loss is
    *routed around*, not raised: a single dead replica's queued and
    in-flight requests are handed off to survivors
    (``fleet.handoff``)."""

    classification = "fleet-unavailable"
    exit_code = EXIT_FLEET_UNAVAILABLE

    def __init__(self, message: str, retry_after_s: float | None = None):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class LeaseStoreError(RuntimeError):
    """Base of the lease-store (``fleet.replica.LeaseStore``) failure
    classification. These are *infrastructure* errors, not solve errors: they
    never escape the fleet router to a caller. The router converts
    "store unreachable past the grace window" into a classified
    :class:`FleetUnavailableError` (exit 9) at the admission boundary —
    fail-safe, never a hang — and everything else into deferred work
    that completes when the store recovers. ``classification`` is the
    tag used in trace events."""

    classification = "lease-store"


class LeaseStoreOutageError(LeaseStoreError):
    """The lease store is unreachable (injected partition/outage, or a
    real backend refusing the round-trip). Replicas holding unexpired
    leases keep serving — epoch *validation* answers from the local
    cache mirror — but every operation that must round-trip (issuing a
    fresh incarnation, fencing a dead one) raises this until the store
    answers a ping again."""

    classification = "lease-store-outage"


class LeaseStoreCorruptError(LeaseStoreError):
    """The persisted lease-store state failed to parse (torn write,
    truncation, bit rot). Classified loudly instead of re-initialising
    the epoch table: silently resetting epochs would let a fenced
    zombie's stale token validate again — the textbook split-brain."""

    classification = "lease-store-corrupt"


# status phrasings XLA/Mosaic use for memory exhaustion, across runtime
# versions; matched case-sensitively (they are absl status spellings)
_OOM_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "Out of memory",
    "out of memory",
    "exceeds the memory capacity",
    "Attempting to allocate",
)


def is_oom_error(exc: BaseException) -> bool:
    """True when ``exc`` is a device memory-exhaustion failure."""
    if isinstance(exc, OutOfMemoryError):
        return True
    if isinstance(exc, MemoryError):
        return True
    text = str(exc)
    return any(marker in text for marker in _OOM_MARKERS)


# status phrasings the runtime uses when a device dies under a dispatch;
# same stance as the OOM markers — the message string is the only
# machine-readable surface this API exposes. The simulated form
# (faultinject.SimulatedDeviceLoss) carries the first marker verbatim.
_DEVICE_LOSS_MARKERS = (
    "DEVICE_LOST",
    "device is in an error state",
    "Device or resource busy",
    "DATA_LOSS",
)


def is_device_loss_error(exc: BaseException) -> bool:
    """True when ``exc`` reads as a lost/failed device under a dispatch
    (real runtime phrasings or the injected
    ``faultinject.SimulatedDeviceLoss``)."""
    if isinstance(exc, DeviceLossError):
        return True
    text = str(exc)
    return any(marker in text for marker in _DEVICE_LOSS_MARKERS)


def classify_error(exc: BaseException) -> str:
    """The classification tag for an arbitrary exception out of a solve
    dispatch: ``oom`` / ``timeout`` / ``diverged`` (already-classified
    SolveErrors keep their own tag) or ``unknown`` for everything else —
    unknowns must stay loud, never be swallowed into a retry loop."""
    if isinstance(exc, SolveError):
        return exc.classification
    if isinstance(exc, LeaseStoreError):
        return exc.classification
    if is_oom_error(exc):
        return "oom"
    if is_device_loss_error(exc):
        return "device-loss"
    return "unknown"
