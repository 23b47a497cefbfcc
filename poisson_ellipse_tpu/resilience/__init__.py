"""resilience — guarded solves, classified failures, fault injection,
silent-corruption detection, degraded-mesh recovery.

Five legs, turning "the solver noticed something was wrong" into "the
service survived it":

- :mod:`.guard` — ``guarded_solve``: any engine's solve run in chunks
  with a one-word-per-chunk device-side health check (breakdown /
  NaN-Inf / stagnation) and a recovery ladder — true residual restart
  (direction-preserving, oracle-parity), f32→f64 precision escalation,
  engine fallback — capped by ``max_recoveries`` and classified on
  exhaustion.
- :mod:`.errors` — the :class:`SolveError` classification and the harness
  exit-code contract (2 = diverged, 3 = oom, 4 = timeout), plus the one
  place device-runtime OOM strings are sniffed.
- :mod:`.faultinject` — deterministic fault injection (NaN into a named
  carry field at iteration k, forced breakdown, stagnation, halo-slab
  corruption, halo bit-flips, sign-flipped psums, simulated OOM /
  device loss / stragglers, checkpoint truncation, shrunken-VMEM
  capacity gates), so every recovery path is exercised in tests and via
  ``harness inject`` — never assumed.
- :mod:`.abft` — algorithm-based silent-corruption detection for the
  sharded engines: checksum/invariant partials riding the existing
  stacked convergence psum (1 psum/iter preserved), classified apart
  from breakdown and answered by rollback-and-rerun, with persistent
  corruption raising :class:`SilentCorruptionError` (exit 6).
- :mod:`.meshguard` — device-loss/straggler detection at chunk
  boundaries and degraded-mesh recovery: shrink the mesh over the
  survivors, re-shard the last durable checkpoint, resume
  (``elastic_solve``; exhaustion raises :class:`DeviceLossError`,
  exit 7).
"""

from poisson_ellipse_tpu.resilience.errors import (
    EXIT_DEVICE_LOSS,
    EXIT_DIVERGED,
    EXIT_FLEET_UNAVAILABLE,
    EXIT_OOM,
    EXIT_SDC,
    EXIT_TIMEOUT,
    DeviceLossError,
    DivergedError,
    FleetUnavailableError,
    OutOfMemoryError,
    SilentCorruptionError,
    SolveError,
    SolveTimeout,
    classify_error,
    is_device_loss_error,
    is_oom_error,
)
from poisson_ellipse_tpu.resilience.faultinject import (
    Fault,
    FaultPlan,
    corrupt_halo,
    device_loss,
    force_breakdown,
    halo_bitflip,
    inject_nan,
    inject_stagnation,
    lease_clock_skew,
    psum_corrupt,
    replica_hang,
    replica_kill,
    simulate_oom,
    simulated_vmem,
    straggler,
    truncate_latest_checkpoint,
)
from poisson_ellipse_tpu.resilience.guard import (
    HEALTH_BREAKDOWN,
    HEALTH_CONVERGED,
    HEALTH_NONFINITE,
    HEALTH_SDC,
    HEALTH_STAGNATION,
    GuardedResult,
    RecoveryEvent,
    guarded_solve,
    health_name,
)
from poisson_ellipse_tpu.resilience.meshguard import (
    ElasticResult,
    MeshEvent,
    elastic_solve,
)

__all__ = [
    "DeviceLossError",
    "ElasticResult",
    "EXIT_DEVICE_LOSS",
    "EXIT_DIVERGED",
    "EXIT_FLEET_UNAVAILABLE",
    "EXIT_OOM",
    "EXIT_SDC",
    "EXIT_TIMEOUT",
    "DivergedError",
    "Fault",
    "FaultPlan",
    "FleetUnavailableError",
    "GuardedResult",
    "HEALTH_BREAKDOWN",
    "HEALTH_CONVERGED",
    "HEALTH_NONFINITE",
    "HEALTH_SDC",
    "HEALTH_STAGNATION",
    "MeshEvent",
    "OutOfMemoryError",
    "RecoveryEvent",
    "SilentCorruptionError",
    "SolveError",
    "SolveTimeout",
    "classify_error",
    "corrupt_halo",
    "device_loss",
    "elastic_solve",
    "force_breakdown",
    "guarded_solve",
    "halo_bitflip",
    "health_name",
    "inject_nan",
    "inject_stagnation",
    "is_device_loss_error",
    "is_oom_error",
    "lease_clock_skew",
    "psum_corrupt",
    "replica_hang",
    "replica_kill",
    "simulate_oom",
    "simulated_vmem",
    "straggler",
    "truncate_latest_checkpoint",
]
