"""One measured solve: the TPU analog of the reference's ``main`` drivers.

Reproduces the reference's wall-clock segmentation (program = init +
solver + finalize, ``poisson_mpi_cuda2.cu:992-1034``) with fenced phase
timers, and its rank-0 result summary (config echo, "converged after k",
iteration count, total time, phase breakdown,
``poisson_mpi_cuda2.cu:1000-1003,1026-1034``) — plus the L2-error-vs-
analytic metric the reference states but never computes (README.md:38-42;
no stage computes it — SURVEY §4.1).
"""

from __future__ import annotations

import math
import statistics
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax import lax

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs import trace as obs_trace
from poisson_ellipse_tpu.parallel.mesh import AXIS_X, AXIS_Y, make_mesh
from poisson_ellipse_tpu.parallel.pcg_sharded import build_sharded_solver
from poisson_ellipse_tpu.resilience.errors import (
    OutOfMemoryError,
    is_oom_error,
)
from poisson_ellipse_tpu.solver.engine import (
    BATCHED_ENGINES,
    CAPACITY_LADDER,
    build_solver,
)
from poisson_ellipse_tpu.utils.error import l2_error_vs_analytic
from poisson_ellipse_tpu.utils.timing import PhaseTimer, fence

# runtime degradation ladder for `--engine auto`: RESOURCE_EXHAUSTED on
# the first (compile + warm-up) dispatch walks down one rung per retry;
# xla has no capacity gate, so the ladder always terminates. The rungs
# are the engine-capability table's (solver.engine.ENGINE_CAPS) — one
# source for the ladder here, in build_solver and in the autotuner.
_DEGRADE_LADDER = CAPACITY_LADDER
# seconds before re-dispatching after an OOM: gives the allocator a beat
# to release the failed attempt's buffers before the smaller engine asks
_DEGRADE_BACKOFF_S = 0.25

DTYPES = {
    "f32": jnp.float32,
    # deliberate f64 menu entry: resolve_dtype below flips jax_enable_x64
    # on before this dtype is ever applied, so it cannot downcast
    "f64": jnp.float64,  # tpulint: disable=TPU001
    "bf16": jnp.bfloat16,
}


def resolve_dtype(dtype: str):
    """Map a dtype name to the jnp dtype, enabling x64 when required.

    Without ``jax_enable_x64``, jnp silently downcasts f64 arrays to f32 —
    a run labelled f64 would actually produce f32 results. The reference
    is entirely double precision, so honouring a f64 request means
    flipping the config switch, not mislabelling.
    """
    if dtype == "f64" and not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)
    return DTYPES[dtype]


def resolve_mesh(mesh_shape: tuple[int, int] | None):
    """A 2D ('x','y') device mesh: explicit PX×PY, or near-square over all
    devices (the reference's ``choose_process_grid`` policy)."""
    if mesh_shape is None:
        return make_mesh()
    px, py = mesh_shape
    devices = jax.devices()
    if px * py > len(devices):
        raise ValueError(
            f"mesh {px}x{py} needs {px * py} devices, have {len(devices)}"
        )
    import numpy as np

    return jax.sharding.Mesh(
        np.asarray(devices[: px * py]).reshape(px, py), (AXIS_X, AXIS_Y)
    )


@dataclass
class RunReport:
    """Everything the reference's rank-0 summary prints, plus L2 error."""

    problem: Problem
    mesh_shape: tuple[int, int]
    dtype: str
    engine: str
    iters: int
    converged: bool
    breakdown: bool
    diff: float
    l2_error: float
    t_init: float
    t_solver: float
    times: list[float] = field(default_factory=list)
    # bytes-per-iteration roofline (harness.roofline): modelled HBM
    # passes/iter for the engine, the achieved GB/s they imply, and the
    # fraction of the chip's HBM peak (None when the peak is unknown)
    passes_per_iter: float = 0.0
    hbm_gbps: float = 0.0
    hbm_peak_frac: float | None = None
    # OpenMP thread count of a native run (0 = runtime default; the
    # stage1 sweep tables key on this — Этап1.pdf table 2)
    threads: int = 0
    # iterations covered by t_solver when it differs from ``iters`` — a
    # resumed checkpointed run times only the iterations it ran, while
    # ``iters`` stays the solver's cumulative (oracle-checked) count
    timed_iters: int | None = None
    # recovery actions a guarded run applied (resilience.guard event
    # kinds, in order); empty = the healthy path ran start to finish
    recoveries: list[str] = field(default_factory=list)
    # lane width of a batched run (--lanes; 1 = the single-solve
    # protocol) and the aggregate throughput it achieved: lanes divided
    # by the per-dispatch T_solver. quarantined counts lanes masked out
    # after a non-finite carry (batch.batched_pcg)
    lanes: int = 1
    solves_per_sec: float | None = None
    quarantined: int = 0
    # HBM storage width of the state/operand streams when it differs
    # from the compute dtype ("bf16": the bandwidth axis, ops.precision);
    # None = storage == compute, the historical single-dtype run
    storage_dtype: str | None = None

    def summary(self) -> str:
        p = self.problem
        lines = [
            f"Grid: {p.M} x {p.N}  (h1={p.h1:.6g}, h2={p.h2:.6g}, "
            f"eps={p.eps_value:.6g}, delta={p.delta:g}, norm={p.norm})",
            f"Mesh: {self.mesh_shape[0]} x {self.mesh_shape[1]}  "
            f"dtype={self.dtype}"
            + (
                f" (storage {self.storage_dtype})"
                if self.storage_dtype else ""
            )
            + f"  engine={self.engine}",
            (
                f"Converged after {self.iters} iterations (diff={self.diff:.3e})"
                if self.converged
                else (
                    f"BREAKDOWN after {self.iters} iterations"
                    if self.breakdown
                    else f"NOT converged after {self.iters} iterations "
                    f"(diff={self.diff:.3e})"
                )
            ),
            f"T_init   {self.t_init:10.4f} s",
            f"T_solver {self.t_solver:10.4f} s"
            + (
                f"  (median of {len(self.times)}: "
                + ", ".join(f"{t:.4f}" for t in self.times)
                + ")"
                if len(self.times) > 1
                else ""
            ),
            f"L2 error vs analytic: {self.l2_error:.6e}",
        ]
        if self.lanes > 1:
            lines.append(
                f"Lanes: {self.lanes}  "
                f"throughput {self.solves_per_sec:.2f} solves/s"
                + (
                    f"  ({self.quarantined} lane(s) quarantined)"
                    if self.quarantined
                    else ""
                )
            )
        if self.recoveries:
            lines.append(
                f"Recoveries: {len(self.recoveries)} "
                f"({', '.join(self.recoveries)})"
            )
        line = self.roofline_line()
        if line:
            lines.append(line)
        return "\n".join(lines)

    def roofline_line(self) -> str:
        """One-line roofline summary, '' when the model does not apply
        (native host runs, zero timed iterations)."""
        n = self.timed_iters if self.timed_iters is not None else self.iters
        if not n or self.engine == "native" or self.lanes > 1:
            # lane-batched runs report throughput (solves/sec), not the
            # single-solve HBM traffic model
            return ""
        if self.passes_per_iter == 0:
            # the engine left the HBM roofline entirely: its working set is
            # VMEM-resident, so "0 GB/s" would read as broken when it is
            # the design goal (harness.roofline module docstring)
            return (
                f"Roofline: {self.t_solver / n * 1e6:.1f} us/iter, "
                "VMEM-resident (no per-iteration HBM traffic)"
            )
        frac = (
            f"  ({self.hbm_peak_frac:.1%} of HBM peak)"
            if self.hbm_peak_frac is not None
            else ""
        )
        return (
            f"Roofline: {self.t_solver / n * 1e6:.1f} us/iter, "
            f"{self.passes_per_iter:g} HBM passes/iter -> "
            f"{self.hbm_gbps:.0f} GB/s{frac}"
        )

    def json_dict(self) -> dict:
        p = self.problem
        return {
            "M": p.M,
            "N": p.N,
            "mesh": list(self.mesh_shape),
            "dtype": self.dtype,
            "engine": self.engine,
            "eps": p.eps_value,
            "delta": p.delta,
            "iters": self.iters,
            "converged": self.converged,
            "diff": self.diff,
            # NaN (the --geometry runs' "analytic metric undefined")
            # must serialize as null: a literal NaN token is not RFC
            # JSON and strict consumers reject the whole record
            "l2_error": (
                self.l2_error if math.isfinite(self.l2_error) else None
            ),
            "t_init_s": self.t_init,
            "t_solver_s": self.t_solver,
            "passes_per_iter": self.passes_per_iter,
            "hbm_gbps": self.hbm_gbps,
            "hbm_peak_frac": self.hbm_peak_frac,
            **({"threads": self.threads} if self.engine == "native" else {}),
            **({"recoveries": self.recoveries} if self.recoveries else {}),
            **(
                {"storage_dtype": self.storage_dtype}
                if self.storage_dtype else {}
            ),
            **(
                {
                    "lanes": self.lanes,
                    "solves_per_sec": self.solves_per_sec,
                    "quarantined": self.quarantined,
                }
                if self.lanes > 1
                else {}
            ),
        }


def run_once(
    problem: Problem,
    mode: str = "auto",
    mesh_shape: tuple[int, int] | None = None,
    dtype: str = "f32",
    engine: str = "auto",
    repeat: int = 1,
    batch: int = 1,
    lanes: int = 1,
    threads: int = 0,
    checkpoint_dir: str | None = None,
    chunk: int = 500,
    timeout: float | None = None,
    guard: bool = False,
    max_recoveries: int = 3,
    geometry=None,
    theta: float | None = None,
    storage_dtype: str | None = None,
    sstep_s: int = 4,
    recycle: int | None = None,
    warm_start: bool = False,
) -> RunReport:
    """Assemble + solve with fenced init/solver timing.

    ``geometry`` (a ``geom.sdf`` shape or its JSON spec) selects an
    arbitrary SDF domain: the admissibility gate runs before any build
    (classified ``InvalidGeometryError``, exit 8 in the CLI), operands
    come from the bisection quadrature with the degenerate-cut clamp at
    ``theta``, and — since the analytic solution is an ellipse fact —
    the report's ``l2_error`` is NaN (convergence + the maximum
    principle are the checks for arbitrary domains).

    mode:  "single" — single-device solver (stage0/1/4-1GPU analog);
           "sharded" — mesh-sharded solver (stage2/3/4 analog);
           "native" — the C++/OpenMP host runtime (stage0/1 natively;
                      always f64; ``threads`` selects the OpenMP count;
                      T_solver includes assembly, exactly as the
                      reference's stage0 chrono wraps its whole solve());
           "auto" — sharded iff >1 device or an explicit mesh is requested.
    engine: single-device solver engine (``solver.engine.ENGINES``) —
           "auto" picks the fastest whose capacity regime applies
           (resident → streamed → xl; f64 takes xla).
    repeat/batch: timing protocol. For single mode with batch>1, each of
    the ``repeat`` measurements times one plain dispatch and one chained
    dispatch of ``batch`` data-dependent solves, and T_solver is the
    median *marginal* solve cost (t_chained − t_single)/(batch − 1) —
    the fixed per-dispatch host overhead cancels out (see
    ``_chain_solver``). Otherwise ``repeat`` measurements of ``batch``
    back-to-back dispatches each; T_solver is the median per-dispatch
    time.

    lanes: lane width for the batched engines — ``lanes`` independent
    solves ride ONE dispatch (``--lanes``; distinct from ``batch``,
    which chains *dispatches* purely as a timing protocol). With
    lanes > 1 the engine must be ``batched``/``batched-pipelined``
    (``auto`` resolves to ``batched``) and the report carries per-lane
    aggregates plus ``solves_per_sec = lanes / T_solver``.

    timeout/guard/max_recoveries: the resilience surface. ``guard=True``
    (or any ``timeout``) routes the solve through
    ``resilience.guard.guarded_solve`` — chunked execution, per-chunk
    health word, the recovery ladder, classified ``SolveError``s instead
    of NaN results — with plain wall-clock timing (restartable solves
    are not the bench protocol, same stance as checkpointed runs).
    ``timeout`` is seconds per solve, cancelled gracefully at a chunk
    boundary (``SolveTimeout``, exit code 4 in the CLI).

    recycle/warm_start: the Krylov-recycling surface (``--recycle`` /
    ``--warm-start``). ``recycle`` (a ring capacity; the CLI default is
    ``solver.recycle.RECYCLE_CAP``) runs one untimed ring-carrying
    capture solve during init, harvests the extremal Ritz deflation
    basis host-side, and times the deflated restart of the same system
    (``x0 = W(WᵀAW)⁻¹Wᵀ·rhs`` — the reported iteration count is the
    deflated one). ``warm_start`` seeds the timed solve with the capture
    solve's solution — the semantic-cache-hit shape (on top of deflation
    when both are set); warm-started solution bits legitimately differ
    from cold, which is why the report stays honest about ``iters`` and
    ``l2_error`` instead of claiming bit-parity. Both ride the xla
    single-device engine (the one with the ``recycle`` contract row) and
    correctness never depends on the basis: ``init_state`` verifies any
    x0 by its TRUE residual.
    """
    if lanes < 1:
        raise ValueError("lanes must be >= 1")
    if storage_dtype is not None:
        if mode == "native":
            raise ValueError(
                "--storage-dtype rides the JAX engines; the native host "
                "runtime is f64 end to end"
            )
        if checkpoint_dir is not None:
            raise ValueError(
                "checkpoint fingerprints do not cover a storage dtype "
                "yet; drop --checkpoint-dir or --storage-dtype"
            )
    if geometry is not None and mode == "native":
        raise ValueError(
            "--geometry rides the JAX assembly paths; the native host "
            "runtime implements the closed-form ellipse only"
        )
    if geometry is not None and checkpoint_dir is not None:
        raise ValueError(
            "checkpoint fingerprints do not cover a geometry spec yet; "
            "drop --checkpoint-dir or --geometry"
        )
    if lanes > 1 or engine in BATCHED_ENGINES:
        if mode == "native":
            raise ValueError(
                "--lanes rides the JAX batched engines; the native host "
                "runtime solves one problem at a time"
            )
        if checkpoint_dir is not None:
            raise ValueError(
                "checkpointing persists the single-solve PCG carry; "
                "drop --checkpoint-dir or --lanes"
            )
        if engine == "auto":
            engine = "batched"
        if engine not in BATCHED_ENGINES:
            raise ValueError(
                f"engine {engine!r} runs one solve per dispatch; "
                "--lanes needs --engine batched or batched-pipelined"
            )
        if mode == "auto":
            # lane batching is the single-chip throughput engine; the
            # lane-sharded mesh composition is opt-in (--mode sharded /
            # --mesh), not inferred from the device count
            mode = "sharded" if mesh_shape is not None else "single"
        lanes = max(lanes, 1)
    if mode == "native":
        if checkpoint_dir is not None:
            raise ValueError("checkpointing covers the JAX paths, not native")
        if timeout is not None or guard:
            raise ValueError(
                "--timeout/--guard cover the JAX paths (chunked guarded "
                "solves); the native host runtime has no chunk boundary "
                "to cancel or recover at"
            )
        return _run_native(problem, repeat=repeat, threads=threads)
    jdtype = resolve_dtype(dtype)
    if mode == "auto":
        mode = (
            "sharded"
            if mesh_shape is not None or len(jax.devices()) > 1
            else "single"
        )
    if mode not in ("single", "sharded"):
        raise ValueError(f"unknown mode: {mode!r}")
    if recycle is not None or warm_start:
        # the recycling surface rides the single-device xla loop — the
        # engine whose ENGINE_CAPS row carries the `recycle` contract
        # (ring-extended carry, recycle=None jaxpr-pinned byte-identical)
        if recycle is not None and recycle < 1:
            raise ValueError("--recycle ring capacity must be >= 1")
        if mode != "single" or engine not in ("auto", "xla"):
            raise ValueError(
                "--recycle/--warm-start ride the single-device xla loop "
                "(the engine with the recycle contract row); sharded "
                "recycling is the serve scheduler's per-bucket pool"
            )
        if lanes > 1:
            raise ValueError(
                "--recycle/--warm-start time one deflated solve; lane "
                "batching takes recycling through the serve scheduler's "
                "per-bucket pools (drop --lanes)"
            )
        if timeout is not None or guard or checkpoint_dir is not None:
            raise ValueError(
                "--recycle/--warm-start are a timing protocol (capture + "
                "deflated restart); drop --guard/--timeout/--checkpoint-dir"
            )
        if geometry is not None or storage_dtype is not None:
            raise ValueError(
                "--recycle/--warm-start cover the full-width analytic "
                "ellipse path (the harvest and the l2 report are ellipse "
                "facts); drop --geometry/--storage-dtype"
            )
        return _run_recycled(
            problem, dtype, jdtype, repeat=repeat, batch=batch,
            recycle=recycle, warm_start=warm_start,
        )
    if (storage_dtype is not None and mode == "sharded"
            and engine not in ("sstep", "sstep-pallas") and not guard
            and timeout is None):
        raise ValueError(
            "sharded --storage-dtype covers the sstep engine (whose "
            "deep-halo exchange ships the narrow state); the classical/"
            "pipelined/batched sharded forms run full width"
        )
    if geometry is not None:
        # the gate runs ONCE here for every JAX path (the sharded
        # builders assemble without re-validating, and build_solver is
        # told the gate already passed)
        from poisson_ellipse_tpu.geom import sdf as geom_sdf
        from poisson_ellipse_tpu.geom import validate as geom_validate

        if isinstance(geometry, dict):
            geometry = geom_sdf.from_spec(geometry)
        geom_validate.validate(problem, geometry, theta=theta)
        if mode == "sharded" and engine in BATCHED_ENGINES:
            raise ValueError(
                "lane-sharded batched runs take per-request geometry "
                "through the serve scheduler; drop --geometry or use a "
                "single-solve engine"
            )
    if timeout is not None or guard:
        if checkpoint_dir is not None:
            raise ValueError(
                "guarded/timeout runs and checkpointed runs are separate "
                "chunked drivers; drop --checkpoint-dir or --timeout/--guard"
            )
        if repeat > 1 or batch > 1:
            raise ValueError(
                "guarded/timeout runs are one wall-clocked chunked solve; "
                "the repeat/batch timing protocol does not apply"
            )
        if engine in BATCHED_ENGINES:
            if mode == "sharded":
                raise ValueError(
                    "guarded batched solves run the single-device chunked "
                    "lane driver (batch.driver); drop --mesh/--mode sharded"
                )
            if geometry is not None:
                raise ValueError(
                    "guarded lane-batched runs take per-request geometry "
                    "through the serve scheduler; drop --geometry or "
                    "--guard/--lanes"
                )
            return _run_batched_guarded(
                problem, dtype, jdtype, engine, lanes, timeout=timeout,
            )
        return _run_guarded(
            problem, mode, mesh_shape, dtype, jdtype, engine,
            timeout=timeout, max_recoveries=max_recoveries,
            geometry=geometry, theta=theta, storage_dtype=storage_dtype,
            sstep_s=sstep_s,
        )
    if checkpoint_dir is not None:
        if repeat > 1 or batch > 1:
            raise ValueError(
                "checkpointed runs are one wall-clocked chunked solve; "
                "the repeat/batch timing protocol does not apply "
                "(drop --repeat/--batch or --checkpoint-dir)"
            )
        return _run_checkpointed(
            problem, mode, mesh_shape, dtype, jdtype, engine,
            checkpoint_dir, chunk,
        )

    timer = PhaseTimer()
    requested_auto = engine == "auto"
    if mode == "single":
        with timer.phase("init"):
            solver, args, engine = build_solver(
                problem, engine, jdtype, lanes=lanes, geometry=geometry,
                theta=theta, validate_geometry=False,
                storage_dtype=storage_dtype, sstep_s=sstep_s,
            )
            fence(args)
        shape = (1, 1)
    elif mode == "sharded" and engine in BATCHED_ENGINES:
        from poisson_ellipse_tpu.parallel.batched_sharded import (
            build_batched_sharded_solver,
        )

        with timer.phase("init"):
            mesh = resolve_mesh(mesh_shape)
            solver, args = build_batched_sharded_solver(
                problem, mesh, lanes, jdtype,
                pipelined=engine == "batched-pipelined",
            )
            fence(args)
        shape = (mesh.shape[AXIS_X], mesh.shape[AXIS_Y])
    elif mode == "sharded" and engine in ("mg-pcg", "cheb-pcg"):
        from poisson_ellipse_tpu.parallel.mg_sharded import (
            build_mg_sharded_solver,
        )
        from poisson_ellipse_tpu.solver.engine import PRECOND_KIND_BY_ENGINE

        with timer.phase("init"):
            mesh = resolve_mesh(mesh_shape)
            solver, args = build_mg_sharded_solver(
                problem, mesh, jdtype,
                kind=PRECOND_KIND_BY_ENGINE[engine],
                geometry=geometry, theta=theta,
            )
            fence(args)
        shape = (mesh.shape[AXIS_X], mesh.shape[AXIS_Y])
    elif mode == "sharded" and engine == "fmg":
        from poisson_ellipse_tpu.parallel.mg_sharded import (
            build_fmg_sharded_solver,
        )

        with timer.phase("init"):
            mesh = resolve_mesh(mesh_shape)
            solver, args = build_fmg_sharded_solver(
                problem, mesh, jdtype, geometry=geometry, theta=theta,
            )
            fence(args)
        shape = (mesh.shape[AXIS_X], mesh.shape[AXIS_Y])
    elif mode == "sharded" and engine in ("sstep", "sstep-pallas"):
        from poisson_ellipse_tpu.parallel.sstep_sharded import (
            build_sstep_sharded_solver,
        )

        with timer.phase("init"):
            mesh = resolve_mesh(mesh_shape)
            solver, args = build_sstep_sharded_solver(
                problem, mesh, jdtype, s=sstep_s,
                storage_dtype=storage_dtype, geometry=geometry,
                theta=theta,
            )
            engine = "sstep"
            fence(args)
        shape = (mesh.shape[AXIS_X], mesh.shape[AXIS_Y])
    elif mode == "sharded":
        if engine not in ("auto", "xla", "pallas", "fused", "pipelined"):
            raise ValueError(
                f"engine {engine!r} is single-device only; sharded mode "
                "runs the XLA block stencil ('xla', default), the "
                "per-shard Pallas stencil kernel ('pallas'), the "
                "two-kernel fused per-shard iteration ('fused', f32/bf16), "
                "the one-psum-per-iteration pipelined recurrence "
                "('pipelined'), the one-psum-per-s-iterations s-step "
                "form ('sstep'), or the preconditioned forms ('mg-pcg' / "
                "'cheb-pcg': V-cycle/Chebyshev per shard, halo-ppermute "
                "only — the scalar-collective cadence stays classical)"
            )
        # (narrow-storage sharded requests were already rejected by the
        # mode-level check above — sstep is the one sharded storage form)
        engine = "xla" if engine == "auto" else engine
        with timer.phase("init"):
            mesh = resolve_mesh(mesh_shape)
            solver, args = build_sharded_solver(
                problem, mesh, jdtype, stencil_impl=engine,
                geometry=geometry, theta=theta,
            )
            fence(args)
        shape = (mesh.shape[AXIS_X], mesh.shape[AXIS_Y])
    else:  # unreachable: mode validated above
        raise ValueError(f"unknown mode: {mode!r}")

    # compile + warm-up outside the timed region (the reference likewise
    # excludes MPI_Init / cudaMalloc from T_solver via its barrier fences).
    # For --engine auto this is also where runtime RESOURCE_EXHAUSTED
    # degrades down the capacity ladder: the gates are budgets, the
    # allocator is the judge.
    if mode == "single":
        solver, args, engine, result = _warm_with_degradation(
            problem, jdtype, solver, args, engine, auto=requested_auto,
            geometry=geometry, theta=theta,
        )
    else:
        result = solver(*args)
        fence(result)

    if batch > 1 and mode == "single":
        # Chained differential protocol: one jitted dispatch runs `batch`
        # data-dependent solves (an opaque but value-exact perturbation of
        # the RHS defeats CSE without changing any f.p. value); T_solver is
        # the marginal cost (t_batch - t_single)/(batch - 1). This isolates
        # the solve from the fixed per-dispatch host overhead, which the
        # reference's MPI_Wtime bracket around its kernels does not see
        # (poisson_mpi_cuda2.cu:1009-1015).
        chained = _chain_solver(solver, args, batch)
        out = chained(*args)
        fence(out)
        t1s, tbs = [], []
        for _ in range(repeat):
            t0 = time.perf_counter()
            result = solver(*args)
            # timing-protocol fences: the sync IS the measurement — each
            # perf_counter bracket must close on completed device work
            fence(result)  # tpulint: disable=TPU008
            t1s.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            out = chained(*args)
            fence(out)  # tpulint: disable=TPU008
            tbs.append(time.perf_counter() - t0)
        t1 = statistics.median(t1s)
        # Noise floor: under host-load jitter a chained dispatch can
        # measure FASTER than the single one (tb ≤ t1), collapsing the
        # marginal estimate to 0 — a meaningless T_solver that poisons
        # every derived rate (solves/sec → None, GB/s → inf). Fall back
        # to the chained per-dispatch cost for those samples: an upper
        # bound on the marginal cost, strictly positive, and exactly
        # equal in the noise-free regime the protocol targets.
        times = [
            (tb - t1) / (batch - 1) if tb > t1 else tb / batch
            for tb in tbs
        ]
    else:
        times = []
        for _ in range(repeat):
            t0 = time.perf_counter()
            for _ in range(batch):
                result = solver(*args)
            # one fence per measurement (after the batch, not per
            # dispatch): the timing protocol's justified sync
            fence(result)  # tpulint: disable=TPU008
            times.append((time.perf_counter() - t0) / batch)
    timer.add("solver", statistics.median(times))

    return _finish_report(
        problem, shape, dtype, jdtype, engine, result, timer, times,
        lanes=lanes, analytic=geometry is None,
        storage_dtype=storage_dtype, sstep_s=sstep_s,
    )


def _run_recycled(
    problem: Problem,
    dtype: str,
    jdtype,
    repeat: int = 1,
    batch: int = 1,
    recycle: int | None = None,
    warm_start: bool = False,
) -> RunReport:
    """One timed deflated/warm-started solve (``--recycle/--warm-start``).

    Init phase: assembly + (with ``recycle``) one ring-carrying capture
    solve, the host-side Ritz harvest, and the Galerkin projection that
    seeds x0 — the serve shape, where the first request of a bucket pays
    full price and its basis is what later requests deflate against.
    Solver phase: the plain repeat/batch timing protocol over the
    deflated restart. The harvest can decline (ill-conditioned Gram,
    short trace) — the run falls back to the undeflated start and the
    report simply shows cold iterations: basis quality buys iterations,
    never correctness.
    """
    from poisson_ellipse_tpu.ops import assembly
    from poisson_ellipse_tpu.solver import recycle as rec
    from poisson_ellipse_tpu.solver.pcg import pcg

    timer = PhaseTimer()
    with timer.phase("init"):
        a, b, rhs = assembly.assemble(problem, jdtype)
        x0 = None
        if recycle is not None:
            res0, trace0, ring = pcg(
                problem, a, b, rhs, history=True, recycle=int(recycle)
            )
            fence(res0)
            basis = rec.harvest(problem, a, b, trace0, ring)
            seed = res0.w if warm_start else None
            if basis is not None:
                if seed is not None:
                    from poisson_ellipse_tpu.ops.stencil import apply_a

                    h1 = jnp.asarray(problem.h1, rhs.dtype)
                    h2 = jnp.asarray(problem.h2, rhs.dtype)
                    residual = rhs - apply_a(seed, a, b, h1, h2)
                    x0 = rec.deflated_x0(basis, rhs, x0=seed,
                                         residual=residual)
                else:
                    x0 = rec.deflated_x0(basis, rhs)
            if x0 is None:  # declined harvest/projection: undeflated start
                x0 = seed
        elif warm_start:
            res0 = pcg(problem, a, b, rhs)
            fence(res0)
            x0 = res0.w
        # one jit per protocol run, operands re-dispatched every repeat:
        # no donation (timing reuses the inputs), no hoisting (the x0
        # closure IS the capture result this run exists to time)
        solver = jax.jit(  # tpulint: disable=TPU004,TPU006
            lambda a_, b_, rhs_: pcg(problem, a_, b_, rhs_, x0=x0)
        )
        args = (a, b, rhs)
        result = solver(*args)  # compile + warm-up inside init, like every
        fence(result)           # other untimed first dispatch

    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        for _ in range(batch):
            result = solver(*args)
        # one fence per measurement: the timing protocol's justified sync
        fence(result)  # tpulint: disable=TPU008
        times.append((time.perf_counter() - t0) / batch)
    timer.add("solver", statistics.median(times))
    return _finish_report(
        problem, (1, 1), dtype, jdtype, "xla", result, timer, times,
    )


def _warm_with_degradation(problem, jdtype, solver, args, engine: str,
                           auto: bool, geometry=None, theta=None):
    """The first (compile + warm-up) dispatch, with the runtime OOM
    ladder for auto-selected engines.

    The capacity gates are *budgets measured on the bench part*; the
    allocator on the actual device is the judge. When it rules
    RESOURCE_EXHAUSTED on an auto pick, the next-smaller engine is built
    and retried after a short backoff (releasing the failed attempt's
    buffers first), down to xla — which has no capacity gate. An
    explicitly requested engine stays loud, but classified: the CLI maps
    :class:`OutOfMemoryError` to exit code 3.
    """
    while True:
        try:
            result = solver(*args)
            # warm-up fence: the sync marks the end of compile+first
            # dispatch, outside every timed region
            fence(result)  # tpulint: disable=TPU008
            return solver, args, engine, result
        except Exception as e:  # noqa: BLE001 — OOM classified, rest re-raised
            if not is_oom_error(e):
                raise
            if not (auto and engine in _DEGRADE_LADDER[:-1]):
                raise OutOfMemoryError(
                    f"engine {engine!r} hit RESOURCE_EXHAUSTED at "
                    f"warm-up: {e}"
                ) from e
            nxt = _DEGRADE_LADDER[_DEGRADE_LADDER.index(engine) + 1]
            obs_trace.note(
                f"engine {engine} hit RESOURCE_EXHAUSTED at warm-up; "
                f"degrading to {nxt} (backoff {_DEGRADE_BACKOFF_S:g}s)",
                _event="degrade:engine",
                from_engine=engine,
                to_engine=nxt,
            )
            del solver, args  # release the failed attempt before rebuilding
            time.sleep(_DEGRADE_BACKOFF_S)
            # the rebuild IS the degradation ladder: one build per OOM
            # rung, bounded by the ladder length
            solver, args, engine = build_solver(
                # tpulint: disable=TPU013 — one build per OOM rung
                problem, nxt, jdtype, geometry=geometry, theta=theta,
                validate_geometry=False,
            )


def _run_guarded(
    problem: Problem,
    mode: str,
    mesh_shape,
    dtype: str,
    jdtype,
    engine: str,
    timeout: float | None,
    max_recoveries: int,
    geometry=None,
    theta=None,
    storage_dtype: str | None = None,
    sstep_s: int = 4,
) -> RunReport:
    """One guarded (and/or deadlined) solve through
    ``resilience.guard.guarded_solve``. Timing is a plain wall clock
    around the chunked run — resilience trades peak dispatch efficiency
    for survivability, so this is not the protocol the bench numbers
    use (the checkpointed driver takes the same stance)."""
    from poisson_ellipse_tpu.resilience.guard import guarded_solve

    timer = PhaseTimer()
    with timer.phase("init"):
        mesh = resolve_mesh(mesh_shape) if mode == "sharded" else None
        if mode == "sharded" and engine == "auto":
            engine = "xla"
    shape = (
        (mesh.shape[AXIS_X], mesh.shape[AXIS_Y]) if mesh is not None else (1, 1)
    )
    t0 = time.perf_counter()
    guarded = guarded_solve(
        problem, engine, jdtype, mesh=mesh, timeout=timeout,
        max_recoveries=max_recoveries, geometry=geometry, theta=theta,
        storage_dtype=storage_dtype, sstep_s=sstep_s,
    )
    fence(guarded.result)
    t_solve = time.perf_counter() - t0
    timer.add("solver", t_solve)
    report = _finish_report(
        problem, shape, dtype, jdtype, guarded.engine, guarded.result,
        timer, [t_solve], analytic=geometry is None,
        storage_dtype=storage_dtype, sstep_s=sstep_s,
    )
    report.recoveries = [event.kind for event in guarded.recoveries]
    return report


def _run_batched_guarded(
    problem: Problem,
    dtype: str,
    jdtype,
    engine: str,
    lanes: int,
    timeout: float | None,
) -> RunReport:
    """One guarded lane-batched solve through the chunked lane driver
    (``batch.driver.solve_batched``): per-chunk lane health, quarantine
    events on the trace, graceful chunk-boundary timeout. Plain
    wall-clock timing — the resilience stance of ``_run_guarded``."""
    from poisson_ellipse_tpu.batch import solve_batched

    timer = PhaseTimer()
    with timer.phase("init"):
        pass
    t0 = time.perf_counter()
    guarded = solve_batched(
        problem, lanes, engine, jdtype, timeout=timeout,
    )
    fence(guarded.result)
    t_solve = time.perf_counter() - t0
    timer.add("solver", t_solve)
    report = _finish_report(
        problem, (1, 1), dtype, jdtype, engine, guarded.result, timer,
        [t_solve], lanes=lanes,
    )
    report.recoveries = [event.kind for event in guarded.recoveries]
    return report


def _chain_solver(solver, args, n: int):
    """One jitted dispatch running n data-dependent solves.

    Relies on the ``build_solver`` contract that the last arg is the RHS.
    The RHS of solve k+1 is multiplied by (1 + tiny*acc_k) where tiny is
    far below the dtype's machine epsilon relative to any reachable acc,
    so the product is bit-identical to the RHS (iteration counts and
    solutions are unchanged — verified against the published oracles) while
    the data dependence stops XLA deduplicating the solves.
    """
    rhs = args[-1]
    tiny = 1e-30 if jnp.dtype(rhs.dtype).itemsize >= 8 else 1e-12

    def chained(*a):
        r0 = a[-1]

        def one(_i, acc):
            res = solver(*a[:-1], r0 * (1.0 + tiny * acc))
            # jnp.sum: a lane-batched result carries (B,) diffs — the
            # perturbation only needs *a* data-dependent scalar
            return acc + jnp.sum(res.diff).astype(acc.dtype)

        acc = lax.fori_loop(0, n - 1, one, jnp.zeros((), r0.dtype))
        return solver(*a[:-1], r0 * (1.0 + tiny * acc))

    return jax.jit(chained)


def _finish_report(
    problem: Problem,
    shape: tuple[int, int],
    dtype: str,
    jdtype,
    engine: str,
    result,
    timer: PhaseTimer,
    times: list[float],
    timed_iters: int | None = None,
    lanes: int = 1,
    quarantined: int = 0,
    analytic: bool = True,
    storage_dtype: str | None = None,
    sstep_s: int = 4,
) -> RunReport:
    """Shared report tail: L2-vs-analytic, roofline, RunReport assembly.

    timed_iters — iterations the solver phase actually covered when that
    differs from the cumulative count (resumed checkpointed runs); the
    roofline is computed over it, and it is suppressed entirely for a
    resume that had nothing left to run.

    A lane-batched ``result`` (BatchedPCGResult) is reduced to the
    report's scalars — worst-lane iters/diff, all-lanes converged,
    lane-0 L2 — plus the aggregate solves/sec; the single-solve HBM
    roofline does not apply to it.
    """
    solves_per_sec = None
    if hasattr(result, "quarantined"):  # a per-lane BatchedPCGResult
        quarantined = int(jnp.sum(result.quarantined))
        iters = int(jnp.max(result.iters))
        converged = bool(jnp.all(result.converged))
        breakdown = bool(jnp.any(result.breakdown))
        diff = float(jnp.max(result.diff))
        w0 = result.w[0]
        if timer.totals["solver"] > 0:
            solves_per_sec = lanes / timer.totals["solver"]
    else:
        iters = int(result.iters)
        converged = bool(result.converged)
        breakdown = bool(result.breakdown)
        diff = float(result.diff)
        w0 = result.w
    with timer.phase("finalize"):
        # the analytic solution is an ellipse fact; for an arbitrary SDF
        # domain the metric is undefined — reported NaN, never a number
        # that silently compares a different domain's solution to it
        l2 = (
            float(l2_error_vs_analytic(problem, w0)) if analytic
            else float("nan")
        )

    from poisson_ellipse_tpu.harness.roofline import roofline

    n = timed_iters if timed_iters is not None else iters
    roof = (
        roofline(
            problem, engine, n, timer.totals["solver"], jdtype,
            n_devices=shape[0] * shape[1], storage_dtype=storage_dtype,
            sstep_s=sstep_s,
        )
        if n > 0 and lanes == 1 and engine not in BATCHED_ENGINES
        else {"passes_per_iter": 0.0, "hbm_gbps": 0.0, "hbm_peak_frac": None}
    )
    return RunReport(
        problem=problem,
        mesh_shape=shape,
        dtype=dtype,
        engine=engine,
        iters=iters,
        converged=converged,
        breakdown=breakdown,
        diff=diff,
        l2_error=l2,
        t_init=timer.totals["init"],
        t_solver=timer.totals["solver"],
        times=times,
        timed_iters=timed_iters,
        lanes=lanes,
        solves_per_sec=solves_per_sec,
        quarantined=quarantined,
        storage_dtype=storage_dtype,
        **roof,
    )


def _run_checkpointed(
    problem: Problem,
    mode: str,
    mesh_shape,
    dtype: str,
    jdtype,
    engine: str,
    directory: str,
    chunk: int,
) -> RunReport:
    """One checkpointed solve (resumes from ``directory`` if it holds a
    matching checkpoint). Timing here is a plain wall clock around the
    chunked run — a checkpointed solve trades peak dispatch efficiency for
    restartability, so it is not the protocol the bench numbers use."""
    from poisson_ellipse_tpu.solver.checkpoint import CheckpointingSolver

    if engine == "auto":
        engine = "xla"
    if engine not in ("xla", "pallas"):
        raise ValueError(
            "checkpointed runs persist the XLA-loop PCG carry; "
            "--engine must be xla or pallas (the per-op/per-shard stencil "
            f"kernel), got {engine!r}"
        )
    timer = PhaseTimer()
    with timer.phase("init"):
        mesh = resolve_mesh(mesh_shape) if mode == "sharded" else None
        solver = CheckpointingSolver(
            problem, directory, chunk=chunk, dtype=jdtype, stencil=engine,
            mesh=mesh,
        )
    shape = (
        (mesh.shape[AXIS_X], mesh.shape[AXIS_Y]) if mesh is not None else (1, 1)
    )
    with solver:
        # a resume timed from iteration start_k covers only the remaining
        # iterations — the roofline must not divide resumed wall-clock by
        # the cumulative count
        start_k = solver.latest_step() or 0
        t0 = time.perf_counter()
        result = solver.run()
        fence(result)
        t_solve = time.perf_counter() - t0
    timer.add("solver", t_solve)
    return _finish_report(
        problem, shape, dtype, jdtype, engine, result, timer, [t_solve],
        timed_iters=int(result.iters) - start_k,
    )


def _run_native(problem: Problem, repeat: int, threads: int) -> RunReport:
    import jax.numpy as jnp

    from poisson_ellipse_tpu.runtime import solve_native

    times = []
    result = None
    for _ in range(max(repeat, 1)):
        t0 = time.perf_counter()
        result = solve_native(problem, threads=threads)
        times.append(time.perf_counter() - t0)
    l2 = float(l2_error_vs_analytic(problem, jnp.asarray(result.w)))
    return RunReport(
        problem=problem,
        mesh_shape=(1, 1),
        dtype="f64",
        engine="native",
        iters=result.iters,
        converged=result.converged,
        breakdown=result.breakdown,
        diff=result.diff,
        l2_error=l2,
        t_init=0.0,
        t_solver=statistics.median(times),
        times=times,
        threads=threads,
    )
