"""CLI: ``python -m poisson_ellipse_tpu.harness M N [options]``.

Argv contract extends the reference executables' (``argv[1]=M argv[2]=N``,
``stage2-mpi/poisson_mpi_decomp.cpp:470-474``,
``poisson_mpi_cuda2.cu:995-999``; process grid from mpirun → here
``--mesh``). Multiple grids sweep like stage0/1's built-in loops
(``stage0/Withoutopenmp1.cpp:176-196``). ``--eps-sweep`` runs the
fictitious-domain stiffness study of BASELINE.json config 5.

Two observability entries ride the same prog:

- ``--trace FILE`` (or ``POISSON_TRACE=FILE`` in the environment) streams
  the run as structured JSONL — phase spans, per-run report events,
  counters — in the ``obs.trace`` schema.
- ``inspect <engine>`` is a subcommand: static cost accounting for one
  engine (psum/ppermute per iteration from the jaxpr, XLA-estimated
  FLOPs/HBM bytes, the roofline traffic model's columns) with no solve
  executed — ``python -m poisson_ellipse_tpu.harness inspect pipelined
  --mode sharded --mesh 1 2``.
- ``diagnose <engine>`` runs the measured half: one history-enabled
  solve read through ``obs.spectrum`` (Ritz values, κ(M⁻¹A), CG rate,
  predicted iterations, plateaus — verified bit-identical to a plain
  solve), the fenced compile/H2D/solve/D2H phase profile with
  measured-vs-modeled roofline columns (``obs.profile``), and an
  optional OpenMetrics snapshot (``--metrics FILE``) —
  ``python -m poisson_ellipse_tpu.harness diagnose xla --grid 400x600``.
- ``--metrics FILE`` on the main prog exports the run's counters/
  gauges/histograms as a periodically rewritten OpenMetrics snapshot
  (``obs.export``).

The serving surface:

- ``--lanes N`` runs N independent solves inside ONE dispatch via the
  lane-batched engines (real batching — ``--batch`` is only the chained
  TIMING protocol and never puts more work on the chip); reports carry
  aggregate solves/sec and per-lane quarantine counts.
- ``--recycle [CAP]`` / ``--warm-start`` run the Krylov-recycling
  protocol (``solver.recycle`` / ``runtime.solvecache``): one untimed
  ring-carrying capture solve harvests the extremal Ritz deflation
  basis, then the timed solve restarts deflated and/or seeded with the
  capture solution (the semantic-cache-hit shape) — the report's
  ``iters`` is the deflated count, its l2 still checked vs analytic.
- ``warmup`` is the cache subcommand: wire the persistent XLA
  compilation cache and AOT-compile bucketed batched executables so
  arbitrary request sizes hit a warm executable —
  ``python -m poisson_ellipse_tpu.harness warmup --grids 400x600
  --lanes 1,8 --engine both``.
- ``tune`` is the autotuner subcommand (``runtime.autotune``): probe
  the shape's telemetry, score every candidate engine configuration,
  print the chosen config vs the static default with predicted-vs-
  measured columns, and (``--persist``) write the winner next to the
  XLA compile cache for ``--engine auto`` and the serve warm pool to
  consult — ``python -m poisson_ellipse_tpu.harness tune --grid
  400x600 --measure --persist``.
- ``serve`` drives a synthetic request stream through the
  continuous-batching scheduler (``serve.scheduler``): seeded Poisson
  arrivals of mixed shapes, bounded admission with backpressure,
  deadlines at chunk granularity, lane retirement/refill, retry
  ladder, optional crash-safe journal — ``python -m
  poisson_ellipse_tpu.harness serve --requests 20 --grids 10x10,12x12
  --deadline 5 --journal /tmp/journal.json``.
- ``chaos`` is the serving chaos drill (``serve.chaos``): the same
  stream with an injected NaN lane, a fake RESOURCE_EXHAUSTED and a
  kill/restart with journal replay, asserting zero lost / zero
  double-completed / all outcomes classified — ``python -m
  poisson_ellipse_tpu.harness chaos --requests 50 --seed 0``.
- ``fleet`` is the replicated-serving drill (``fleet.FleetRouter``):
  the stream routed over ``--replicas`` scheduler replicas by
  compile-bucket affinity, with lease health checks and
  ``--kill-replica-at`` arming a mid-stream SIGKILL whose journal
  hands off to the survivors — ``python -m poisson_ellipse_tpu.harness
  fleet --replicas 3 --requests 24 --kill-replica-at 8``. SIGTERM
  drains ``serve``/``fleet`` gracefully: stop admitting, finish
  in-flight, flush the trace, exit 0.
- ``grad`` is the differentiable-solving drill (``diff/``): an
  end-to-end inverse workload — ``--workload ellipse`` recovers
  perturbed ellipse parameters from the solution they produced,
  ``--workload source`` a per-node source field — driven by
  implicit-function-theorem adjoints (one extra PCG per gradient) —
  ``python -m poisson_ellipse_tpu.harness grad --workload ellipse
  --engine mg-pcg``. Exit 0 iff the workload's acceptance holds.

And the resilience surface:

- ``--guard`` routes the solve through ``resilience.guard`` (chunked
  execution, per-chunk health word, recovery ladder); ``--timeout S``
  implies it and cancels gracefully at a chunk boundary, emitting the
  partial trace instead of hanging.
- ``inject <fault>`` is the chaos subcommand: run a guarded solve with a
  deterministic fault (nan / breakdown / stagnation / halo / oom)
  injected at an exact iteration and report the recovery —
  ``python -m poisson_ellipse_tpu.harness inject nan 40 40 --at 10``.
- Exit codes are a contract: 0 converged, 1 iteration cap without
  convergence, 2 diverged (breakdown / recovery exhausted; also invalid
  invocations, per argparse convention), 3 device out-of-memory with no
  engine left to degrade to, 4 ``--timeout`` exceeded, 5 shed at
  admission by the serving layer (backpressure; retry after the hint),
  8 geometry rejected by the admissibility gate (``--geometry`` with a
  malformed/empty/under-resolved spec or an inadmissible operator —
  classified before any device dispatch), 9 every fleet replica down
  or draining (``FleetUnavailableError`` — no admission path left).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

from poisson_ellipse_tpu.harness.run import (
    DTYPES,
    resolve_dtype,
    resolve_mesh,
    run_once,
)
from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs import metrics as obs_metrics
from poisson_ellipse_tpu.obs import trace as obs_trace
from poisson_ellipse_tpu.resilience.errors import SolveError
from poisson_ellipse_tpu.runtime.native import NativeBuildError
from poisson_ellipse_tpu.solver.engine import ENGINES

EXIT_CODES_HELP = (
    "exit codes (contract): 0 converged; 1 iteration cap reached without "
    "convergence; 2 diverged — breakdown or recovery budget exhausted "
    "(also invalid invocations, per argparse convention); 3 device "
    "out-of-memory with no engine left to degrade to; 4 --timeout "
    "exceeded (partial trace artifact emitted); 5 shed at admission by "
    "the serving layer (backpressure — resubmit after retry_after_s); "
    "6 silent data corruption detected by the ABFT checks and not "
    "cleared by rollback-and-rerun (persistent SDC source); 7 mesh "
    "device lost with no degraded mesh left to resume on; 8 geometry "
    "rejected by the admissibility gate (malformed/empty/under-resolved "
    "spec or inadmissible operator — classified BEFORE any device "
    "dispatch); 9 every serving-fleet replica down or draining — no "
    "admission path left (FleetUnavailableError; resubmit after "
    "retry_after_s once a replica rejoins)."
)


class _SigtermDrain:
    """SIGTERM → graceful drain for the serving subcommands.

    The handler only sets a flag; the serve loop checks it between
    arrivals and switches to drain mode (stop admitting, finish or
    journal in-flight, flush metrics/trace, exit 0) instead of dying
    mid-stream with the trace tail unflushed. Installed around the
    loop and restored on exit; a non-main-thread caller (tests driving
    ``main()`` from a worker) simply gets no handler, never an error.
    """

    def __init__(self):
        self.requested = False
        self._prev = None
        self._installed = False

    def _handle(self, signum, frame):
        self.requested = True

    def __enter__(self):
        import signal

        try:
            self._prev = signal.signal(signal.SIGTERM, self._handle)
            self._installed = True
        except ValueError:  # not the main thread: no handler, no error
            pass
        return self

    def __exit__(self, *exc):
        import signal

        if self._installed:
            signal.signal(signal.SIGTERM, self._prev)
        return False


def _parse_grid(spec: str | None, default=(40, 40)) -> tuple[int, int]:
    """One ``MxN`` grid spec (the sweep syntax's single-grid form), or
    ``default`` when the flag was not given at all. Raises ValueError on
    malformed input — an EMPTY spec included (a trailing comma in a
    --grids list must error, not silently inject the default grid) —
    which the subcommands catch into their curated exit-2 path."""
    if spec is None:
        return default
    m, _, n = spec.lower().partition("x")
    return (int(m), int(n or m))


def _parse_grids(args) -> list[tuple[int, int]]:
    if args.M is not None:
        return [(args.M, args.N if args.N is not None else args.M)]
    if args.grids:
        return [_parse_grid(spec) for spec in args.grids.split(",")]
    return [(40, 40)]


def _run_threads_sweep(
    problem: Problem, counts: list[int], repeat: int, as_json: bool
) -> int:
    """The stage1 in-run OpenMP sweep: one native solve per thread count,
    reported as the reference's table 2 (threads / iters / T / speedup vs
    the sweep's first count; ``stage1-openmp/Withopenmp1.cpp:205-229``
    loops ``omp_set_num_threads(t)`` around the same solve)."""
    if not counts:
        raise ValueError("--threads-sweep needs at least one thread count")
    reports = [
        run_once(problem, mode="native", threads=t, repeat=repeat)
        for t in counts
    ]
    base = reports[0].t_solver
    if as_json:
        for rep in reports:
            rec = rep.json_dict()
            rec["speedup_vs_first"] = round(base / rep.t_solver, 3)
            print(json.dumps(rec))
    else:
        print(
            f"Threads sweep {problem.M}x{problem.N} (native f64, "
            f"delta={problem.delta:g}):"
        )
        print("  threads    iters    T_solver(s)   speedup")
        for t, rep in zip(counts, reports):
            print(
                f"  {t:7d}  {rep.iters:7d}  {rep.t_solver:12.4f}  "
                f"{base / rep.t_solver:8.2f}"
            )
        print()
    return 0 if all(r.converged for r in reports) else 1


def _run_inspect(argv: list[str]) -> int:
    """The ``inspect`` subcommand: static cost accounting per engine."""
    ap = argparse.ArgumentParser(
        prog="python -m poisson_ellipse_tpu.harness inspect",
        description="Static cost accounting for one solver engine: "
        "collectives per iteration read from the jaxpr, XLA-estimated "
        "FLOPs/HBM bytes, and the roofline traffic model side by side. "
        "No solve is executed.",
    )
    ap.add_argument(
        "engine",
        help=f"engine to inspect (single-chip: {', '.join(ENGINES[1:])}; "
        "sharded via --mode sharded: xla, pallas, fused, pipelined, "
        "sstep — sstep reports per-BODY counts (1 psum + 4 ppermute per "
        "s iterations) alongside the per-iteration division",
    )
    ap.add_argument(
        "--mode", choices=("single", "sharded"), default="single",
        help="single-device engine or the mesh-sharded composition",
    )
    ap.add_argument(
        "--mesh", type=int, nargs=2, metavar=("PX", "PY"),
        help="mesh shape for --mode sharded (default: all devices)",
    )
    ap.add_argument("--grid", help="MxN grid to trace at (default 40x40)")
    ap.add_argument("--dtype", choices=sorted(DTYPES), default="f32")
    ap.add_argument(
        "--storage-dtype", choices=("bf16", "f16", "f32"), default=None,
        help="trace the narrow-storage build: the modeled HBM bytes/iter "
        "column shows the storage-width byte bill (bf16 under f32 = the "
        "~2x cut)",
    )
    ap.add_argument(
        "--sstep-s", type=int, choices=(2, 4), default=4,
        help="s-step block size for the sstep engines",
    )
    ap.add_argument(
        "--no-xla-cost", action="store_true",
        help="skip the XLA compile + cost analysis (jaxpr counts only)",
    )
    ap.add_argument("--json", action="store_true", help="one JSON line")
    args = ap.parse_args(argv)

    from poisson_ellipse_tpu.obs import static_cost

    try:
        grid = _parse_grid(args.grid)
        report = static_cost.engine_report(
            Problem(M=grid[0], N=grid[1]),
            engine=args.engine,
            dtype=resolve_dtype(args.dtype),
            mode=args.mode,
            mesh_shape=tuple(args.mesh) if args.mesh else None,
            with_xla_cost=not args.no_xla_cost,
            storage_dtype=args.storage_dtype,
            sstep_s=args.sstep_s,
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(report))
    else:
        print(static_cost.render_report(report))
    obs_trace.event("inspect", **report)
    return 0


def _run_inject(argv: list[str]) -> int:
    """The ``inject`` subcommand: one guarded solve with a deterministic
    fault, reporting the recovery — the recovery paths stay exercised
    from the command line, not only from the test matrix."""
    from poisson_ellipse_tpu.resilience import faultinject
    from poisson_ellipse_tpu.resilience.guard import guarded_solve

    ap = argparse.ArgumentParser(
        prog="python -m poisson_ellipse_tpu.harness inject",
        description="Fault-injection harness: run a guarded solve with "
        "one deterministic fault (resilience.faultinject) and report the "
        "recovery ladder's actions. " + EXIT_CODES_HELP,
    )
    ap.add_argument(
        "fault",
        # device_loss/straggler are mesh-level dispatch faults — they
        # belong to the meshguard/chaos drills, not the single-solve
        # guard this subcommand runs
        choices=sorted(
            set(faultinject.FAULT_KINDS) - {"device_loss", "straggler"}
        ),
        help="fault class to inject (see resilience.faultinject)",
    )
    ap.add_argument("M", type=int, nargs="?", default=40)
    ap.add_argument("N", type=int, nargs="?", default=None)
    ap.add_argument(
        "--at", type=int, default=10, metavar="K",
        help="iteration to inject at (guard chunks stop exactly there)",
    )
    ap.add_argument(
        "--field", default=None,
        help="carry field to corrupt (nan/halo faults; default r)",
    )
    ap.add_argument(
        "--persistent", action="store_true",
        help="re-fire the fault on every visit instead of one-shot — "
        "forces the guard up the ladder and into the classified error",
    )
    ap.add_argument(
        "--engine", default="xla",
        choices=("xla", "pallas", "pipelined", "pipelined-pallas",
                 "mg-pcg", "cheb-pcg", "fmg"),
        help="chunk-steppable engine to guard (carry faults need one); "
        "the multigrid engines walk the mg->cheb->diag fallback ladder, "
        "and fmg chunk-steps its verification handoff loop",
    )
    ap.add_argument("--dtype", choices=sorted(DTYPES), default="f32")
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--max-recoveries", type=int, default=3)
    ap.add_argument("--timeout", type=float, default=None)
    ap.add_argument("--delta", type=float, default=1e-6)
    ap.add_argument("--trace", metavar="FILE", help="JSONL trace sink")
    ap.add_argument("--json", action="store_true", help="one JSON line")
    args = ap.parse_args(argv)

    if args.trace:
        obs_trace.start(args.trace)
    # everything past tracer start sits under the finally that stops it:
    # an invalid fault/problem spec must not leak the process-global
    # tracer or exit with a raw traceback instead of the contract's 2
    try:
        try:
            problem = Problem(
                M=args.M, N=args.N if args.N is not None else args.M,
                delta=args.delta,
            )
            plan = faultinject.FaultPlan(faultinject.Fault(
                args.fault, at_iter=args.at, field=args.field,
                persistent=args.persistent,
            ))
            guarded = guarded_solve(
                problem, args.engine, resolve_dtype(args.dtype),
                chunk=args.chunk, max_recoveries=args.max_recoveries,
                timeout=args.timeout, faults=plan,
            )
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        except SolveError as e:
            record = {
                "fault": args.fault, "at": args.at, "engine": args.engine,
                "aborted": e.classification, "iters": e.iters,
            }
            obs_trace.event("inject_report", **record)
            if args.json:
                print(json.dumps(record))
            else:
                print(
                    f"fault {args.fault}@{args.at}: solve aborted — "
                    f"{e.classification} ({e}); exit {e.exit_code}",
                    file=sys.stderr,
                )
            return e.exit_code
        return _report_inject(args, guarded)
    finally:
        # stop LAST: every inject_report above must land in the trace
        if args.trace:
            obs_trace.stop()


def _report_inject(args, guarded) -> int:
    result = guarded.result
    record = {
        "fault": args.fault, "at": args.at,
        "engine_requested": args.engine, "engine_final": guarded.engine,
        "dtype_final": guarded.dtype,
        "iters": int(result.iters), "converged": bool(result.converged),
        "recoveries": [e.kind for e in guarded.recoveries],
    }
    obs_trace.event("inject_report", **record)
    if args.json:
        print(json.dumps(record))
    else:
        kinds = ", ".join(e.kind for e in guarded.recoveries) or "none"
        print(
            f"fault {args.fault}@{args.at} on {args.engine}: "
            f"{'converged' if record['converged'] else 'NOT converged'} "
            f"after {record['iters']} iterations "
            f"(recoveries: {kinds}; finished on {guarded.engine}"
            + (f", {guarded.dtype}" if guarded.dtype else "")
            + ")"
        )
    return 0 if record["converged"] else 1


def _run_diagnose(argv: list[str]) -> int:
    """The ``diagnose`` subcommand: spectrum + profile + export, one report.

    Runs one history-enabled solve (``obs.convergence``) and reads the
    spectral story out of it (``obs.spectrum``: Ritz values, κ(M⁻¹A),
    CG rate, predicted iterations, plateaus), next to a plain solve that
    pins the telemetry's zero-perturbation contract (bit-identical
    iterates — diagnosing a solver must not change it), plus the fenced
    compile/H2D/solve/D2H phase profile with the measured-vs-modeled
    roofline columns (``obs.profile``), and optionally an OpenMetrics
    snapshot (``--metrics FILE``) so the numbers land where a scraper
    can find them.
    """
    import numpy as np

    from poisson_ellipse_tpu.solver.engine import (
        HISTORY_ENGINES,
        build_solver,
    )

    ap = argparse.ArgumentParser(
        prog="python -m poisson_ellipse_tpu.harness diagnose",
        description="Solver diagnostics in one report: Lanczos spectral "
        "estimates (kappa, CG rate, predicted iterations, plateaus) from "
        "the on-device convergence trace, fenced compile/H2D/solve/D2H "
        "phase profiling with measured-vs-modeled roofline columns, and "
        "OpenMetrics export. The history solve is verified bit-identical "
        "to a plain solve: diagnosing never changes the solver.",
    )
    ap.add_argument(
        "engine", nargs="?", default="auto",
        help="history-capable engine to diagnose "
        f"({', '.join(HISTORY_ENGINES)}; auto resolves to xla)",
    )
    ap.add_argument("--grid", help="MxN grid (default 40x40)")
    ap.add_argument("--dtype", choices=sorted(DTYPES), default="f32")
    ap.add_argument("--delta", type=float, default=1e-6)
    ap.add_argument(
        "--repeat", type=int, default=3,
        help="solve-phase repetitions for the profile median",
    )
    ap.add_argument(
        "--no-profile", action="store_true",
        help="skip the phase profile (spectrum + contract check only)",
    )
    ap.add_argument(
        "--no-xla-cost", action="store_true",
        help="skip the XLA cost analysis columns of the profile",
    )
    ap.add_argument(
        "--metrics", metavar="FILE",
        help="write the diagnostic numbers as an OpenMetrics snapshot "
        "(obs.export; atomic write)",
    )
    ap.add_argument("--trace", metavar="FILE", help="JSONL trace sink")
    ap.add_argument("--json", action="store_true", help="one JSON line")
    args = ap.parse_args(argv)

    if args.trace:
        obs_trace.start(args.trace)
    try:
        from poisson_ellipse_tpu.obs import profile as obs_profile
        from poisson_ellipse_tpu.obs import spectrum as obs_spectrum

        try:
            grid = _parse_grid(args.grid)
            problem = Problem(M=grid[0], N=grid[1], delta=args.delta)
            jdtype = resolve_dtype(args.dtype)
            if args.repeat < 1:
                # checked HERE, not after two solves have been paid for:
                # profile_engine would reject it with the same message
                raise ValueError("repeat must be >= 1")
            if args.engine not in HISTORY_ENGINES:
                raise ValueError(
                    f"engine {args.engine!r} records no history; diagnose "
                    f"covers {', '.join(HISTORY_ENGINES)}"
                )
            if args.metrics:
                from poisson_ellipse_tpu.obs.export import MetricsExporter

                # fail FAST on an unwritable path — same exit-2 contract
                # as the main prog's --metrics, checked BEFORE the
                # solves below are paid for (overwritten with the real
                # snapshot at the end)
                err = MetricsExporter(
                    args.metrics, registry=obs_metrics.MetricsRegistry()
                ).try_write()
                if err is not None:
                    raise ValueError(
                        f"cannot write --metrics {args.metrics}: {err}"
                    )
            # the contract half: history must not perturb one bit
            solver, solver_args, engine = build_solver(
                problem, args.engine, jdtype, history=True
            )
            result, trace = solver(*solver_args)
            plain_solver, plain_args, _ = build_solver(
                problem, engine, jdtype
            )
            plain = plain_solver(*plain_args)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        bit_identical = bool(
            int(plain.iters) == int(result.iters)
            and float(plain.diff) == float(result.diff)
            and np.array_equal(np.asarray(plain.w), np.asarray(result.w))
        )
        spec = obs_spectrum.spectrum_report(
            trace, delta=problem.delta, actual_iters=int(result.iters)
        )
        # the widened Lanczos interval — exactly what mg.cheby's setup
        # consumes (one shared helper, obs.spectrum.eigenvalue_bounds)
        bounds = obs_spectrum.eigenvalue_bounds(trace)
        spec["eigenvalue_bounds"] = list(bounds) if bounds else None
        diag_spec = None
        if engine in ("mg-pcg", "cheb-pcg"):
            # the yardstick: the preconditioner's kappa(M^-1 A) is only
            # meaningful NEXT TO the diagonal baseline it displaced
            diag_solver, diag_args, _ = build_solver(
                problem, "xla", jdtype, history=True
            )
            diag_result, diag_trace = diag_solver(*diag_args)
            diag_spec = obs_spectrum.spectrum_report(
                diag_trace, delta=problem.delta,
                actual_iters=int(diag_result.iters),
            )
        prof = None
        if not args.no_profile:
            prof = obs_profile.profile_engine(
                problem, engine, jdtype, repeat=args.repeat,
                with_xla_cost=not args.no_xla_cost,
            )
        record = {
            "engine": engine,
            "grid": list(grid),
            "dtype": args.dtype,
            "iters": int(result.iters),
            "converged": bool(result.converged),
            "bit_identical": bit_identical,
            "spectrum": spec,
            "profile": prof,
        }
        if diag_spec is not None:
            record["diag_spectrum"] = diag_spec
        if args.metrics:
            from poisson_ellipse_tpu.obs.export import MetricsExporter

            reg = obs_metrics.MetricsRegistry()
            reg.gauge("diagnose_iters").set(record["iters"])
            if spec.get("available"):
                reg.gauge("diagnose_kappa").set(spec["kappa"])
                reg.gauge("diagnose_cg_rate").set(spec["cg_rate"])
                if spec.get("predicted_iters") is not None:
                    reg.gauge("diagnose_predicted_iters").set(
                        spec["predicted_iters"]
                    )
            if prof is not None:
                hist = reg.histogram("diagnose_solve_seconds")
                hist.observe(prof["t_solve_s"])
                reg.gauge("diagnose_compile_seconds").set(
                    prof["t_compile_s"]
                )
                if prof.get("hbm_gbps") is not None:
                    reg.gauge("diagnose_hbm_gbps").set(prof["hbm_gbps"])
            record["metrics_path"] = MetricsExporter(
                args.metrics, registry=reg
            ).write()
        obs_trace.event("diagnose_report", **record)
        if args.json:
            print(json.dumps(record))
        else:
            print(
                f"diagnose {engine} {grid[0]}x{grid[1]} ({args.dtype}): "
                f"{record['iters']} iterations, "
                f"{'converged' if record['converged'] else 'NOT converged'}; "
                "history-enabled iterates "
                + (
                    "BIT-IDENTICAL to the plain solve"
                    if bit_identical
                    else "DIFFER from the plain solve (contract violation)"
                )
            )
            print(obs_spectrum.render_report(spec))
            if spec.get("eigenvalue_bounds"):
                lo, hi = spec["eigenvalue_bounds"]
                print(
                    f"  chebyshev interval    [{lo:.6g}, {hi:.6g}]  "
                    "(widened Lanczos bounds — what mg.cheby consumes)"
                )
            if diag_spec is not None and diag_spec.get("available"):
                line = (
                    f"  vs diag-PCG           kappa {diag_spec['kappa']:.6g}"
                    f" in {diag_spec['iters']} iterations"
                )
                if spec.get("available"):
                    line += (
                        f" -> {diag_spec['kappa'] / spec['kappa']:.1f}x "
                        "kappa reduction"
                    )
                print(line)
            if prof is not None:
                print(obs_profile.render_profile(prof))
            if args.metrics:
                print(f"metrics snapshot: {record['metrics_path']}")
        if not bit_identical:
            return 2
        return 0 if record["converged"] else 1
    finally:
        if args.trace:
            obs_trace.stop()


def _run_tune(argv: list[str]) -> int:
    """The ``tune`` subcommand: the closed-loop autotuner for one shape.

    Runs ``runtime.autotune`` end to end — telemetry probe (κ and
    Ritz-predicted iterations via ``obs.spectrum``, measured GB/s via
    ``obs.profile``), candidate scoring, winner selection with the
    static default as the anchor it must beat — and prints the chosen
    config against the static default with predicted-vs-measured
    columns. ``--persist`` writes the winner into the registry next to
    the XLA compile cache, where ``build_solver(engine="auto")`` and
    the serve warm pool consult it at admission.
    """
    ap = argparse.ArgumentParser(
        prog="python -m poisson_ellipse_tpu.harness tune",
        description="Telemetry-driven autotuning for one shape: score "
        "engine configurations from measured telemetry (obs.spectrum "
        "Ritz-predicted iterations, obs.profile GB/s), pick a winner "
        "that provably does not lose to the static default, and "
        "optionally persist it in the checkout (.autotune/) for "
        "engine='auto' and the serve warm pool to consult.",
    )
    ap.add_argument("--grid", help="MxN grid to tune (default 40x40)")
    ap.add_argument("--dtype", choices=sorted(DTYPES), default="f32")
    ap.add_argument(
        "--storage-dtype", choices=("bf16", "f16", "f32"), default=None,
        help="tune the narrow-storage key (separate registry entry: a "
        "narrow executable is a different accuracy contract)",
    )
    ap.add_argument("--delta", type=float, default=1e-6)
    ap.add_argument(
        "--geometry", metavar="SPEC",
        help="tune for an SDF domain (JSON spec file or inline JSON); "
        "the key carries the geometry fingerprint",
    )
    ap.add_argument(
        "--measure", action="store_true",
        help="wall-clock the winner against the static default and "
        "demote a loser before persisting (the measured half of the "
        "never-loses contract; predictions alone decide otherwise)",
    )
    ap.add_argument(
        "--persist", action="store_true",
        help="write the winner into the tuned-config registry "
        "(<repo>/.autotune/registry.json)",
    )
    ap.add_argument(
        "--registry", metavar="FILE", default=None,
        help="registry path override "
        "(default: <repo>/.autotune/registry.json)",
    )
    ap.add_argument("--trace", metavar="FILE", help="JSONL trace sink")
    ap.add_argument("--json", action="store_true", help="one JSON line")
    args = ap.parse_args(argv)

    from poisson_ellipse_tpu.runtime import autotune

    if args.trace:
        obs_trace.start(args.trace)
    try:
        try:
            grid = _parse_grid(args.grid)
            problem = Problem(M=grid[0], N=grid[1], delta=args.delta)
            jdtype = resolve_dtype(args.dtype)
            geometry = _geometry_spec(args.geometry)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        except OSError as e:
            print(f"error: cannot read --geometry: {e}", file=sys.stderr)
            return 2
        except SolveError as e:
            print(f"error: {e.classification}: {e}", file=sys.stderr)
            return e.exit_code
        try:
            registry = (
                autotune.TuneRegistry(args.registry).load()
                if args.registry else None
            )
            report = autotune.tune(
                problem, jdtype, storage_dtype=args.storage_dtype,
                geometry=geometry, registry=registry, persist=args.persist,
                measure=args.measure,
            )
        except SolveError as e:
            # classified failures inside the loop itself (geometry
            # assembly, telemetry probe, measurement solves) exit with
            # the same curated contract as `harness run`
            print(f"error: {e.classification}: {e}", file=sys.stderr)
            return e.exit_code
        if args.json:
            print(json.dumps(report))
            return 0
        chosen = report["chosen"]
        tel = report["telemetry"]
        print(
            f"tune {grid[0]}x{grid[1]} ({args.dtype}"
            + (f", storage {args.storage_dtype}" if args.storage_dtype
               else "")
            + f"): key {report['key']}"
        )
        kappa = tel.get("kappa")
        print(
            "telemetry: kappa "
            + (f"{kappa:.6g}" if kappa is not None else "n/a")
            + f", Ritz-predicted diag iters {tel.get('predicted_iters')}"
            + (f", measured {tel['gbps']:.0f} GB/s" if tel.get("gbps")
               else "")
        )
        print(
            "  candidate            knobs                         "
            "pred iters   pred T(s)    meas T(s)"
        )
        for row in report["candidates"]:
            # the chosen knobs carry the serve chunk on top of the
            # candidate's own — subset match identifies the winner row
            marker = "->" if (
                row["engine"] == chosen["engine"]
                and all(chosen["knobs"].get(k) == v
                        for k, v in row["knobs"].items())
            ) else "  "
            measured = ""
            if row["engine"] == chosen["engine"] and chosen.get(
                    "measured_t_s") is not None:
                measured = f"{chosen['measured_t_s']:12.5f}"
            elif row["engine"] == chosen.get("static_engine") and chosen.get(
                    "static_measured_t_s") is not None:
                measured = f"{chosen['static_measured_t_s']:12.5f}"
            knobs = ",".join(f"{k}={v}" for k, v in row["knobs"].items())
            print(
                f"{marker} {row['engine']:18s} {knobs:28s} "
                f"{row['predicted_iters']:10.1f} "
                f"{row['predicted_t_s']:11.6f} {measured}"
            )
        static = chosen.get("static_engine")
        if chosen["engine"] == static:
            print(
                f"chosen: the static default ({static}) stands"
                + ("; predicted winner DEMOTED after measurement"
                   if report["demoted_to_static"] else "")
            )
        else:
            print(
                f"chosen: {chosen['engine']} over static default "
                f"{static}"
                + (" (measured winner)" if chosen.get("measured_t_s")
                   is not None else " (predicted winner)")
                + ("; DEMOTED to static after measurement"
                   if report["demoted_to_static"] else "")
            )
        if report.get("registry_path"):
            print(f"persisted: {report['registry_path']}")
        return 0
    finally:
        obs_metrics.REGISTRY.emit()
        obs_metrics.REGISTRY.reset()
        if args.trace:
            obs_trace.stop()


def _run_warmup(argv: list[str]) -> int:
    """The ``warmup`` subcommand: pre-fill the compilation caches.

    AOT-compiles the batched engines' bucket executables for the
    requested grids/lane counts (``runtime.compile_cache``) — into the
    persistent cache ``main`` turned on — so a serving worker's first
    real request is a cache hit instead of a cold compile. Hit/miss counts
    land on the trace (``cache:hit`` / ``cache:miss`` events).
    """
    import jax

    from poisson_ellipse_tpu.runtime import compile_cache

    ap = argparse.ArgumentParser(
        prog="python -m poisson_ellipse_tpu.harness warmup",
        description="Warm the compilation caches: AOT-compile bucketed "
        "executables for the batched engines, keyed (engine, "
        "grid-bucket, dtype, lane-bucket). "
        "Arbitrary request sizes then hit a warm executable by "
        "pad-and-mask embedding.",
    )
    ap.add_argument(
        "--grids", default="40x40",
        help="comma list of MxN grids to warm buckets for",
    )
    ap.add_argument(
        "--lanes", default="1,8",
        help="comma list of lane counts (each rounds up to its bucket)",
    )
    ap.add_argument(
        "--engine", default="batched",
        choices=("batched", "batched-pipelined", "both"),
    )
    ap.add_argument("--dtype", choices=sorted(DTYPES), default="f32")
    ap.add_argument("--trace", metavar="FILE", help="JSONL trace sink")
    ap.add_argument("--json", action="store_true", help="one JSON line")
    args = ap.parse_args(argv)

    if args.trace:
        obs_trace.start(args.trace)
    try:
        # main() already turned the persistent cache on; report where
        cache_dir = (
            jax.config.jax_compilation_cache_dir
            if jax.config.jax_enable_compilation_cache else None
        )
        engines = (
            ("batched", "batched-pipelined")
            if args.engine == "both"
            else (args.engine,)
        )
        try:
            grids = [_parse_grid(spec) for spec in args.grids.split(",")]
            lane_counts = [int(x) for x in args.lanes.split(",")]
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        pool = compile_cache.warm_pool()
        rows = []
        dtype = resolve_dtype(args.dtype)
        for engine in engines:
            for grid in grids:
                for lanes in lane_counts:
                    entry = pool.warmup(engine, grid, dtype, lanes)
                    rows.append({
                        "engine": engine,
                        "grid": list(grid),
                        "bucket": list(entry.bucket),
                        "lanes": lanes,
                        "lane_bucket": entry.lanes,
                        "compile_s": round(entry.compile_s, 4),
                    })
        record = {
            "persistent_dir": cache_dir,
            "warmed": rows,
            "hits": pool.hits,
            "misses": pool.misses,
        }
        obs_trace.event("warmup_report", **record)
        if args.json:
            print(json.dumps(record))
        else:
            for row in rows:
                print(
                    f"warm {row['engine']:18s} {row['grid'][0]}x"
                    f"{row['grid'][1]} -> bucket {row['bucket'][0]}x"
                    f"{row['bucket'][1]} lanes {row['lanes']} -> "
                    f"{row['lane_bucket']}  compile "
                    + (
                        f"{row['compile_s']:.3f}s"
                        if row["compile_s"] else "cached"
                    )
                )
            print(
                f"warm pool: {pool.misses} compiled, {pool.hits} already "
                "warm"
                + (f"; persistent cache at {cache_dir}" if cache_dir else "")
            )
        return 0
    finally:
        if args.trace:
            obs_trace.stop()


def _run_serve(argv: list[str]) -> int:
    """The ``serve`` subcommand: a synthetic arrival stream through the
    continuous-batching scheduler — the serving layer exercised from
    the command line, lifecycle events on the trace, latency quantiles
    in the report."""
    import random
    import time as _time

    from poisson_ellipse_tpu.serve import Scheduler

    ap = argparse.ArgumentParser(
        prog="python -m poisson_ellipse_tpu.harness serve",
        description="Continuous-batching serve drill: drive a seeded "
        "Poisson arrival stream of mixed shapes through the scheduler "
        "(bounded admission, chunk-boundary lane retirement/refill, "
        "deadlines, retry ladder, optional crash-safe journal). "
        "exit code = the WORST per-request outcome of the stream "
        "(numerically highest of the per-request contract): 0 every "
        "request completed; 1 iteration cap; 2 failed/diverged (also "
        "invalid invocations, per argparse convention); 4 deadline "
        "missed; 5 shed at admission (backpressure — resubmit after "
        "retry_after_s).",
    )
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument(
        "--grids", default="10x10,12x12",
        help="comma list of MxN request shapes, mixed by the seeded RNG",
    )
    ap.add_argument(
        "--rate", type=float, default=200.0,
        help="Poisson arrival rate (requests/second of wall clock)",
    )
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument(
        "--deadline", type=float, default=None, metavar="SECONDS",
        help="per-request deadline (admission sheds infeasible asks; "
        "mid-solve expiry cancels at a chunk boundary, partial result)",
    )
    ap.add_argument("--retries", type=int, default=1)
    ap.add_argument(
        "--journal", metavar="FILE",
        help="crash-safe request journal; admitted-but-unfinished "
        "requests replay on the next start (see --replay)",
    )
    ap.add_argument(
        "--replay", action="store_true",
        help="replay the journal's unfinished requests before the new "
        "stream (requires --journal)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", choices=sorted(DTYPES), default="f32")
    ap.add_argument(
        "--warm-start", action="store_true",
        help="per-bucket solve-cache pools (runtime.solvecache): "
        "consult on admission, deposit on retirement; replays always "
        "run cold (solvecache_hit_total / recycle:hit on the trace)",
    )
    ap.add_argument("--trace", metavar="FILE", help="JSONL trace sink")
    ap.add_argument(
        "--metrics", metavar="FILE",
        help="OpenMetrics snapshot of the serving counters/histograms",
    )
    ap.add_argument("--json", action="store_true", help="one JSON line")
    args = ap.parse_args(argv)

    if args.trace:
        obs_trace.start(args.trace)
    try:
        try:
            if args.replay and not args.journal:
                raise ValueError("--replay needs --journal")
            grids = [_parse_grid(spec) for spec in args.grids.split(",")]
            if args.requests < (0 if args.replay else 1):
                # --requests 0 is the pure-replay restart: drain the
                # journal's unfinished admissions, admit nothing new
                raise ValueError(
                    "--requests must be >= 1 (0 allowed with --replay)"
                )
            if args.rate <= 0:
                raise ValueError("--rate must be > 0 requests/second")
            sched = Scheduler(
                lanes=args.lanes, chunk=args.chunk,
                queue_capacity=args.queue_capacity,
                dtype=resolve_dtype(args.dtype),
                max_retries=args.retries, journal=args.journal,
                keep_solutions=False, warm_start=args.warm_start,
            )
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        replayed = sched.replay() if args.replay else 0
        rng = random.Random(args.seed)
        t0 = _time.monotonic()
        # results are harvested through collect() as the stream runs —
        # the eviction hand-off a long-lived server needs (the
        # scheduler's buffer stays bounded by the in-flight window)
        results: dict = {}
        drained_early = False
        with _SigtermDrain() as term:
            for _ in range(args.requests):
                if term.requested:
                    # SIGTERM: stop admitting, finish (or journal) the
                    # in-flight work, flush, exit 0 — the trace tail
                    # survives the shutdown instead of dying with it
                    drained_early = True
                    sched.begin_drain()
                    obs_trace.event(
                        "serve:sigterm-drain", queued=len(sched.queue),
                    )
                    break
                M, N = rng.choice(grids)
                sched.submit(
                    Problem(M=M, N=N), deadline_s=args.deadline,
                )
                _time.sleep(min(rng.expovariate(args.rate), 0.05))
                sched.step()
                results.update(sched.collect())
            sched.drain()
            results.update(sched.collect())
        wall = _time.monotonic() - t0
        counts: dict[str, int] = {}
        for res in results.values():
            counts[res.outcome] = counts.get(res.outcome, 0) + 1
        completed = counts.get("completed", 0)
        lat = obs_metrics.REGISTRY.histogram("time_in_queue_seconds")
        record = {
            "requests": args.requests,
            "replayed": replayed,
            "outcomes": counts,
            "solves_per_sec": round(completed / wall, 2) if wall else None,
            "queue_p50_s": lat.quantile(0.5),
            "queue_p99_s": lat.quantile(0.99),
            "wall_s": round(wall, 4),
            "drained_on_sigterm": drained_early,
        }
        obs_trace.event("serve_report", **record)
        if args.metrics:
            from poisson_ellipse_tpu.obs.export import MetricsExporter

            err = MetricsExporter(args.metrics).try_write()
            if err is not None:
                print(
                    f"warning: metrics snapshot failed: {err}",
                    file=sys.stderr,
                )
        if args.json:
            print(json.dumps(record))
        else:
            outcome_str = ", ".join(
                f"{k}={v}" for k, v in sorted(counts.items())
            )
            print(
                f"serve: {args.requests} requests (+{replayed} replayed) "
                f"in {wall:.2f}s — {outcome_str}; "
                f"{record['solves_per_sec']} solves/sec sustained"
            )
        # the documented contract: exit with the worst (numerically
        # highest) per-request outcome, so a gate scripting on the
        # help text classifies deadline misses and sheds as themselves
        # rather than as convergence failures. A SIGTERM'd run that
        # drained cleanly exits 0 — graceful shutdown is a success,
        # not the worst outcome of a stream it cut short.
        from poisson_ellipse_tpu.serve import EXIT_BY_OUTCOME

        if drained_early:
            return 0
        return max((EXIT_BY_OUTCOME[o] for o in counts), default=0)
    finally:
        obs_metrics.REGISTRY.emit()
        obs_metrics.REGISTRY.reset()
        if args.trace:
            obs_trace.stop()


def _run_grad(argv: list[str]) -> int:
    """The ``grad`` subcommand: the differentiable-solving workloads
    (``diff.optimize``) end-to-end — ellipse-recovers-itself inverse
    geometry or inverse-source recovery, driven by IFT adjoints through
    the converged solve (``diff.adjoint``)."""
    ap = argparse.ArgumentParser(
        prog="python -m poisson_ellipse_tpu.harness grad",
        description="Differentiable solving (diff/): gradients of a "
        "functional of the converged solution via implicit-function-"
        "theorem adjoints — one extra PCG solve with the same operator "
        "per gradient. Workloads: 'ellipse' recovers randomly perturbed "
        "ellipse parameters from the solution they produced (acceptance "
        "rel err <= 1e-3); 'source' recovers a per-node source field "
        "(acceptance misfit drop >= 100x). Exit 0 on acceptance, 2 "
        "otherwise.",
    )
    ap.add_argument("--workload", choices=("ellipse", "source"),
                    default="ellipse")
    ap.add_argument("--grid", default=None, metavar="MxN",
                    help="grid (default 24x24 ellipse / 16x16 source)")
    ap.add_argument("--engine", choices=("xla", "pipelined", "mg-pcg",
                                         "cheb-pcg"), default="xla")
    ap.add_argument("--steps", type=int, default=None,
                    help="optimizer step cap (workload defaults)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", metavar="FILE", help="JSONL trace sink")
    ap.add_argument("--json", action="store_true", help="one JSON line")
    args = ap.parse_args(argv)

    import jax

    from poisson_ellipse_tpu.diff import optimize as diff_optimize

    # the diff/ contract is f64 (gradient accuracy is quoted against
    # the solve tolerance) — flip x64 like the menu's f64 entry does
    # (harness.run.resolve_dtype): a process-global flag, set before
    # any trace is built
    if not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)
    if args.trace:
        obs_trace.start(args.trace)
    try:
        kwargs = {"engine": args.engine, "seed": args.seed}
        if args.grid is not None:
            try:
                kwargs["grid"] = _parse_grid(args.grid)
            except ValueError as e:
                print(f"error: {e}", file=sys.stderr)
                return 2
        if args.steps is not None:
            kwargs["steps"] = args.steps
        if args.workload == "ellipse":
            report = diff_optimize.recover_ellipse(**kwargs)
        else:
            report = diff_optimize.recover_source(**kwargs)
        if args.json:
            print(json.dumps(report))
        elif args.workload == "ellipse":
            print(
                f"grad/{report['workload']}: grid "
                f"{report['grid'][0]}x{report['grid'][1]} engine "
                f"{report['engine']} — rel err {report['rel_err']:.2e} "
                f"(acceptance 1e-3), misfit "
                f"{report['misfit_initial']:.3e} -> "
                f"{report['misfit_final']:.3e}, "
                f"{report['n_evals']} value+grad evals — "
                f"{'OK' if report['ok'] else 'NOT CONVERGED'}"
            )
        else:
            print(
                f"grad/{report['workload']}: grid "
                f"{report['grid'][0]}x{report['grid'][1]} engine "
                f"{report['engine']} — misfit drop "
                f"{report['misfit_drop']:.1f}x (acceptance 100x), "
                f"{report['n_evals']} value+grad evals — "
                f"{'OK' if report['ok'] else 'NOT CONVERGED'}"
            )
        return 0 if report["ok"] else 2
    finally:
        obs_metrics.REGISTRY.emit()
        obs_metrics.REGISTRY.reset()
        if args.trace:
            obs_trace.stop()


def _run_chaos(argv: list[str]) -> int:
    """The ``chaos`` subcommand: the serving invariants under injected
    lane NaN, fake OOM and a kill/restart — zero lost, zero
    double-completed, every outcome classified."""
    import os
    import tempfile

    from poisson_ellipse_tpu.serve import run_chaos

    ap = argparse.ArgumentParser(
        prog="python -m poisson_ellipse_tpu.harness chaos",
        description="Serving chaos drill (serve.chaos): a seeded Poisson "
        "stream of mixed shapes with an injected NaN-poisoned lane, a "
        "fake RESOURCE_EXHAUSTED dispatch, and one mid-stream "
        "kill/restart with journal replay. Exit 0 iff zero requests "
        "were lost, none double-completed, and every terminal state is "
        "a classified outcome; exit 2 otherwise.",
    )
    ap.add_argument("--requests", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grids", default="10x10,12x12,8x8")
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument(
        "--no-kill", action="store_true",
        help="skip the kill/restart (fault injection only)",
    )
    ap.add_argument(
        "--mesh", action="store_true",
        help="add the mesh-kill drill: a simulated device loss takes "
        "out every live batch carry mid-stream and every in-flight "
        "request must re-enter through the journal/retry ladder — the "
        "zero-lost/zero-double invariants asserted across a DEVICE "
        "kill, not just a process kill",
    )
    ap.add_argument(
        "--journal", metavar="FILE",
        help="journal path (default: a temp file, removed after)",
    )
    ap.add_argument(
        "--deadline", type=float, default=None, metavar="SECONDS",
        help="per-request deadline for the stream",
    )
    ap.add_argument(
        "--warm-start", action="store_true",
        help="run the drill with the per-bucket recycle pools ON "
        "(runtime.solvecache) and a cache_poison fault armed on one "
        "request: the zero-lost/zero-double/all-classified triple must "
        "hold unchanged with recycling enabled, and the poisoned "
        "consult may only cost iterations",
    )
    ap.add_argument("--trace", metavar="FILE", help="JSONL trace sink")
    ap.add_argument("--json", action="store_true", help="one JSON line")
    args = ap.parse_args(argv)

    if args.trace:
        obs_trace.start(args.trace)
    try:
        try:
            grids = tuple(
                _parse_grid(spec) for spec in args.grids.split(",")
            )
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        tmp_dir = None
        journal = args.journal
        if journal is None and not args.no_kill:
            tmp_dir = tempfile.TemporaryDirectory()
            journal = os.path.join(tmp_dir.name, "chaos-journal.json")
        try:
            report = run_chaos(
                n_requests=args.requests, seed=args.seed, grids=grids,
                lanes=args.lanes, chunk=args.chunk,
                journal_path=journal,
                kill_after=None if not args.no_kill else 0,
                deadline_s=args.deadline,
                mesh_kill_request=(
                    max(args.requests // 3, 1) if args.mesh else None
                ),
                warm_start=args.warm_start,
                poison_request=(
                    max(args.requests // 4, 1) if args.warm_start else None
                ),
            )
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        finally:
            if tmp_dir is not None:
                tmp_dir.cleanup()
        if args.json:
            print(json.dumps(report.json_dict()))
        else:
            verdict = "OK" if report.ok else "INVARIANT VIOLATION"
            mesh_note = (
                "; mesh-kill drill fired" if report.mesh_killed else ""
            )
            print(
                f"chaos: {report.n_requests} requests, seed {args.seed} — "
                f"{verdict}; outcomes {report.counts}; "
                f"replayed {report.replayed} after kill; "
                f"{report.faults_fired} faults fired{mesh_note}; "
                f"lost {len(report.lost)}, doubled "
                f"{len(report.double_completed)} ({report.wall_s:.2f}s)"
            )
        return 0 if report.ok else 2
    finally:
        obs_metrics.REGISTRY.emit()
        obs_metrics.REGISTRY.reset()
        if args.trace:
            obs_trace.stop()


def _run_fleet(argv: list[str]) -> int:
    """The ``fleet`` subcommand: an N-replica Poisson drill through the
    replicated serving layer (``fleet.FleetRouter``) — shape-affinity
    routing, lease-checked replicas, optional mid-stream replica kill
    with journal-backed handoff, optional REJOIN of the killed replica
    as a fresh incarnation, a pluggable (memory/file) lease store,
    SIGTERM-graceful drain."""
    import os as _os
    import random
    import tempfile
    import time as _time

    from poisson_ellipse_tpu.fleet import FileLeaseStore, FleetRouter
    from poisson_ellipse_tpu.resilience import faultinject
    from poisson_ellipse_tpu.resilience.errors import FleetUnavailableError
    from poisson_ellipse_tpu.serve import EXIT_BY_OUTCOME

    ap = argparse.ArgumentParser(
        prog="python -m poisson_ellipse_tpu.harness fleet",
        description="Replicated-serving drill: a seeded Poisson stream "
        "of mixed shapes routed over N scheduler replicas "
        "(compile-bucket affinity, per-replica backpressure, lease "
        "health checks). --kill-replica-at SIGKILLs replica 0 at that "
        "arrival index: its journal hands off to the survivors with "
        "remaining-deadline budgets preserved, and the stream "
        "continues. SIGTERM drains gracefully (stop admitting, finish "
        "in-flight, flush, exit 0). exit code = the worst per-request "
        "outcome; 9 when every replica is down "
        "(FleetUnavailableError). " + EXIT_CODES_HELP,
    )
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument(
        "--kill-replica-at", type=int, default=None, metavar="INDEX",
        help="SIGKILL replica 0 when arrival INDEX lands (journal "
        "handoff drill); default: no kill",
    )
    ap.add_argument(
        "--rejoin-at", type=int, default=None, metavar="INDEX",
        help="re-enter the killed replica 0 as a FRESH incarnation "
        "when arrival INDEX lands (fresh epoch, archived-journal "
        "replay, warm-pool pre-warm); needs --kill-replica-at earlier "
        "in the stream",
    )
    ap.add_argument(
        "--lease-store", choices=("memory", "file"), default="memory",
        help="the fleet's lease/fencing store: 'memory' is the "
        "in-process default; 'file' persists epochs to "
        "<journal-dir>/lease-store.json (atomic rename, fsync) so a "
        "restarted driver fences against the previous run's epochs",
    )
    ap.add_argument("--grids", default="10x10,12x12")
    ap.add_argument("--rate", type=float, default=200.0)
    ap.add_argument("--lanes", type=int, default=2,
                    help="lanes per replica")
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument("--deadline", type=float, default=None,
                    metavar="SECONDS")
    ap.add_argument("--retries", type=int, default=1)
    ap.add_argument(
        "--journal-dir", metavar="DIR",
        help="fleet journal directory, one ledger per replica "
        "(default: a temp dir, removed after)",
    )
    ap.add_argument(
        "--lease", type=float, default=0.5, metavar="SECONDS",
        help="replica lease length (monotonic-clock heartbeat)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", metavar="FILE", help="JSONL trace sink")
    ap.add_argument(
        "--metrics", metavar="FILE",
        help="OpenMetrics snapshot of the fleet counters/histograms",
    )
    ap.add_argument("--json", action="store_true", help="one JSON line")
    args = ap.parse_args(argv)

    if args.trace:
        obs_trace.start(args.trace)
    tmp_dir = None
    try:
        try:
            grids = [_parse_grid(spec) for spec in args.grids.split(",")]
            if args.replicas < 1:
                raise ValueError("--replicas must be >= 1")
            if args.requests < 1:
                raise ValueError("--requests must be >= 1")
            if args.rate <= 0:
                raise ValueError("--rate must be > 0 requests/second")
            if args.rejoin_at is not None:
                if args.kill_replica_at is None:
                    raise ValueError(
                        "--rejoin-at needs --kill-replica-at: only a "
                        "dead replica can rejoin"
                    )
                if args.rejoin_at <= args.kill_replica_at:
                    raise ValueError(
                        "--rejoin-at must land strictly after "
                        "--kill-replica-at"
                    )
            journal_dir = args.journal_dir
            if journal_dir is None:
                tmp_dir = tempfile.TemporaryDirectory()
                journal_dir = tmp_dir.name
            faults = []
            if args.kill_replica_at is not None:
                faults.append(faultinject.replica_kill(
                    at_request=args.kill_replica_at, replica=0,
                ))
            lease_store = None
            if args.lease_store == "file":
                lease_store = FileLeaseStore(
                    _os.path.join(journal_dir, "lease-store.json"),
                )
            router = FleetRouter(
                replicas=args.replicas,
                journal_dir=journal_dir,
                lease_s=args.lease,
                lease_store=lease_store,
                faults=faultinject.FaultPlan(*faults),
                lanes=args.lanes,
                chunk=args.chunk,
                queue_capacity=args.queue_capacity,
                max_retries=args.retries,
                keep_solutions=False,
            )
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        rng = random.Random(args.seed)
        t0 = _time.monotonic()
        results: dict = {}
        drained_early = False
        try:
            with _SigtermDrain() as term:
                for i in range(args.requests):
                    if term.requested:
                        drained_early = True
                        obs_trace.event("serve:sigterm-drain")
                        results.update(router.shutdown())
                        break
                    M, N = rng.choice(grids)
                    router.submit(
                        Problem(M=M, N=N), deadline_s=args.deadline,
                    )
                    _time.sleep(min(rng.expovariate(args.rate), 0.05))
                    router.step()
                    if (args.rejoin_at is not None
                            and i >= args.rejoin_at
                            and not router.rejoins):
                        victim = router._by_id(0)
                        if victim is not None and not victim.live:
                            router.rejoin_replica(0)
                    results.update(router.collect())
                else:
                    results.update(router.drain())
                    results.update(router.collect())
        except FleetUnavailableError as e:
            print(
                f"error: fleet unavailable — {e}",
                file=sys.stderr,
            )
            return e.exit_code
        wall = _time.monotonic() - t0
        counts: dict[str, int] = {}
        for res in results.values():
            counts[res.outcome] = counts.get(res.outcome, 0) + 1
        completed = counts.get("completed", 0)
        handoff = obs_metrics.REGISTRY.histogram(
            obs_metrics.HANDOFF_LATENCY_SECONDS
        )
        record = {
            "replicas": args.replicas,
            "requests": args.requests,
            "outcomes": counts,
            "solves_per_sec": round(completed / wall, 2) if wall else None,
            "handoffs": router.handoffs,
            "adopted": router.adopted_total,
            "handoff_p99_s": handoff.quantile(0.99),
            "rejoins": router.rejoins,
            "rejoin_p99_s": obs_metrics.REGISTRY.histogram(
                obs_metrics.REJOIN_LATENCY_SECONDS
            ).quantile(0.99),
            "lease_store": args.lease_store,
            "live_replicas": [r.replica_id for r in router.live_replicas()],
            "wall_s": round(wall, 4),
            "drained_on_sigterm": drained_early,
        }
        obs_trace.event("fleet_report", **record)
        if args.metrics:
            from poisson_ellipse_tpu.obs.export import MetricsExporter

            err = MetricsExporter(args.metrics).try_write()
            if err is not None:
                print(
                    f"warning: metrics snapshot failed: {err}",
                    file=sys.stderr,
                )
        if args.json:
            print(json.dumps(record))
        else:
            outcome_str = ", ".join(
                f"{k}={v}" for k, v in sorted(counts.items())
            )
            print(
                f"fleet: {args.requests} requests over {args.replicas} "
                f"replicas in {wall:.2f}s — {outcome_str}; "
                f"{record['solves_per_sec']} solves/sec aggregate; "
                f"{router.handoffs} handoff(s), {router.adopted_total} "
                "request(s) adopted"
            )
        if drained_early:
            return 0
        return max((EXIT_BY_OUTCOME[o] for o in counts), default=0)
    finally:
        obs_metrics.REGISTRY.emit()
        obs_metrics.REGISTRY.reset()
        if tmp_dir is not None:
            tmp_dir.cleanup()
        if args.trace:
            obs_trace.stop()


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    from poisson_ellipse_tpu.runtime import compile_cache

    # every subcommand compiles: the cache goes on before the first one
    compile_cache.enable_persistent_cache()
    if argv and argv[0] == "inspect":
        return _run_inspect(argv[1:])
    if argv and argv[0] == "inject":
        return _run_inject(argv[1:])
    if argv and argv[0] == "warmup":
        return _run_warmup(argv[1:])
    if argv and argv[0] == "tune":
        return _run_tune(argv[1:])
    if argv and argv[0] == "diagnose":
        return _run_diagnose(argv[1:])
    if argv and argv[0] == "serve":
        return _run_serve(argv[1:])
    if argv and argv[0] == "fleet":
        return _run_fleet(argv[1:])
    if argv and argv[0] == "chaos":
        return _run_chaos(argv[1:])
    if argv and argv[0] == "grad":
        return _run_grad(argv[1:])
    ap = argparse.ArgumentParser(
        prog="python -m poisson_ellipse_tpu.harness",
        description="Fictitious-domain Poisson PCG on TPU",
        epilog=EXIT_CODES_HELP,
    )
    ap.add_argument("M", type=int, nargs="?", help="grid cells in x")
    ap.add_argument("N", type=int, nargs="?", help="grid cells in y")
    ap.add_argument(
        "--grids", help="comma list of MxN grids to sweep, e.g. 400x600,800x1200"
    )
    ap.add_argument(
        "--mode",
        choices=("auto", "single", "sharded", "native"),
        default="auto",
    )
    ap.add_argument(
        "--engine",
        choices=ENGINES,
        default="auto",
        help="solver engine. Single-device: auto picks the fastest whose "
        "capacity regime applies (resident -> streamed -> xl; f64 takes "
        "xla); fused is the two-kernel "
        "HBM iteration, pallas the per-op stencil kernel, pipelined the "
        "one-fused-reduction-per-iteration recurrence (pipelined-pallas: "
        "same loop through the fused stencil+partials kernel); batched/"
        "batched-pipelined run --lanes independent solves per dispatch "
        "(the throughput engines, per-lane results); sstep/sstep-pallas "
        "run the s-step communication-avoiding recurrence (--sstep-s "
        "iterations per matrix-powers round); fmg runs ONE full-"
        "multigrid F-cycle (O(N) work, constant per grid point) plus "
        "the verified mg-pcg handoff against delta. Sharded "
        "mode: xla (default), pallas (the per-shard stencil kernel), "
        "fused (the two-kernel per-shard iteration, f32/bf16), "
        "pipelined (one stacked psum per iteration), sstep (ONE psum + "
        "one s-deep halo per s iterations), fmg (per-level halo "
        "discipline, classical psum cadence in the handoff), mg-pcg/"
        "cheb-pcg, or batched/batched-pipelined with --lanes sharded "
        "over the mesh",
    )
    ap.add_argument(
        "--threads",
        type=int,
        default=0,
        help="OpenMP thread count for --mode native (0 = default)",
    )
    ap.add_argument(
        "--threads-sweep",
        help="comma list of OpenMP thread counts to sweep with --mode "
        "native, printing the stage1 table (T per count + speedup vs the "
        "first count; Этап1.pdf table 2's in-run sweep)",
    )
    ap.add_argument(
        "--mesh",
        type=int,
        nargs=2,
        metavar=("PX", "PY"),
        help="device mesh shape (default: near-square over all devices)",
    )
    ap.add_argument(
        "--dtype",
        choices=sorted(DTYPES),
        default="f32",
        help="f64 flips jax_enable_x64 for the whole process (a global "
        "JAX switch: later runs in the same process stay x64-enabled)",
    )
    ap.add_argument("--delta", type=float, default=1e-6)
    ap.add_argument(
        "--storage-dtype",
        choices=("bf16", "f16", "f32"),
        default=None,
        metavar="DT",
        help="HBM storage width for state/operand streams, separate from "
        "the compute dtype (ops.precision): bf16 halves the loop's HBM "
        "bytes while every stencil/reduction upcasts to --dtype "
        "tile-locally. The raw engines converge to the storage floor; "
        "with --guard the escalation ladder (bf16 -> f32 -> f64) "
        "promotes the solve to full width before accepting convergence "
        "— the accuracy-recovered product path. Covers engines "
        "xla/pallas/pipelined*/sstep*/streamed/xl/batched (sharded: "
        "sstep)",
    )
    ap.add_argument(
        "--sstep-s",
        type=int,
        choices=(2, 4),
        default=4,
        metavar="S",
        help="block size of the s-step engines (--engine sstep/"
        "sstep-pallas): S iterations per matrix-powers round — sharded, "
        "ONE psum + one S-deep halo per S iterations",
    )
    ap.add_argument("--eps", type=float, default=None)
    ap.add_argument(
        "--eps-sweep",
        help="comma list of eps values to sweep (overrides --eps)",
    )
    ap.add_argument(
        "--norm", choices=("weighted", "unweighted"), default="weighted"
    )
    ap.add_argument("--max-iter", type=int, default=None)
    ap.add_argument(
        "--geometry",
        metavar="SPEC",
        help="solve on an arbitrary SDF domain: a path to a JSON "
        "geometry spec file, or the inline JSON itself (geom.sdf "
        "primitives + union/intersection/difference/translate). The "
        "admissibility gate (geom.validate) runs before any device "
        "dispatch — a bad spec is the classified exit 8, never a hung "
        "solve. The default (no flag) is the closed-form ellipse, "
        "bit-identical to previous releases",
    )
    ap.add_argument(
        "--theta",
        type=float,
        default=None,
        metavar="FRAC",
        help="degenerate-cut clamp threshold for --geometry: face "
        "fractions within theta of empty/full snap to empty/full, each "
        "clamp reported as a geom:degenerate-cut trace event (default: "
        "geom.quadrature.DEFAULT_THETA; 0 disables the defense)",
    )
    ap.add_argument("--repeat", type=int, default=1, help="timing repetitions")
    ap.add_argument(
        "--batch",
        type=int,
        default=1,
        help="TIMING protocol: dispatches chained per repetition so the "
        "fixed per-dispatch host overhead cancels out of T_solver. This "
        "does NOT batch solves onto the chip — that is --lanes",
    )
    ap.add_argument(
        "--lanes",
        type=int,
        default=1,
        help="REAL lane batching: run N independent solves inside one "
        "dispatch via the batched engines (--engine batched/"
        "batched-pipelined; auto resolves to batched when N > 1). "
        "Reports per-dispatch T_solver plus aggregate solves/sec. "
        "Distinct from --batch, which only chains dispatches to time "
        "them",
    )
    ap.add_argument(
        "--recycle",
        type=int,
        nargs="?",
        const=-1,  # bare flag → solver.recycle.RECYCLE_CAP, resolved below
        default=None,
        metavar="CAP",
        help="Krylov recycling (solver.recycle): one untimed ring-"
        "carrying capture solve harvests the extremal Ritz deflation "
        "basis, then the timed solve restarts deflated (x0 = the "
        "Galerkin projection of the rhs) — the report's iters is the "
        "deflated count. CAP is the Lanczos-vector ring capacity "
        "(default: solver.recycle.RECYCLE_CAP); rides the single-device "
        "xla engine. Correctness never depends on the basis: any x0 is "
        "verified by its TRUE residual at init",
    )
    ap.add_argument(
        "--warm-start",
        action="store_true",
        help="seed the timed solve with a prior solve's solution — the "
        "semantic-cache-hit shape (runtime.solvecache); stacks on "
        "--recycle (the hit is deflated against its true residual). "
        "Warm-started solution bits legitimately differ from cold",
    )
    ap.add_argument(
        "--checkpoint-dir",
        help="persist the PCG carry here every --chunk iterations and "
        "resume from it after a kill (single and sharded modes; sharded "
        "carries are saved with their mesh shardings)",
    )
    ap.add_argument(
        "--chunk",
        type=int,
        default=500,
        help="iterations between checkpoints (with --checkpoint-dir)",
    )
    ap.add_argument(
        "--timeout",
        type=float,
        default=None,
        metavar="SECONDS",
        help="per-solve deadline, enforced at guard chunk boundaries "
        "(graceful cancel: the in-flight chunk completes, a partial "
        "schema-valid trace is emitted, exit code 4); implies --guard",
    )
    ap.add_argument(
        "--guard",
        action="store_true",
        help="run through resilience.guard: chunked execution with a "
        "per-chunk device-side health word (breakdown/NaN/stagnation), "
        "the recovery ladder (residual restart -> f32->f64 escalation "
        "-> engine fallback), and classified errors instead of NaN "
        "results",
    )
    ap.add_argument(
        "--max-recoveries",
        type=int,
        default=3,
        help="recovery-action budget for guarded runs before the solve "
        "is classified diverged (exit code 2)",
    )
    ap.add_argument(
        "--profile",
        action="store_true",
        help="segmented per-phase iteration profile (stage4 timers)",
    )
    ap.add_argument(
        "--trace-dir",
        help="capture a jax.profiler trace of the solve into this directory "
        "(open with TensorBoard / xprof)",
    )
    ap.add_argument(
        "--trace",
        metavar="FILE",
        help="append a structured JSONL run trace (phase spans, run-report "
        "events, counters; obs.trace schema) to FILE; POISSON_TRACE=FILE "
        "in the environment does the same without the flag",
    )
    ap.add_argument(
        "--metrics",
        metavar="FILE",
        help="export the run's counters/gauges/histograms as an "
        "OpenMetrics snapshot to FILE (obs.export; written periodically "
        "while running — point a scraper at it — and once at exit)",
    )
    ap.add_argument(
        "--metrics-interval",
        type=float,
        default=30.0,
        metavar="SECONDS",
        help="periodic snapshot cadence for --metrics",
    )
    ap.add_argument("--json", action="store_true", help="one JSON line per run")
    args = ap.parse_args(argv)

    if args.metrics and args.metrics_interval <= 0:
        print(
            "error: --metrics-interval must be positive (a zero cadence "
            "would busy-spin the exporter thread)",
            file=sys.stderr,
        )
        return 2
    if args.trace:
        obs_trace.start(args.trace)
    obs_trace.event("cli-args", argv=list(argv))
    exporter = None
    if args.metrics:
        from poisson_ellipse_tpu.obs.export import MetricsExporter

        exporter = MetricsExporter(
            args.metrics, interval_s=args.metrics_interval
        )
        # fail FAST on an unwritable path: a snapshot that can only
        # fail at exit would crash the finally block after a good
        # run — bad input is the up-front exit-2 contract
        err = exporter.try_write()
        if err is not None:
            print(
                f"error: cannot write --metrics {args.metrics}: {err}",
                file=sys.stderr,
            )
            if args.trace:
                obs_trace.stop()
            return 2
        exporter.start()
    rc = None
    try:
        rc = _run_cli(args)
        return rc
    finally:
        # emit/reset unconditionally (crashed runs included): per-run
        # aggregates — a later main() in the same process must not
        # report this run's counts as its own. The metrics snapshot
        # flushes BEFORE the reset, or the exported file would be empty.
        obs_metrics.REGISTRY.emit()
        if exporter is not None:
            # the path was validated up front, but a filesystem can
            # still die mid-run: report it, never mask the solve's
            # result or skip the reset/stop cleanup below
            exporter.stop(final_write=False)
            err = exporter.try_write()
            if err is not None:
                print(
                    f"warning: metrics snapshot failed: {err}",
                    file=sys.stderr,
                )
        obs_metrics.REGISTRY.reset()
        obs_trace.event("cli-exit", rc="error" if rc is None else rc)
        if args.trace:
            obs_trace.stop()


def _geometry_spec(arg: str | None):
    """The --geometry value as a parsed JSON object: a file path or the
    inline JSON itself. An unreadable path is an invocation error
    (exit 2); unparseable JSON is a *content* defect and classifies as
    the gate's ``malformed-spec`` (exit 8) like every other bad spec."""
    if arg is None:
        return None
    from poisson_ellipse_tpu.resilience.errors import InvalidGeometryError

    text = arg
    if not arg.lstrip().startswith("{"):
        with open(arg, "r", encoding="utf-8") as fh:
            text = fh.read()
    try:
        return json.loads(text)
    except json.JSONDecodeError as e:
        raise InvalidGeometryError(
            f"malformed geometry spec: not valid JSON ({e})",
            reason="malformed-spec",
        ) from e


def _run_cli(args) -> int:
    """The measured-run body of ``main`` (post-parse, post-trace-setup)."""
    eps_values = (
        [float(e) for e in args.eps_sweep.split(",")]
        if args.eps_sweep
        else [args.eps]
    )
    try:
        geometry = _geometry_spec(args.geometry)
    except OSError as e:
        print(f"error: cannot read --geometry: {e}", file=sys.stderr)
        return 2
    except SolveError as e:
        print(f"error: {e.classification}: {e}", file=sys.stderr)
        return e.exit_code
    if args.geometry is None and args.theta is not None:
        print("error: --theta needs --geometry", file=sys.stderr)
        return 2
    recycle_cap = args.recycle
    if recycle_cap is not None and recycle_cap < 0:
        # bare --recycle: the product default ring capacity
        from poisson_ellipse_tpu.solver.recycle import RECYCLE_CAP

        recycle_cap = RECYCLE_CAP

    if args.threads_sweep:
        if args.mode != "native":
            print(
                "error: --threads-sweep is the OpenMP runtime's in-run "
                "sweep; it requires --mode native",
                file=sys.stderr,
            )
            return 2
        if args.threads:
            print(
                "error: --threads conflicts with --threads-sweep (the "
                "sweep list is the thread counts)",
                file=sys.stderr,
            )
            return 2
        if args.checkpoint_dir:
            print(
                "error: checkpointing covers the JAX paths, not native "
                "runs; drop --checkpoint-dir or --threads-sweep",
                file=sys.stderr,
            )
            return 2

    try:
        grids = _parse_grids(args)
    except ValueError as e:
        print(f"error: invalid --grids: {e}", file=sys.stderr)
        return 2
    # a sweep re-fingerprints the checkpoint each run, so a shared directory
    # would refuse every run after the first — key per-run subdirectories
    sweeping = len(grids) * len(eps_values) > 1

    rc = 0
    for M, N in grids:
        for eps in eps_values:
            ck_dir = args.checkpoint_dir
            if ck_dir is not None and sweeping:
                import os

                ck_dir = os.path.join(
                    ck_dir,
                    f"{M}x{N}" + (f"_eps{eps:g}" if eps is not None else ""),
                )
            problem = Problem(
                M=M,
                N=N,
                delta=args.delta,
                eps=eps,
                norm=args.norm,
                max_iter=args.max_iter,
            )
            if args.threads_sweep:
                try:
                    rc = max(
                        rc,
                        _run_threads_sweep(
                            problem,
                            [int(t) for t in args.threads_sweep.split(",")],
                            repeat=args.repeat,
                            as_json=args.json,
                        ),
                    )
                except (ValueError, NativeBuildError) as e:
                    print(f"error: {e}", file=sys.stderr)
                    return 2
                continue
            try:
                import jax

                # jax.profiler trace around the measured solve — the TPU
                # analog of the reference's per-phase timers beyond what
                # the fenced PhaseTimer's coarse split covers (SURVEY §5)
                trace_cm = (
                    jax.profiler.trace(args.trace_dir)
                    if args.trace_dir
                    else contextlib.nullcontext()
                )
                with trace_cm:
                    report = run_once(
                        problem,
                        mode=args.mode,
                        mesh_shape=tuple(args.mesh) if args.mesh else None,
                        dtype=args.dtype,
                        engine=args.engine,
                        repeat=args.repeat,
                        batch=args.batch,
                        lanes=args.lanes,
                        threads=args.threads,
                        checkpoint_dir=ck_dir,
                        chunk=args.chunk,
                        timeout=args.timeout,
                        guard=args.guard,
                        max_recoveries=args.max_recoveries,
                        geometry=geometry,
                        theta=args.theta,
                        storage_dtype=args.storage_dtype,
                        sstep_s=args.sstep_s,
                        recycle=recycle_cap,
                        warm_start=args.warm_start,
                    )
            except SolveError as e:
                # the classified exit contract: the trace keeps every
                # event flushed before the abort (recovery:* included),
                # plus this partial report — an artifact, not a hang
                record = {
                    "M": M, "N": N, "dtype": args.dtype,
                    "engine": args.engine,
                    "aborted": e.classification,
                    "iters": e.iters,
                }
                obs_trace.event("run_report_partial", **record)
                if args.json:
                    print(json.dumps(record))
                print(
                    f"error: solve aborted — {e.classification}: {e}",
                    file=sys.stderr,
                )
                return e.exit_code
            except (ValueError, NativeBuildError) as e:
                # NativeBuildError = g++ missing or the C++ build failed —
                # an environment problem to report, not a traceback. Other
                # RuntimeErrors (incl. jax XlaRuntimeError) stay loud.
                print(f"error: {e}", file=sys.stderr)
                return 2
            # the structured twin of the human summary below: one event
            # per run, same fields as --json's line
            obs_trace.event("run_report", **report.json_dict())
            obs_metrics.counter("runs").inc()
            if report.converged:
                obs_metrics.counter("runs_converged").inc()
            obs_metrics.gauge("last_iters").set(report.iters)
            # latency distribution across the run/sweep: the p50/p90/p99
            # the --metrics OpenMetrics snapshot renders as a summary
            obs_metrics.histogram("solve_seconds").observe(report.t_solver)
            phases = None
            if args.profile and args.mode == "native":
                print(
                    "note: --profile covers the JAX paths; skipped for "
                    "--mode native",
                    file=sys.stderr,
                )
            elif args.profile:
                from poisson_ellipse_tpu.harness.profile import (
                    profile_single,
                    profile_sharded,
                )

                jdtype = resolve_dtype(args.dtype)
                if report.mesh_shape == (1, 1):
                    phases = profile_single(problem, jdtype)
                else:
                    phases = profile_sharded(
                        problem,
                        mesh=resolve_mesh(
                            tuple(args.mesh) if args.mesh else None
                        ),
                        dtype=jdtype,
                    )
                # the stage4 timers: halo/stencil/dot/... mean seconds per
                # iteration from the segmented replay, measured apart
                # from this run, so one event and not spans
                obs_trace.event("profile", phase_s=phases)
            if args.json:
                # keep stdout one JSON line per run: phases ride inside it
                record = report.json_dict()
                if phases is not None:
                    record["phase_s"] = phases
                print(json.dumps(record))
            else:
                from poisson_ellipse_tpu.harness.profile import format_phases

                print(report.summary())
                if phases is not None:
                    print(format_phases(phases, report.iters))
                if (
                    args.batch == 1
                    and args.mode != "native"
                    and args.checkpoint_dir is None
                ):
                    import jax

                    if jax.default_backend() != "cpu":
                        print(
                            "note: single-dispatch T_solver includes the "
                            "fixed per-dispatch host overhead; pass e.g. "
                            "--repeat 3 --batch 5 for the amortised "
                            "protocol",
                            file=sys.stderr,
                        )
                print()
            if report.breakdown:
                rc = max(rc, 2)  # diverged, per the exit-code contract
            elif not report.converged:
                rc = max(rc, 1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
