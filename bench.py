"""Benchmark: T_solver on the reference's headline grids, single TPU chip.

Prints ONE JSON line on stdout:
  {"metric": ..., "value": T_solver_800x1200_s, "unit": "s", "vs_baseline": speedup}

vs_baseline is the speedup over the reference's strongest published
single-accelerator number on the same grid: stage4 MPI+CUDA, 1 rank /
1×P100, 800×1200, T_solver = 0.83 s (Этап_4_1213.pdf table 1; BASELINE.md).
Convergence (δ=1e-6, weighted norm) and the iteration-count oracles
(546 @ 400×600, 989 @ 800×1200, 1858 @ 1600×2400, 2449 @ 2400×3200) are
checked and reported on stderr; a mismatch marks the run invalid.

Beyond the reference grids, the BASELINE.json target configs also run and
ride inside the same JSON line (the reference publishes no numbers for
them, so they carry no vs-ratio — convergence + L2-vs-analytic are the
checks):
  config 2    — 1024×1024 single-chip        -> "config2" key
  north star  — 4096×4096 single-chip        -> "north_star" key
  pipelined   — headline grid, the one-fused-reduction-per-iteration
                engine vs xla under the same protocol -> "pipelined" key
                (oracle check ±2 iterations: a documented reordering)
  config 5    — ε-sweep (1e-2..1e-6) @ 1024² -> "eps_sweep" key, with the
                fictitious-domain stiffness result asserted: iteration
                counts stay FLAT as ε shrinks (the Jacobi preconditioner
                absorbs the 1/ε stiffness — see ``bench_eps_sweep``).
  spectrum    — κ(M⁻¹A) + predicted-vs-actual iterations per published
                grid from the Lanczos-of-CG reconstruction
                (``obs.spectrum``) -> "spectrum" key; κ is regression-
                gated between rounds by ``tools/bench_compare.py``.
  precond     — mg-pcg / cheb-pcg vs diag-PCG per published grid
                ("precond" key): iters + T_solver + l2 parity, asserted
                ≥3× iteration reduction everywhere and a wall-clock win
                at ≥1600×2400 (ROADMAP item 1's acceptance record;
                iters/t_solver regression-gated per grid).
  serving     — "throughput" key: aggregate solves/sec with the batched
                engine at lanes ∈ {1, 8, 32} on 400×600 and the headline
                grid (marginal-cost protocol; lane-0 oracle equality);
                "coldstart" key: compile-vs-solve split with the AOT warm
                pool off/on (the re-request must be a cache HIT —
                ``runtime.compile_cache``'s no-recompile contract); and
                "serving" key: sustained solves/sec + p50/p99 latency
                under a seeded Poisson arrival stream through the
                continuous-batching scheduler (``serve.scheduler``,
                chunk-boundary lane retire/refill) vs the static-batch
                baseline — valid iff every request completes.
  fleet       — "fleet" key: aggregate solves/sec through the replicated
                fleet (``fleet.FleetRouter``) at 1/2/3 replicas under
                the same mixed Poisson stream (non-decreasing within
                the serving noise floor), plus the journal-handoff
                latency p99 of a mid-stream replica kill — valid iff
                every request completes at every width and the kill
                round loses nothing (``fleet-agg-pct`` gated).
  abft        — "abft" key: the silent-corruption checks' healthy-path
                cost at 800×1200 — checks-on vs checks-off T_solver
                (gate: ≤2% overhead) with the per-iteration collective
                counts pinned IDENTICAL from the jaxpr (every checksum
                partial rides the existing stacked convergence psum —
                ``resilience.abft``).
  geometry    — "geometry" key: the SDF-general assembly study at
                400×600 — ellipse-via-quadrature vs the closed form
                (≤1e-12 relative face-fraction error, ±2 iterations,
                asserted into ``valid``), host f64 assembly overhead,
                and a composite ellipse-minus-hole solve (converged +
                discrete maximum principle) as the arbitrary-geometry
                timing row (``geom.*``).
  fmg         — "fmg" key: full multigrid as the solver (``mg.fmg``) —
                T_solver + work units per grid point vs mg-pcg per
                published grid with the constant-work-per-point pin
                (±20% across grids, the O(N) claim) and a ≥4096²
                headline row whose wall clock must beat mg-pcg at
                equal accuracy (``fmg-pct`` gated between rounds).
  autotune    — "autotune" key: the closed-loop tuner
                (``runtime.autotune``) — tuned-vs-static-default wall
                clock per shape with the never-loses pin (a tuned
                config measuring slower than the static default fails
                the round AND the ``bench_compare`` gate) and the
                registry persistence round-trip (``autotune-pct``).
  recycle     — "recycle" key: Krylov recycling (``solver.recycle``) on
                a correlated request stream — one ring-carrying capture
                solve harvests the deflation basis, then ±1%-perturbed
                rhs requests run warm (previous solution + deflated_x0)
                vs cold; mean iteration cut hard-pinned ≥2× at ≤10%
                analytic-l2 gap, plus solves/sec both ways
                (``recycle-pct`` gated between rounds).
  grad        — "grad" key: differentiable solving as a served workload
                (``diff/``) — grad-solves/sec for a batch of grad=True
                requests (primal + IFT-adjoint lane pairs) through the
                scheduler at 400×600, valid iff every gradient lands
                finite and nonzero, plus the adjoint-vs-primal
                iteration ratio per published grid (the quoted ~2x
                cost of a gradient; ``grad-pct`` gated between rounds).
"""

from __future__ import annotations

import json
import math
import os
import statistics
import sys
import time

import jax

from poisson_ellipse_tpu.harness.run import run_once
from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs.trace import event as trace_event, note

# (M, N, oracle_iters, reference stage4 1-GPU T_solver seconds or None)
GRIDS = [
    (400, 600, 546, None),
    (800, 1200, 989, 0.83),
    (1600, 2400, 1858, 4.85),
    (2400, 3200, 2449, 13.24),
]
HEADLINE = (800, 1200)
REPS = 3
BATCH = 9
# BASELINE.json config 5: ε-sweep grid + values (largest -> smallest)
EPS_GRID = (1024, 1024)
EPS_VALUES = (1e-2, 1e-3, 1e-4, 1e-5, 1e-6)


def bench_grid(M: int, N: int, oracle: int, ref_t: float | None):
    # run_once provides the measurement protocol: warm-up outside the
    # timed region, then the chained differential — each rep times one
    # plain dispatch and one chained dispatch of BATCH data-dependent
    # solves, reporting the median marginal cost (t_chain - t_1)/(BATCH-1)
    # so the fixed per-dispatch host overhead cancels. engine="auto" selects
    # the fastest single-chip engine that fits (VMEM-resident mega-kernel
    # -> streamed -> XLA).
    report = run_once(
        Problem(M=M, N=N),
        mode="single",
        dtype="f32",
        engine="auto",
        repeat=REPS,
        batch=BATCH,
    )
    ok = report.converged and report.iters == oracle
    note(
        f"  {M}x{N}: T_solver={report.t_solver:.4f}s iters={report.iters} "
        f"(oracle {oracle}) converged={report.converged} "
        f"engine={report.engine} l2_err={report.l2_error:.3e}  "
        + report.roofline_line(),
    )
    row = {
        "grid": [M, N],
        "t_solver_s": round(report.t_solver, 5),
        "iters": report.iters,
        "converged": report.converged,
        "engine": report.engine,
        "l2_error": report.l2_error,
        # achieved GB/s under the roofline traffic model (0 for the
        # VMEM-resident engine): tools/bench_compare.py gates on it
        "hbm_gbps": report.hbm_gbps,
        "hbm_peak_frac": report.hbm_peak_frac,
        "ref_p100_s": ref_t,
        "vs_p100": round(ref_t / report.t_solver, 2) if ref_t else None,
    }
    return report.t_solver, ok, row


def bench_f64_row(grid: tuple[int, int] = HEADLINE, oracle: int = 989):
    """The f64 fidelity row: the reference is entirely double precision
    (SURVEY §7 names TPU f64 the single biggest fidelity risk), so the
    bench proves the emulated-f64 path converges in exactly the published
    iteration count at the headline grid. One plain repetition — this row
    is a correctness gate, not the timed headline."""
    M, N = grid
    report = run_once(
        Problem(M=M, N=N), mode="single", dtype="f64", engine="auto"
    )
    ok = report.converged and report.iters == oracle
    note(
        f"  {M}x{N} f64: T_solver={report.t_solver:.4f}s "
        f"iters={report.iters} (oracle {oracle}) converged={report.converged} "
        f"engine={report.engine} l2_err={report.l2_error:.3e}",
    )
    row = {
        "grid": [M, N],
        "t_solver_s": round(report.t_solver, 5),
        "iters": report.iters,
        "converged": report.converged,
        "engine": report.engine,
        "l2_error": report.l2_error,
    }
    return ok, row


def bench_baseline_config(M: int, N: int, label: str, amortised: bool,
                          repeat: int = 2):
    """One BASELINE.json target config (no published reference number:
    checks are convergence + a finite, small L2-vs-analytic error).

    amortised=False uses plain dispatch timing — at the north-star size a
    solve takes seconds, so the fixed per-dispatch overhead is noise and
    the chained protocol would multiply a multi-second solve by BATCH.
    ``repeat`` overrides the plain-protocol repetition count (the 8192²
    row keeps the driver bench's wall clock bounded with one)."""
    report = run_once(
        Problem(M=M, N=N),
        mode="single",
        dtype="f32",
        engine="auto",
        repeat=REPS if amortised else repeat,
        batch=BATCH if amortised else 1,
    )
    ok = report.converged and math.isfinite(report.l2_error) \
        and report.l2_error < 1e-2
    note(
        f"  [{label}] {M}x{N}: T_solver={report.t_solver:.4f}s "
        f"iters={report.iters} converged={report.converged} "
        f"engine={report.engine} l2_err={report.l2_error:.3e}  "
        + report.roofline_line(),
    )
    row = {
        "grid": [M, N],
        "t_solver_s": round(report.t_solver, 5),
        "iters": report.iters,
        "converged": report.converged,
        "engine": report.engine,
        "l2_error": report.l2_error,
    }
    return row, ok


def bench_pipelined_row(grid: tuple[int, int] = HEADLINE, oracle: int = 989):
    """The pipelined-engine row at the headline grid: the same amortised
    protocol as the grid rows, engine pinned to ``pipelined``, plus an
    ``xla`` run under the identical protocol for the vs-xla ratio.

    The pipelined recurrence is a documented reordering (one fused
    reduction per iteration — ``ops.pipelined_pcg``), so its oracle check
    is ±2 iterations, not equality. Its single-chip contract is "no
    slower than xla" (the win itself is the sharded path's halved
    collectives; ``vs_xla`` makes the single-chip cost visible in the
    artifact — bench_multichip --engine pipelined carries the mesh side).
    """
    M, N = grid
    pipe = run_once(
        Problem(M=M, N=N), mode="single", dtype="f32", engine="pipelined",
        repeat=REPS, batch=BATCH,
    )
    ref = run_once(
        Problem(M=M, N=N), mode="single", dtype="f32", engine="xla",
        repeat=REPS, batch=BATCH,
    )
    ok = (
        pipe.converged
        and abs(pipe.iters - oracle) <= 2
        and ref.converged
        and ref.iters == oracle
    )
    vs_xla = round(ref.t_solver / pipe.t_solver, 3) if pipe.t_solver > 0 else None
    note(
        f"  {M}x{N} pipelined: T_solver={pipe.t_solver:.4f}s "
        f"iters={pipe.iters} (oracle {oracle}±2) converged={pipe.converged} "
        f"l2_err={pipe.l2_error:.3e}  vs xla {ref.t_solver:.4f}s -> "
        f"{vs_xla}x  " + pipe.roofline_line(),
    )
    row = {
        "grid": [M, N],
        "t_solver_s": round(pipe.t_solver, 5),
        "iters": pipe.iters,
        "converged": pipe.converged,
        "engine": "pipelined",
        "l2_error": pipe.l2_error,
        "t_xla_s": round(ref.t_solver, 5),
        "vs_xla": vs_xla,
    }
    return row, ok


def bench_eps_sweep():
    """BASELINE.json config 5: the fictitious-domain stiffness study.

    Smaller ε stiffens the raw operator (face coefficients scale as 1/ε
    outside the ellipse — ``ops/assembly.py``), but the stiff rows are
    diagonally dominated by the same 1/ε, so the Jacobi-preconditioned
    system's conditioning is ε-uniform: measured iteration counts are
    *flat* as ε → 0 (e.g. 315/287/285/285/285 over ε = 1/1e-1/1e-2/1e-4/
    1e-6 at 256²). That ε-robustness — the solver does not degrade as the
    fictitious domain hardens — is the study's result, and what the sweep
    asserts: every run converged and the iteration counts sit in a narrow
    band (≤ 25% spread) across four decades of ε.

    One jitted XLA solver serves every ε: ε reaches the solve only
    through the assembled (a, b, rhs) operands (h/δ/max_iter are
    ε-independent), so the sweep pays one compile, not five — keeping
    the driver-run bench's wall clock bounded. The compile is paid by a
    fenced warm-up dispatch BEFORE the timed loop (BENCH_r05's first
    sweep entry read 1.51 s against ~0.35 s for the identical
    921-iteration solves that followed — compile leaking into the first
    timed solve), and the sweep asserts the fix holds: per-iteration
    times across the (equal-iteration) entries must stay within 2×."""
    import jax.numpy as jnp

    from poisson_ellipse_tpu.ops import assembly
    from poisson_ellipse_tpu.solver.engine import build_solver
    from poisson_ellipse_tpu.utils.error import l2_error_vs_analytic
    from poisson_ellipse_tpu.utils.timing import fence

    M, N = EPS_GRID
    solver, warm_args, _ = build_solver(
        Problem(M=M, N=N, eps=EPS_VALUES[0]), "xla", jnp.float32
    )
    # warm the executable outside the timed region: compile + first
    # dispatch land here, so entry 0's clock sees the same warm
    # executable as every later entry
    fence(solver(*warm_args))
    rows = []
    for eps in EPS_VALUES:
        problem = Problem(M=M, N=N, eps=eps)
        args = assembly.assemble(problem, jnp.float32)
        t0 = time.perf_counter()
        result = solver(*args)
        fence(result)
        t = time.perf_counter() - t0
        l2 = float(l2_error_vs_analytic(problem, result.w))
        row = {
            "eps": eps,
            "iters": int(result.iters),
            "converged": bool(result.converged),
            "t_solver_s": round(t, 5),
            "l2_error": l2,
        }
        note(
            f"  [eps-sweep] {M}x{N} eps={eps:g}: iters={row['iters']} "
            f"converged={row['converged']} engine=xla "
            f"T_solver={t:.4f}s l2_err={l2:.3e}",
        )
        rows.append(row)
    iters = [r["iters"] for r in rows]
    flat = (max(iters) - min(iters)) <= 0.25 * min(iters)
    # the warm-up regression fence: with the compile paid up front,
    # equal-iteration sweep entries are the same work on the same warm
    # executable — per-iteration times beyond 2× apart mean something
    # (compile, allocation churn) leaked back into a timed region
    per_iter = [r["t_solver_s"] / max(r["iters"], 1) for r in rows]
    warm = max(per_iter) <= 2.0 * min(per_iter)
    ok = all(r["converged"] for r in rows) and flat and warm
    note(
        f"  [eps-sweep] iters {iters} over eps {EPS_VALUES[0]:g} -> "
        f"{EPS_VALUES[-1]:g}: "
        + (
            "flat (eps-robust, preconditioner absorbs the stiffness) — OK"
            if flat
            else "TREND VIOLATION (iteration count is eps-sensitive)"
        )
        + (
            f"; per-iter spread {max(per_iter) / min(per_iter):.2f}x "
            + ("(warm) — OK" if warm else "> 2x — WARM-UP LEAK (regression)")
        ),
    )
    return rows, ok


def bench_convergence(grid: tuple[int, int] = (400, 600), oracle: int = 546):
    """On-device convergence telemetry summary for the artifact.

    One history-enabled xla solve at the smallest published grid: the
    per-iteration (zr, diff, α, β) series is captured inside the fused
    while_loop (``obs.convergence`` — zero host syncs), summarised into
    a handful of scalars the artifact can carry, and cross-checked: the
    final traced step-norm must equal the solver's own ``diff`` exactly
    (the trace records the loop's values, not a reconstruction).

    Returns ``(row, ok, (result, trace))`` — the solve is also exactly
    the input ``bench_spectrum`` needs for this grid, so the trace is
    handed on instead of paying the full history solve twice per round.
    """
    from poisson_ellipse_tpu.solver.engine import solve as engine_solve

    import jax.numpy as jnp

    M, N = grid
    result, trace = engine_solve(
        Problem(M=M, N=N), "xla", jnp.float32, history=True
    )
    v = trace.valid()
    n = int(result.iters)
    ok = (
        bool(result.converged)
        and result.iters == oracle
        and n > 0
        and float(v["diff"][-1]) == float(result.diff)
    )
    row = {
        "grid": [M, N],
        "engine": "xla",
        "iters": n,
        "converged": bool(result.converged),
        "diff_first": float(v["diff"][0]) if n else None,
        "diff_final": float(v["diff"][-1]) if n else None,
        "zr_first": float(v["zr"][0]) if n else None,
        "zr_final": float(v["zr"][-1]) if n else None,
    }
    note(
        f"  [convergence] {M}x{N} xla history: {n} iterations traced "
        f"on-device, diff {row['diff_first']:.3e} -> {row['diff_final']:.3e} "
        + ("— OK" if ok else "— MISMATCH vs PCGResult"),
    )
    return row, ok, (result, trace)


# grids from (M, N) up where the wall-clock criterion applies: below
# this the solve is dispatch-bound and mg's extra passes/iter can wash
# out the iteration win on latency alone
PRECOND_WALLCLOCK_FLOOR = (1600, 2400)


def bench_precond(grid_rows):
    """The preconditioner study: mg-pcg (+ the cheb-pcg first rung) vs
    diag-PCG per published grid — ROADMAP item 1's acceptance record.

    ``grid_rows`` are the diag-PCG rows ``bench_grid`` already measured
    (same protocol, no re-run). Per grid: iters, T_solver and
    l2-vs-analytic for mg-pcg under the identical amortised protocol,
    plus the ratios. Checks folded into ``valid``: every run converged;
    l2_err no more than 10% ABOVE diag's (one-sided: at equal δ the
    V-cycle lands at-or-below diag's algebraic error); iteration
    reduction ≥ 3× everywhere; and a wall-clock T_solver win at the
    ≥1600×2400 grids where the solve is streaming-bound (smaller grids
    are dispatch-bound and reported without the wall-clock gate). A
    cheb-pcg row at the headline grid records the cheap first rung.
    """
    diag_by_grid = {tuple(r["grid"]): r for r in grid_rows}
    rows = []
    all_ok = True
    for M, N, _oracle, _ref in GRIDS:
        diag = diag_by_grid.get((M, N))
        engines = ["mg-pcg"] + (["cheb-pcg"] if (M, N) == HEADLINE else [])
        for engine in engines:
            report = run_once(
                Problem(M=M, N=N), mode="single", dtype="f32",
                engine=engine, repeat=REPS, batch=BATCH,
            )
            row = {
                "grid": [M, N],
                "engine": engine,
                "t_solver_s": round(report.t_solver, 5),
                "iters": report.iters,
                "converged": report.converged,
                "l2_error": report.l2_error,
            }
            ok = report.converged
            if diag is not None:
                row["diag_iters"] = diag["iters"]
                row["diag_t_solver_s"] = diag["t_solver_s"]
                row["iters_reduction"] = (
                    round(diag["iters"] / report.iters, 2)
                    if report.iters else None
                )
                row["speedup_vs_diag"] = (
                    round(diag["t_solver_s"] / report.t_solver, 2)
                    if report.t_solver > 0 else None
                )
                # one-sided: fail only when the preconditioned solve is
                # WORSE than diag by >10%. At equal δ the step-norm rule
                # leaves the V-cycle with LESS algebraic error than diag
                # (measured 2× at 1600×2400) — more accurate must never
                # read as a parity miss
                l2_ok = (
                    diag["l2_error"] > 0
                    and report.l2_error <= diag["l2_error"] * 1.10
                )
                reduction_ok = (
                    row["iters_reduction"] is not None
                    and row["iters_reduction"] >= 3.0
                )
                wallclock_ok = (
                    M * N < PRECOND_WALLCLOCK_FLOOR[0]
                    * PRECOND_WALLCLOCK_FLOOR[1]
                    or engine != "mg-pcg"
                    or (
                        row["speedup_vs_diag"] is not None
                        and row["speedup_vs_diag"] > 1.0
                    )
                )
                ok = ok and l2_ok and reduction_ok and wallclock_ok
            all_ok &= ok
            note(
                f"  [precond] {M}x{N} {engine}: iters={report.iters} "
                f"(diag {row.get('diag_iters')}, "
                f"{row.get('iters_reduction')}x fewer) "
                f"T_solver={report.t_solver:.4f}s "
                f"({row.get('speedup_vs_diag')}x vs diag) "
                f"l2_err={report.l2_error:.3e} "
                + ("— OK" if ok else "— MISS (parity/reduction/wall-clock)"),
            )
            rows.append(row)
    return rows, all_ok


def bench_fmg(precond_rows, headline_grid: tuple[int, int] = (4096, 4096)):
    """Full multigrid as the solver: T_solver + work units per grid
    point vs mg-pcg per published grid, plus the ≥4096² headline row —
    ROADMAP item 4's acceptance record.

    Per grid: one fmg solve under the amortised protocol next to the
    mg-pcg row ``bench_precond`` already measured (same protocol, no
    re-run). Checks folded into ``valid``: every run converged; l2
    parity with mg-pcg (one-sided ≤10% worse — at equal δ the F-cycle
    seed usually lands BELOW); MEASURED per-point wall clock at the
    largest grid no more than 20% over the best published grid's (the
    O(N) pin; the model's level sum ``mg.fmg.work_units_per_point`` is
    reported per row as a column); and at the headline
    ≥4096² grid a wall-clock win over mg-pcg at equal accuracy (smaller
    grids are dispatch-bound and reported without the wall-clock gate).
    """
    from poisson_ellipse_tpu.mg import coarsen
    from poisson_ellipse_tpu.mg.fmg import work_units_per_point

    mg_by_grid = {
        tuple(r["grid"]): r for r in precond_rows
        if r.get("engine") == "mg-pcg"
    }
    rows = []
    all_ok = True
    grids = [(M, N) for M, N, _o, _r in GRIDS] + [headline_grid]
    for M, N in grids:
        headline = (M, N) == headline_grid
        report = run_once(
            Problem(M=M, N=N), mode="single", dtype="f32", engine="fmg",
            repeat=1 if headline else REPS, batch=1 if headline else BATCH,
        )
        wu = work_units_per_point(coarsen.num_levels(M, N))
        row = {
            "grid": [M, N],
            "t_solver_s": round(report.t_solver, 5),
            "iters": report.iters,  # the verification-handoff count
            "converged": report.converged,
            "l2_error": report.l2_error,
            "work_units_per_point": round(wu, 2),
            "headline": headline,
        }
        ok = report.converged
        mg = mg_by_grid.get((M, N))
        if mg is None and headline:
            # the ≥4096² acceptance comparison: one mg-pcg run at the
            # headline grid (bench_precond covers the published grids)
            mg_rep = run_once(
                Problem(M=M, N=N), mode="single", dtype="f32",
                engine="mg-pcg", repeat=1, batch=1,
            )
            mg = {
                "t_solver_s": round(mg_rep.t_solver, 5),
                "iters": mg_rep.iters,
                "l2_error": mg_rep.l2_error,
            }
        if mg is not None:
            row["mg_t_solver_s"] = mg["t_solver_s"]
            row["mg_iters"] = mg["iters"]
            row["speedup_vs_mg"] = (
                round(mg["t_solver_s"] / report.t_solver, 2)
                if report.t_solver > 0 else None
            )
            l2_ok = (
                mg["l2_error"] > 0
                and report.l2_error <= mg["l2_error"] * 1.10
            )
            # the wall-clock acceptance applies where the solve is
            # streaming-bound; dispatch-bound small grids only report
            wallclock_ok = (not headline) or (
                row["speedup_vs_mg"] is not None
                and row["speedup_vs_mg"] >= 1.0
            )
            ok = ok and l2_ok and wallclock_ok
        all_ok &= ok
        note(
            f"  [fmg] {M}x{N}: T_solver={report.t_solver:.4f}s "
            f"handoff_iters={report.iters} "
            f"wu/pt={wu:.1f} l2_err={report.l2_error:.3e} "
            f"({row.get('speedup_vs_mg')}x vs mg-pcg) "
            + ("— OK" if ok else "— MISS (parity/wall-clock)"),
        )
        rows.append(row)
    # the O(N) pin, MEASURED: per-point wall clock at the largest grid
    # must not exceed the best published per-point figure by >20%.
    # Super-linear work shows up exactly here; dispatch-bound small
    # grids only push their own per-point figure UP, which the
    # one-sided anchor-on-the-min allows. (The model's geometric level
    # sum — work_units_per_point, reported per row — is a pure function
    # of num_levels and cannot regress by measurement, so it is a
    # column, not the gate.)
    t_per_point = [
        r["t_solver_s"] / float(r["grid"][0] * r["grid"][1])
        for r in rows if r["t_solver_s"] > 0
    ]
    wu_ok = (
        len(t_per_point) == len(rows) and len(t_per_point) >= 2
        and t_per_point[-1] <= min(t_per_point[:-1]) * 1.20
    )
    if not wu_ok:
        note(f"  [fmg] O(N) per-point wall-clock pin MISS: "
             f"{[f'{t:.3e}' for t in t_per_point]}")
    return {"rows": rows, "work_units_constant": wu_ok}, all_ok and wu_ok


def bench_autotune(grids=((400, 600), (800, 1200), (1600, 2400))):
    """The closed-loop autotuner's acceptance row: tuned-vs-static wall
    clock per shape (``runtime.autotune`` with ``measure=True`` — the
    never-loses contract, measured).

    Per shape: telemetry probe → candidate scoring → winner, then one
    warmed dispatch each of the winner and the static default. Valid
    iff no tuned config loses to the static default (a measured loss is
    demoted by ``tune`` itself, so a row can only fail if demotion
    broke), and the tuned registry round-trips deterministically.
    ``tools/bench_compare.py`` gates ``tuned_t_s`` per shape between
    rounds (``autotune-pct``) and hard-fails any row with
    ``tuned_loses=True``.
    """
    import tempfile

    from poisson_ellipse_tpu.runtime import autotune

    rows = []
    all_ok = True
    with tempfile.TemporaryDirectory() as td:
        reg = autotune.TuneRegistry(os.path.join(td, "autotune.json"))
        for M, N in grids:
            problem = Problem(M=M, N=N)
            rep = autotune.tune(problem, registry=reg, persist=True,
                                measure=True)
            chosen = rep["chosen"]
            t_tuned = chosen.get("measured_t_s")
            t_static = chosen.get("static_measured_t_s")
            if t_tuned is None:
                # the winner IS the static default: measure it once so
                # the row still carries a gated wall-clock number
                t_static = autotune._measure_once(
                    problem, chosen["static_engine"], jax.numpy.float32
                )
                t_tuned = t_static
            loses = t_tuned > t_static * 1.05  # measurement noise floor
            # persistence round-trip: the registry must hand back the
            # exact config it was given (determinism is select()'s pin)
            reloaded = autotune.TuneRegistry(reg.path).load().get(rep["key"])
            roundtrip_ok = (
                reloaded is not None
                and reloaded.to_json() == chosen
            )
            ok = (not loses) and roundtrip_ok
            all_ok &= ok
            note(
                f"  [autotune] {M}x{N}: {chosen['engine']} "
                f"tuned={t_tuned:.4f}s static={t_static:.4f}s "
                f"({chosen['static_engine']}) "
                + ("— OK" if ok else "— MISS (loses/round-trip)"),
            )
            rows.append({
                "grid": [M, N],
                "tuned_engine": chosen["engine"],
                "knobs": chosen["knobs"],
                "static_engine": chosen["static_engine"],
                "tuned_t_s": round(t_tuned, 5),
                "static_t_s": round(t_static, 5),
                "tuned_loses": loses,
                "roundtrip_ok": roundtrip_ok,
                "demoted": rep["demoted_to_static"],
            })
    return {"rows": rows}, all_ok


SPECTRUM_GRIDS = ((400, 600, 546), (800, 1200, 989))


def bench_spectrum(precomputed=None):
    """Spectral diagnostics rows: κ(M⁻¹A) and predicted-vs-actual
    iterations per published grid (``obs.spectrum``).

    ``precomputed`` maps a grid to an already-run history solve's
    ``(result, trace)`` (bench_convergence hands its 400×600 one over —
    same engine/dtype/history, no second full solve).

    One history-enabled xla solve per grid; the Lanczos tridiagonal
    reconstructed from the recorded α/β yields the condition number the
    iteration-count wall is made of — the before/after yardstick any
    preconditioner work (ROADMAP item 1) reports against, regression-
    gated per round by ``tools/bench_compare.py`` (κ is grid-determined:
    round-over-round drift means the estimator broke). Checks: oracle
    iteration counts, a sane κ (finite, > 1, growing with the grid —
    the measured growth law behind 546 → 5889), and the Ritz-model
    iteration prediction within ±15% of actual."""
    from poisson_ellipse_tpu.obs import spectrum as obs_spectrum
    from poisson_ellipse_tpu.solver.engine import solve as engine_solve

    import jax.numpy as jnp

    rows = []
    all_ok = True
    prev_kappa = None
    for M, N, oracle in SPECTRUM_GRIDS:
        problem = Problem(M=M, N=N)
        if precomputed and (M, N) in precomputed:
            result, trace = precomputed[(M, N)]
        else:
            result, trace = engine_solve(
                problem, "xla", jnp.float32, history=True
            )
        rep = obs_spectrum.spectrum_report(
            trace, delta=problem.delta, actual_iters=int(result.iters)
        )
        pred = rep.get("predicted_iters")
        err = rep.get("predicted_err")
        ok = (
            bool(result.converged)
            and int(result.iters) == oracle
            and rep.get("available", False)
            and rep["kappa"] > 1.0
            and math.isfinite(rep["kappa"])
            and pred is not None
            and err is not None
            and abs(err) <= 0.15
            and (prev_kappa is None or rep["kappa"] > prev_kappa)
        )
        all_ok &= ok
        prev_kappa = rep.get("kappa") if rep.get("available") else prev_kappa
        row = {
            "grid": [M, N],
            "engine": "xla",
            "iters": int(result.iters),
            "converged": bool(result.converged),
            "kappa": rep.get("kappa"),
            "lambda_min": rep.get("lambda_min"),
            "lambda_max": rep.get("lambda_max"),
            "cg_rate": rep.get("cg_rate"),
            "iters_bound": rep.get("iters_bound"),
            "predicted_iters": pred,
            "predicted_err": err,
            "stagnated": rep.get("stagnated"),
        }
        rows.append(row)
        note(
            f"  [spectrum] {M}x{N}: kappa={row['kappa']} "
            f"rate={row['cg_rate']} predicted={pred} actual={row['iters']} "
            f"(oracle {oracle}) "
            + (
                f"err={err:+.1%} — OK"
                if ok
                else "— MISMATCH (kappa/prediction out of band)"
            ),
        )
    return rows, all_ok


def bench_recovery(grid: tuple[int, int] = (400, 600), oracle: int = 546):
    """Resilience row for the artifact: one guarded solve with a NaN
    injected into the carried residual mid-solve (``resilience.guard`` +
    ``resilience.faultinject``). The guard must detect it from the
    per-chunk health word, apply the direction-preserving true-residual
    restart, and reconverge to oracle parity (±2) — the detect-and-
    correct property, regression-checked in every artifact."""
    from poisson_ellipse_tpu.resilience import (
        FaultPlan,
        SolveError,
        guarded_solve,
        inject_nan,
    )

    import jax.numpy as jnp

    M, N = grid
    at = max(oracle // 2, 1)
    try:
        guarded = guarded_solve(
            Problem(M=M, N=N), "xla", jnp.float32, chunk=64,
            faults=FaultPlan(inject_nan(at, "r")),
        )
    except SolveError as e:
        note(
            f"  [recovery] {M}x{N} nan@{at}: solve aborted "
            f"({e.classification}) — recovery FAILED"
        )
        return {
            "grid": [M, N], "engine": "xla", "fault": "nan", "at": at,
            "converged": False, "aborted": e.classification,
        }, False
    n = int(guarded.result.iters)
    kinds = [event.kind for event in guarded.recoveries]
    ok = (
        bool(guarded.result.converged)
        and abs(n - oracle) <= 2
        and kinds == ["residual-restart"]
    )
    row = {
        "grid": [M, N],
        "engine": "xla",
        "fault": "nan",
        "at": at,
        "iters": n,
        "clean_iters": oracle,
        "converged": bool(guarded.result.converged),
        "recoveries": kinds,
    }
    note(
        f"  [recovery] {M}x{N} nan@{at}: {n} iterations "
        f"(clean oracle {oracle}), recoveries={kinds} "
        + ("— OK (oracle parity after recovery)" if ok else "— PARITY MISS"),
    )
    return row, ok


def bench_recycle(grid: tuple[int, int] = (128, 128), stream_len: int = 5,
                  scale_eps: float = 0.01):
    """Krylov recycling on a correlated request stream vs cold solves —
    the headline number of ``solver.recycle`` / ``runtime.solvecache``.

    One capture solve (history + a :data:`RECYCLE_CAP`-slot Lanczos
    ring) harvests the k-mode deflation basis; then a stream of
    ``stream_len`` correlated requests — the SAME operator with the rhs
    scalar-perturbed by ±``scale_eps`` (s·rhs has analytic solution s·u,
    so analytic-l2 parity is checkable per request) — runs twice:

    - **cold**: every request from x0 = 0 (the pre-recycling fleet);
    - **warm**: each request seeded semantic-cache style with the
      PREVIOUS request's solution (deliberately unscaled — a related,
      not identical, hit) and deflated on top via ``deflated_x0``
      against its true residual.

    The grid is chosen so the ring respects the basis-quality rule
    (cap ≥ ~40% of the iteration count — ``solver.recycle``): benching
    recycling with a starved ring would measure the misconfiguration,
    not the mechanism. Valid iff every solve converges, the warm
    stream's analytic l2 matches cold per request (≤10% relative: both
    streams sit on the same ~1e-3 discretisation floor and stop on the
    same step-norm δ, so the residual wiggle is solver-tolerance-level,
    two-sided, and bounded — measured ≤5% at the widest perturbation),
    and the mean iteration cut clears the ISSUE's ≥2× pin — which
    ``tools/bench_compare.py`` also hard-gates (``recycle-pct``).
    """
    import jax.numpy as jnp

    from poisson_ellipse_tpu.ops import assembly
    from poisson_ellipse_tpu.ops.stencil import apply_a
    from poisson_ellipse_tpu.solver import recycle as rec
    from poisson_ellipse_tpu.solver.pcg import pcg
    from poisson_ellipse_tpu.utils.error import l2_error_vs_analytic

    M, N = grid
    problem = Problem(M=M, N=N)
    a, b, rhs = assembly.assemble(problem, jnp.float32)
    h1 = jnp.asarray(problem.h1, rhs.dtype)
    h2 = jnp.asarray(problem.h2, rhs.dtype)

    # capture solve: cold, ring-carrying; its basis is what the stream
    # recycles (serve shape: first request of a bucket pays full price)
    res0, trace0, ring = pcg(
        problem, a, b, rhs, history=True, recycle=rec.RECYCLE_CAP
    )
    basis = rec.harvest(problem, a, b, trace0, ring)
    if not bool(res0.converged) or basis is None:
        note("  [recycle] capture solve failed to converge or harvest")
        return {"grid": [M, N], "valid": False}, False

    # the correlated stream: ±scale_eps scalar perturbations around 1
    scales = [
        1.0 + scale_eps * (i + 1) * (1 if i % 2 == 0 else -1)
        for i in range(stream_len)
    ]
    streams = {"cold": [], "warm": []}
    l2 = {"cold": [], "warm": []}
    converged = True
    t_stream = {}
    for mode in ("cold", "warm"):
        w_prev = res0.w
        # warm-up: compile both executables outside the timed loop
        pcg(problem, a, b, rhs).w.block_until_ready()
        pcg(problem, a, b, rhs, x0=res0.w).w.block_until_ready()
        t0 = time.perf_counter()
        for s in scales:
            rhs_s = rhs * s
            if mode == "warm":
                r0 = rhs_s - apply_a(w_prev, a, b, h1, h2)
                x0 = rec.deflated_x0(basis, rhs_s, x0=w_prev, residual=r0)
                result = pcg(
                    problem, a, b, rhs_s,
                    x0=w_prev if x0 is None else x0,
                )
            else:
                result = pcg(problem, a, b, rhs_s)
            result.w.block_until_ready()
            converged &= bool(result.converged)
            streams[mode].append(int(result.iters))
            l2[mode].append(float(l2_error_vs_analytic(problem, result.w / s)))
            w_prev = result.w
        t_stream[mode] = time.perf_counter() - t0

    mean_cold = statistics.fmean(streams["cold"])
    mean_warm = max(statistics.fmean(streams["warm"]), 1e-9)
    iter_cut = mean_cold / mean_warm
    l2_gap = max(
        abs(wv - cv) / cv for wv, cv in zip(l2["warm"], l2["cold"])
    )
    sps = {m: len(scales) / t_stream[m] for m in t_stream}
    ok = bool(converged and iter_cut >= 2.0 and l2_gap <= 0.10)
    row = {
        "grid": [M, N],
        "stream": len(scales),
        "ring_cap": rec.RECYCLE_CAP,
        "basis_rank": basis.rank,
        "capture_iters": int(res0.iters),
        "iters_cold": streams["cold"],
        "iters_warm": streams["warm"],
        "iters_cold_mean": round(mean_cold, 2),
        "iters_warm_mean": round(mean_warm, 2),
        "iter_cut": round(iter_cut, 2),
        "l2_rel_gap_max": l2_gap,
        "solves_per_s_cold": round(sps["cold"], 3),
        "solves_per_s_warm": round(sps["warm"], 3),
        "converged": bool(converged),
        "valid": ok,
    }
    note(
        f"  [recycle] {M}x{N} stream of {len(scales)}: iters "
        f"{mean_cold:.1f} cold -> {mean_warm:.1f} warm "
        f"({iter_cut:.1f}x cut), {sps['cold']:.2f} -> {sps['warm']:.2f} "
        f"solves/s, l2 gap {l2_gap:.2%} "
        + ("— OK" if ok else "— BELOW THE 2x PIN"),
    )
    return row, ok


def bench_geometry(grid: tuple[int, int] = (400, 600), oracle: int = 546):
    """The geometry key: the SDF-general assembly's cost and fidelity.

    Three facts per round, folded into ``valid``:

    - **parity** — the ellipse THROUGH the bisection quadrature matches
      the closed form to ≤1e-12 relative face fraction, and its f32
      solve lands within ±2 iterations of the oracle (the
      closed-form-stays-default acceptance, measured);
    - **assembly overhead** — host-f64 quadrature assembly time vs the
      closed form (a one-time setup cost, but it must stay a *setup*
      cost — regression-gated between rounds);
    - **composite solve** — an ellipse-minus-hole domain through the
      validated path: converged, discrete maximum principle held, and
      its T_solver as the arbitrary-geometry timing row.
    """
    import numpy as np

    from poisson_ellipse_tpu.geom import quadrature, sdf
    from poisson_ellipse_tpu.models import ellipse as ellipse_mod
    from poisson_ellipse_tpu.ops import assembly as assembly_mod
    from poisson_ellipse_tpu.solver.engine import build_solver
    from poisson_ellipse_tpu.utils.timing import fence

    M, N = grid
    p = Problem(M=M, N=N)

    t0 = time.perf_counter()
    assembly_mod.assemble_numpy(p)
    t_cf = time.perf_counter() - t0
    t0 = time.perf_counter()
    la, lb = quadrature.segment_lengths(p, sdf.Ellipse())
    t_quad = time.perf_counter() - t0

    gi = np.arange(M + 1, dtype=np.float64)
    gj = np.arange(N + 1, dtype=np.float64)
    x = p.a1 + gi * p.h1
    y = p.a2 + gj * p.h2
    xc, yc = x[:, None], y[None, :]
    la_cf = ellipse_mod.segment_length_vertical(
        xc - 0.5 * p.h1, yc - 0.5 * p.h2, yc + 0.5 * p.h2, np
    )
    lb_cf = ellipse_mod.segment_length_horizontal(
        yc - 0.5 * p.h2, xc - 0.5 * p.h1, xc + 0.5 * p.h1, np
    )
    frac_err = max(
        float(np.abs(la / p.h2 - la_cf / p.h2).max()),
        float(np.abs(lb / p.h1 - lb_cf / p.h1).max()),
    )

    solver, args, _ = build_solver(p, "xla", geometry=sdf.Ellipse())
    res = solver(*args)
    fence(res)
    sdf_iters = int(res.iters)

    composite = sdf.Difference(sdf.Ellipse(), sdf.Circle(r=0.25))
    solver_c, args_c, _ = build_solver(p, "xla", geometry=composite)
    res_c = solver_c(*args_c)
    fence(res_c)  # warm-up: compile + first dispatch out of the timing
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        res_c = solver_c(*args_c)
        fence(res_c)  # tpulint: disable=TPU008 — timing-protocol fence
        times.append(time.perf_counter() - t0)
    w_c = np.asarray(res_c.w)
    min_u = float(w_c.min())

    ok = (
        frac_err <= 1e-12
        and abs(sdf_iters - oracle) <= 2
        and bool(res_c.converged)
        and min_u >= -1e-6
    )
    row = {
        "grid": [M, N],
        "assembly_cf_s": round(t_cf, 5),
        "assembly_quad_s": round(t_quad, 5),
        "assembly_overhead_x": round(t_quad / max(t_cf, 1e-9), 2),
        "max_frac_err": frac_err,
        "sdf_ellipse_iters": sdf_iters,
        "oracle_iters": oracle,
        "composite": {
            "domain": "ellipse-minus-hole",
            "t_solver_s": round(statistics.median(times), 5),
            "iters": int(res_c.iters),
            "converged": bool(res_c.converged),
            "min_u": min_u,
        },
    }
    note(
        f"  [geometry] {M}x{N}: quad-vs-closed-form frac err "
        f"{frac_err:.2e}, sdf-ellipse {sdf_iters} iters (oracle "
        f"{oracle}), assembly {t_quad:.3f}s vs {t_cf:.3f}s, composite "
        f"{row['composite']['t_solver_s']}s/{row['composite']['iters']} "
        f"iters " + ("— OK" if ok else "— GEOMETRY CHECK FAILED"),
    )
    return row, ok


def bench_grad(grid: tuple[int, int] = (400, 600), lanes: int = 4,
               n_requests: int = 8):
    """The grad key: differentiable solving as a served workload.

    Two facts per round, folded into ``valid``:

    - **grad-solves/sec through the scheduler** — ``n_requests``
      ``grad=True`` requests (shifted-ellipse geometry, Dirichlet-energy
      objective) at ``grid`` drained through the continuous-batching
      scheduler with ``lanes`` candidate lanes: each is a primal + an
      IFT-adjoint lane solve (``diff.serving``), the batched-candidate
      traffic shape of a shape-optimization step. Valid iff every
      request completes with a finite nonzero gradient.
    - **adjoint-vs-primal iteration ratio per published grid** — one
      ``diff.adjoint`` gradient per GRIDS row; the adjoint reuses the
      same operator and preconditioner, so its iteration count should
      track the primal's (the ratio is the quoted cost of a gradient:
      ~2x a solve). Valid iff every adjoint converged.
    """
    import numpy as np

    from poisson_ellipse_tpu.diff.adjoint import ImplicitSolver
    from poisson_ellipse_tpu.geom import sdf
    from poisson_ellipse_tpu.serve.request import ServeRequest
    from poisson_ellipse_tpu.serve.scheduler import Scheduler

    M, N = grid
    p = Problem(M=M, N=N)
    geometry = {"kind": "ellipse", "cx": 0.05, "cy": -0.02, "rx": 0.9,
                "ry": 0.45}

    sched = Scheduler(lanes=lanes, chunk=32, queue_capacity=n_requests + 1,
                      keep_solutions=False)
    # warm the bucket executable before the timed stream (the compile
    # belongs to the coldstart key, not this one)
    warm = ServeRequest(problem=p, grad=True, geometry=dict(geometry),
                        objective={"kind": "energy"}, request_id="grad-warm")
    sched.submit_request(warm)
    sched.drain()
    sched.collect()

    t0 = time.perf_counter()
    for i in range(n_requests):
        req = ServeRequest(
            problem=p, grad=True, geometry=dict(geometry),
            objective={"kind": "energy"}, request_id=f"grad-{i:03d}",
        )
        sched.submit_request(req)
    results = sched.drain()
    wall = time.perf_counter() - t0

    ok = True
    for i in range(n_requests):
        res = results.get(f"grad-{i:03d}")
        good = (
            res is not None and res.outcome == "completed"
            and res.grad is not None
            and np.all(np.isfinite(res.grad))
            and float(np.abs(np.asarray(res.grad)).max()) > 0.0
        )
        ok &= bool(good)
    gps = n_requests / wall if wall > 0 else None

    # the per-grid adjoint/primal iteration ratio (one gradient per
    # published grid; the solver quotes both solves in `last`)
    rows = []
    import jax.numpy as jnp

    template = sdf.Ellipse(cx=0.05, cy=-0.02, rx=0.9, ry=0.45)
    for gm, gn, _oracle, _ref in GRIDS:
        solver = ImplicitSolver(Problem(M=gm, N=gn), template,
                                engine="xla")
        g = jax.grad(
            lambda q: jnp.sum(solver.solve(q) ** 2)
        )({"shape": jnp.asarray(sdf.params_of(template),
                                solver.dtype)})
        quotes = list(solver.last)
        ok &= (
            len(quotes) == 2
            and all(q["converged"] for q in quotes)
            and bool(np.all(np.isfinite(np.asarray(g["shape"]))))
        )
        primal_it = quotes[0]["iters"] if quotes else 0
        adjoint_it = quotes[1]["iters"] if len(quotes) > 1 else 0
        rows.append({
            "grid": [gm, gn],
            "primal_iters": primal_it,
            "adjoint_iters": adjoint_it,
            "ratio": round(adjoint_it / max(primal_it, 1), 3),
        })
        note(
            f"  [grad] {gm}x{gn}: primal {primal_it} + adjoint "
            f"{adjoint_it} iters (ratio "
            f"{rows[-1]['ratio']})"
        )

    row = {
        "grid": [M, N],
        "lanes": lanes,
        "n_requests": n_requests,
        "grad_solves_per_sec": (
            round(gps, 3) if gps is not None else None
        ),
        "wall_s": round(wall, 4),
        "rows": rows,
        "valid": bool(ok),
    }
    note(
        f"  [grad] {M}x{N} x{n_requests} grad requests over {lanes} "
        f"lanes: {row['grad_solves_per_sec']} grad-solves/s "
        + ("— OK" if ok else "— GRAD CHECK FAILED")
    )
    return row, ok


# the ABFT healthy-path overhead gate: checks-on vs checks-off T_solver
# at the headline grid (percent; tools/bench_compare.py diffs the
# measured overhead between rounds under [tool.bench_compare] abft-pp)
ABFT_OVERHEAD_GATE_PCT = 2.0


def bench_abft(grid: tuple[int, int] = (800, 1200)):
    """The ABFT key: the silent-corruption checks' healthy-path cost.

    One sharded solve at the headline grid with ``abft=False`` and one
    with ``abft=True`` (``parallel.pcg_sharded.build_sharded_stepper``),
    both fenced and timed over the full solve. The contract this key
    regression-pins: (1) collective counts per iteration are IDENTICAL
    — every checksum partial rides the existing stacked convergence
    psum, read from the jaxpr via ``obs.static_cost``; (2) the walltime
    overhead of checks-on is ≤ 2% of T_solver (the extra work is fused
    reductions over arrays the loop already touches). Single-device
    environments skip (``available: false``) rather than fake a mesh.
    """
    if len(jax.devices()) < 2:
        note("  [abft] fewer than 2 devices: overhead study skipped")
        return {"available": False}, True
    import jax.numpy as jnp

    from poisson_ellipse_tpu.obs.static_cost import loop_collectives
    from poisson_ellipse_tpu.parallel.mesh import make_mesh
    from poisson_ellipse_tpu.parallel.pcg_sharded import (
        build_sharded_stepper,
    )

    M, N = grid
    problem = Problem(M=M, N=N)
    mesh = make_mesh()
    stats = {}
    for abft in (False, True):
        try:
            init_fn, advance_fn = build_sharded_stepper(
                problem, mesh, jnp.float32, abft=abft
            )
            state0 = init_fn()
            # warm dispatch compiles the advance; the timed one is the
            # steady-state full solve (fenced)
            jax.block_until_ready(advance_fn(state0, 1))
            t0 = time.perf_counter()
            state = advance_fn(init_fn(), problem.max_iterations)
            jax.block_until_ready(state)  # tpulint: disable=TPU011
            t = time.perf_counter() - t0
            psum, ppermute = loop_collectives(
                advance_fn, (state0, problem.max_iterations)
            )
            stats[abft] = {
                "t": t,
                "iters": int(state[0]),
                "converged": bool(state[6]),
                "psum": psum,
                "ppermute": ppermute,
            }
        except Exception as e:  # noqa: BLE001 — the study must never kill
            # the artifact: the timing rows above already ran and must ship
            note(f"  [abft] study failed ({type(e).__name__}: {e})")
            return {"available": False, "error": str(e)}, True
    off, on = stats[False], stats[True]
    overhead_pct = (
        (on["t"] - off["t"]) / off["t"] * 100.0 if off["t"] > 0 else 0.0
    )
    same_collectives = (
        off["psum"] == on["psum"] and off["ppermute"] == on["ppermute"]
    )
    ok = (
        off["converged"] and on["converged"]
        and abs(on["iters"] - off["iters"]) <= 1
        and same_collectives
        and overhead_pct <= ABFT_OVERHEAD_GATE_PCT
    )
    row = {
        "available": True,
        "grid": [M, N],
        "mesh": [int(mesh.shape[a]) for a in mesh.axis_names],
        "t_off_s": round(off["t"], 5),
        "t_on_s": round(on["t"], 5),
        "overhead_pct": round(overhead_pct, 3),
        "gate_pct": ABFT_OVERHEAD_GATE_PCT,
        "iters_off": off["iters"],
        "iters_on": on["iters"],
        "psum_per_iter": on["psum"],
        "ppermute_per_iter": on["ppermute"],
        "collectives_identical": same_collectives,
        "ok": ok,
    }
    note(
        f"  [abft] {M}x{N}: off {off['t']:.4f}s, on {on['t']:.4f}s "
        f"-> {overhead_pct:+.2f}% (gate {ABFT_OVERHEAD_GATE_PCT:.0f}%), "
        f"psum/iter {off['psum']}->{on['psum']}, "
        f"ppermute/iter {off['ppermute']}->{on['ppermute']} "
        + ("— OK" if ok else "— GATE MISS"),
    )
    return row, ok


# bandwidth key: the modeled bf16/f32 byte ratio every cell must beat
# (acceptance: ≤ 0.6×), and the l2 parity band the guarded bf16 path
# must land in relative to the f32 cell (the guard's promotion rung
# finishes every narrow solve at full width, so parity is recovered,
# not approximate — the band absorbs iterate-path noise only)
BANDWIDTH_BYTE_RATIO_GATE = 0.6
BANDWIDTH_L2_BAND = 1.10
BANDWIDTH_GRID = (2400, 3200)


def bench_bandwidth(grid: tuple[int, int] = BANDWIDTH_GRID):
    """The memory-bandwidth-frontier key: {f32, bf16-storage} ×
    {pipelined, sstep} at the HBM-bound grid.

    Per cell: T_solver, achieved GB/s against the storage-width traffic
    model (``harness.roofline``), and the analytic l2_err. The f32
    cells run the raw engines fenced and warm (steady-state); the bf16
    cells run the PRODUCT path — ``resilience.guard`` with the storage
    promotion rung, because the raw narrow engines converge to the
    storage floor by design — under the guard's documented plain-wall-
    clock protocol (adapter builds included; ``protocol`` names this
    per cell, and the round-over-round gate in bench_compare compares
    like with like). A bf16 cell's GB/s apportions its bytes across
    the narrow phase and the full-width polish using the promotion
    iteration from the recovery log — never all-narrow for a run whose
    tail ran full-width. Gates folded into ``valid``: every cell
    converged, each bf16 cell's modeled HBM bytes/iter ≤ 0.6× its f32
    sibling's, and bf16 l2_err within the parity band of f32's.
    """
    import jax.numpy as jnp

    from poisson_ellipse_tpu.harness.roofline import (
        modeled_hbm_bytes_per_iter,
        roofline,
    )
    from poisson_ellipse_tpu.resilience.guard import guarded_solve
    from poisson_ellipse_tpu.solver.engine import build_solver
    from poisson_ellipse_tpu.utils.error import l2_error_vs_analytic

    M, N = grid
    problem = Problem(M=M, N=N)
    cells = []
    ok = True
    try:
        for engine in ("pipelined", "sstep"):
            f32_l2 = None
            for storage in (None, "bf16"):
                if storage is None:
                    solver, args, _ = build_solver(problem, engine)
                    jax.block_until_ready(solver(*args))  # warm compile
                    t0 = time.perf_counter()
                    result = solver(*args)
                    jax.block_until_ready(result)  # tpulint: disable=TPU011
                    t = time.perf_counter() - t0
                    iters = int(result.iters)
                    converged = bool(result.converged)
                    w = result.w
                    narrow_iters = None
                else:
                    t0 = time.perf_counter()
                    guarded = guarded_solve(
                        problem, engine, jnp.float32, storage_dtype=storage
                    )
                    jax.block_until_ready(guarded.result.w)  # tpulint: disable=TPU011
                    t = time.perf_counter() - t0
                    iters = int(guarded.result.iters)
                    converged = bool(guarded.result.converged)
                    w = guarded.result.w.astype(jnp.float32)
                    # iterations the NARROW phase ran: up to the
                    # promotion event (whole run if it never fired)
                    narrow_iters = iters
                    for ev in guarded.recoveries:
                        if ev.kind == "storage-promotion":
                            narrow_iters = min(narrow_iters, ev.at_iter)
                l2 = float(l2_error_vs_analytic(problem, w))
                if storage is None or narrow_iters is None:
                    roof = roofline(
                        problem, engine, iters, t, jnp.float32,
                        storage_dtype=storage,
                    )
                else:
                    # apportion: narrow_iters at bf16 bytes + the
                    # full-width polish at f32 bytes, over the one
                    # measured wall clock
                    from poisson_ellipse_tpu.harness.roofline import (
                        hbm_peak_bytes_per_s,
                        modeled_hbm_bytes_per_iter,
                    )

                    total_bytes = (
                        narrow_iters * modeled_hbm_bytes_per_iter(
                            problem, engine, jnp.float32,
                            storage_dtype=storage,
                        )
                        + max(iters - narrow_iters, 0)
                        * modeled_hbm_bytes_per_iter(
                            problem, engine, jnp.float32
                        )
                    )
                    gbps = total_bytes / t / 1e9 if t > 0 else 0.0
                    peak = hbm_peak_bytes_per_s()
                    roof = {
                        "hbm_gbps": round(gbps, 2),
                        "hbm_peak_frac": (
                            round(total_bytes / t / peak, 4)
                            if peak and t > 0 else None
                        ),
                    }
                modeled = modeled_hbm_bytes_per_iter(
                    problem, engine, jnp.float32, storage_dtype=storage
                )
                if storage is None:
                    f32_l2 = l2
                    byte_ratio, parity = None, True
                else:
                    f32_modeled = modeled_hbm_bytes_per_iter(
                        problem, engine, jnp.float32
                    )
                    byte_ratio = modeled / f32_modeled
                    parity = l2 <= BANDWIDTH_L2_BAND * f32_l2
                    ok &= byte_ratio <= BANDWIDTH_BYTE_RATIO_GATE and parity
                ok &= converged
                cells.append({
                    "engine": engine,
                    "storage": storage or "f32",
                    # f32 cells: fenced steady-state dispatch; bf16
                    # cells: the guard's plain wall clock, builds
                    # included (the documented resilience stance)
                    "protocol": (
                        "fenced-warm" if storage is None
                        else "guarded-wall-clock"
                    ),
                    "t_solver_s": round(t, 5),
                    "iters": iters,
                    **(
                        {"narrow_iters": narrow_iters}
                        if narrow_iters is not None else {}
                    ),
                    "converged": converged,
                    "l2_err": l2,
                    "hbm_gbps": roof["hbm_gbps"],
                    "hbm_peak_frac": roof["hbm_peak_frac"],
                    "modeled_bytes_per_iter": modeled,
                    **(
                        {"byte_ratio_vs_f32": round(byte_ratio, 4),
                         "l2_parity": parity}
                        if byte_ratio is not None else {}
                    ),
                })
                note(
                    f"  [bandwidth] {engine}/{storage or 'f32'} {M}x{N}: "
                    f"{t:.3f}s, {iters} iters, l2 {l2:.3e}, "
                    f"{roof['hbm_gbps']:.0f} GB/s"
                    + (
                        f", bytes ratio {byte_ratio:.2f}x"
                        if byte_ratio is not None else ""
                    )
                )
    except Exception as e:  # noqa: BLE001 — the study must never kill
        # the artifact: every other key's rows already ran and must ship
        note(f"  [bandwidth] study failed ({type(e).__name__}: {e})")
        return {"available": False, "error": str(e)}, True
    return {
        "available": True,
        "grid": [M, N],
        "byte_ratio_gate": BANDWIDTH_BYTE_RATIO_GATE,
        "l2_band": BANDWIDTH_L2_BAND,
        "cells": cells,
        "ok": ok,
    }, ok


THROUGHPUT_LANES = (1, 8, 32)
THROUGHPUT_GRIDS = ((400, 600, 546), (800, 1200, 989))


def bench_throughput():
    """The serving-throughput study: aggregate solves/sec vs lane count.

    Each row runs the ``batched`` engine with lanes ∈ {1, 8, 32} under
    the same marginal-cost protocol as the grid rows (chained dispatches,
    fixed per-dispatch overhead cancelled), at 400×600 and the 800×1200
    headline grid. Lane 0 of the batched engine is bit-identical to the
    single solve, so the oracle check is exact equality per lane-batch.
    ``speedup_vs_1lane`` is the aggregate-throughput ratio — the number
    that justifies batching on a dispatch/latency-bound chip (BENCH_r05:
    1.29 ms/solve at 400×600 leaves most of the chip idle at 1 lane).
    """
    rows = []
    all_ok = True
    for M, N, oracle in THROUGHPUT_GRIDS:
        base_sps = None
        first_row = True
        for lanes in THROUGHPUT_LANES:
            report = run_once(
                Problem(M=M, N=N),
                mode="single",
                dtype="f32",
                engine="batched",
                lanes=lanes,
                repeat=REPS,
                batch=3,
            )
            sps = report.solves_per_sec or 0.0
            # vs-1-lane stays honest when the baseline row failed: later
            # rows carry None rather than silently rebasing on lanes=8
            if first_row:
                speedup = 1.0 if sps else None
            else:
                speedup = round(sps / base_sps, 3) if base_sps else None
            ok = (
                report.converged
                and report.iters == oracle
                and report.quarantined == 0
            )
            all_ok &= ok
            note(
                f"  [throughput] {M}x{N} lanes={lanes}: "
                f"T_batch={report.t_solver:.4f}s -> {sps:.2f} solves/s "
                f"({speedup}x vs 1 lane) iters={report.iters} "
                f"(oracle {oracle}) converged={report.converged}",
            )
            rows.append({
                "grid": [M, N],
                "lanes": lanes,
                "engine": "batched",
                "t_batch_s": round(report.t_solver, 5),
                "solves_per_sec": round(sps, 3),
                "speedup_vs_1lane": speedup,
                "iters": report.iters,
                "converged": report.converged,
            })
            if first_row:
                base_sps = sps or None
                first_row = False
    return rows, all_ok


def bench_coldstart(grid: tuple[int, int] = (400, 600), lanes: int = 8):
    """Compile-time vs solve-time split, warm pool off and on.

    Cold start is its own latency budget: the split lets future BENCH
    rounds regression-check it separately from T_solver. Three numbers:
    the AOT trace+compile cost a cacheless worker pays (`t_compile_s`),
    the steady-state solve it then runs (`t_solve_s`), and the warm
    pool's answer — a second request for the same shape bucket must be a
    cache HIT returning the already-compiled executable (`pool_hit`,
    `t_pool_warm_s` ≈ 0), which is the no-recompile contract
    ``runtime.compile_cache`` exists for.
    """
    import jax.numpy as jnp

    from poisson_ellipse_tpu.runtime.compile_cache import WarmPool
    from poisson_ellipse_tpu.solver.engine import build_solver
    from poisson_ellipse_tpu.utils.timing import fence

    M, N = grid
    problem = Problem(M=M, N=N)
    # warm pool OFF: the cold worker's path — trace + compile, timed
    solver, args, _ = build_solver(problem, "batched", jnp.float32,
                                   lanes=lanes)
    t0 = time.perf_counter()
    compiled = solver.lower(*args).compile()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    result = compiled(*args)
    fence(result)
    t_solve = time.perf_counter() - t0

    # warm pool ON: miss fills the bucket, the re-request must hit
    pool = WarmPool()
    t0 = time.perf_counter()
    first = pool.warmup("batched", grid, jnp.float32, lanes)
    t_pool_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    second = pool.warmup("batched", grid, jnp.float32, lanes)
    t_pool_warm = time.perf_counter() - t0
    hit = second.compiled is first.compiled and pool.hits == 1
    ok = bool(hit and jnp.all(result.converged))
    row = {
        "grid": [M, N],
        "engine": "batched",
        "lanes": lanes,
        "t_compile_s": round(t_compile, 4),
        "t_solve_s": round(t_solve, 4),
        "t_pool_cold_s": round(t_pool_cold, 4),
        "t_pool_warm_s": round(t_pool_warm, 6),
        "pool_hit": bool(hit),
    }
    note(
        f"  [coldstart] {M}x{N} lanes={lanes}: compile {t_compile:.3f}s "
        f"vs solve {t_solve:.4f}s; warm pool cold {t_pool_cold:.3f}s -> "
        f"re-request {t_pool_warm * 1e3:.2f} ms "
        + ("(HIT, same executable) — OK" if hit else "— MISSED (regression)"),
    )
    return row, ok


def bench_serving(n_requests: int = 32, lanes: int = 4,
                  grids=((40, 40), (48, 48)), seed: int = 0):
    """The serving key: sustained solves/sec + latency quantiles under a
    Poisson arrival stream, vs the static-batch baseline.

    The continuous-batching scheduler (``serve.scheduler``) retires and
    refills lanes at chunk boundaries, so a converged lane's slot goes
    straight to the next queued request; the static baseline solves the
    same request set in fixed ``lanes``-wide batches where every lane
    waits for the slowest (PR 5's whole-batch semantics). Reported:
    ``solves_per_sec`` for both disciplines plus the scheduler's
    p50/p99 time-in-system. Validity = every request completed (zero
    lost, zero unclassified) — the serving layer must never trade
    correctness for the throughput number.
    """
    import random

    import jax.numpy as jnp

    from poisson_ellipse_tpu.batch.driver import solve_batched
    from poisson_ellipse_tpu.serve import Scheduler

    rng = random.Random(seed)
    shapes = [rng.choice(list(grids)) for _ in range(n_requests)]

    # continuous batching: seeded arrival stream through the scheduler
    sched = Scheduler(lanes=lanes, chunk=32, queue_capacity=n_requests + 1,
                      keep_solutions=False)
    t0 = time.perf_counter()
    for i, (M, N) in enumerate(shapes):
        sched.submit(Problem(M=M, N=N), request_id=f"bench-{i:03d}")
        sched.step()
    results = sched.drain()
    t_stream = time.perf_counter() - t0
    lat = sorted(r.total_s for r in results.values())
    completed = sum(1 for r in results.values() if r.outcome == "completed")
    ok = completed == n_requests and len(results) == n_requests

    # static baseline: same requests, fixed lanes-wide batches per shape
    t0 = time.perf_counter()
    for M, N in sorted(set(shapes)):
        count = sum(1 for s in shapes if s == (M, N))
        p = Problem(M=M, N=N)
        done = 0
        while done < count:
            width = min(lanes, count - done)
            static = solve_batched(p, width, "batched", jnp.float32,
                                   chunk=1 << 30)
            ok &= bool(static.result.converged.all())
            done += width
    t_static = time.perf_counter() - t0

    def q(p):
        return lat[min(int(p * len(lat)), len(lat) - 1)] if lat else None

    row = {
        "requests": n_requests,
        "lanes": lanes,
        "grids": [list(g) for g in grids],
        "solves_per_sec": round(n_requests / t_stream, 3),
        "static_solves_per_sec": round(n_requests / t_static, 3),
        "latency_p50_s": round(q(0.50), 4) if lat else None,
        "latency_p99_s": round(q(0.99), 4) if lat else None,
        "completed": completed,
        "valid": bool(ok),
    }
    note(
        f"  [serving] {n_requests} requests over {sorted(set(shapes))} "
        f"lanes={lanes}: continuous {row['solves_per_sec']} solves/s "
        f"(p50 {row['latency_p50_s']}s, p99 {row['latency_p99_s']}s) vs "
        f"static {row['static_solves_per_sec']} solves/s — "
        + ("OK" if ok else "INCOMPLETE (regression)"),
    )
    return row, ok


# noise floor for the replicas-scaling gate: in-process replicas share
# one chip, so "non-decreasing aggregate throughput" is asserted within
# the serving wall-clock noise band, not as strict monotonic growth
FLEET_AGG_NOISE_FRAC = 0.25
FLEET_REPLICA_COUNTS = (1, 2, 3)


def bench_fleet(n_requests: int = 24, lanes: int = 2,
                grids=((10, 10), (12, 12)), seed: int = 0):
    """The fleet key: aggregate solves/sec vs replica count, plus the
    handoff-latency p99 of a mid-stream replica kill.

    The same seeded Poisson stream runs through a 1-, 2- and 3-replica
    fleet (``fleet.FleetRouter``: compile-bucket affinity routing,
    per-replica lanes). Validity folded into ``valid``: every request
    completes at every width, and aggregate solves/sec is non-decreasing
    1→3 replicas within the serving noise floor (in-process replicas
    share one chip, so the claim the gate defends is "replication does
    not COST throughput" — the scale-out win itself is a multi-host
    story). A final 2-replica round kills replica 0 mid-stream and
    reports the journal-handoff latency p99 — the fleet's
    recovery-time number, regression-gated by ``tools/bench_compare.py``
    (``fleet-agg-pct``).
    """
    import random
    import tempfile

    from poisson_ellipse_tpu.fleet import FleetRouter
    from poisson_ellipse_tpu.obs import metrics as obs_metrics
    from poisson_ellipse_tpu.resilience import faultinject

    def run_stream(replicas: int, kill_at=None, rejoin_at=None):
        rng = random.Random(seed)
        faults = []
        if kill_at is not None:
            faults.append(faultinject.replica_kill(
                at_request=kill_at, replica=0,
            ))
        with tempfile.TemporaryDirectory() as td:
            router = FleetRouter(
                replicas=replicas, journal_dir=td, lanes=lanes,
                chunk=4, queue_capacity=n_requests + 1,
                keep_solutions=False, backoff_base_s=0.001,
                faults=faultinject.FaultPlan(*faults),
            )
            t0 = time.perf_counter()
            for i in range(n_requests):
                M, N = rng.choice(list(grids))
                router.submit(Problem(M=M, N=N),
                              request_id=f"fleet-{i:03d}")
                router.step()
                if (rejoin_at is not None and i >= rejoin_at
                        and not router.rejoins
                        and not router.replicas[0].live):
                    router.rejoin_replica(0)
            results = router.drain()
            wall = time.perf_counter() - t0
        completed = sum(
            1 for r in results.values() if r.outcome == "completed"
        )
        return router, results, completed, wall

    # warm the bucket executables outside every timed round: the lru
    # cache (serve.scheduler._bucket_advance) is process-wide, so
    # WITHOUT this the 1-replica round would eat every compile and the
    # scaling comparison would measure the cache, not the fleet
    run_stream(1)

    rows = []
    all_ok = True
    prev_sps = None
    non_decreasing = True
    for replicas in FLEET_REPLICA_COUNTS:
        _, results, completed, wall = run_stream(replicas)
        sps = n_requests / wall if wall > 0 else 0.0
        ok = completed == n_requests and len(results) == n_requests
        if prev_sps is not None and sps < prev_sps * (
            1.0 - FLEET_AGG_NOISE_FRAC
        ):
            non_decreasing = False
        all_ok &= ok
        note(
            f"  [fleet] {replicas} replica(s) x {lanes} lanes: "
            f"{n_requests} requests in {wall:.3f}s -> {sps:.2f} "
            f"solves/s aggregate, completed {completed}/{n_requests} "
            + ("— OK" if ok else "— INCOMPLETE (regression)"),
        )
        rows.append({
            "replicas": replicas,
            "lanes": lanes,
            "solves_per_sec": round(sps, 3),
            "completed": completed,
            "wall_s": round(wall, 4),
        })
        prev_sps = sps
    all_ok &= non_decreasing

    # the kill→rejoin round: handoff latency under a real mid-stream
    # death, then the victim re-enters as a fresh incarnation and the
    # kill→first-completed-solve latency of the rejoiner is the fleet's
    # recovery-time-to-capacity number (rejoin_latency_s, p99)
    hist = obs_metrics.REGISTRY.histogram(
        obs_metrics.HANDOFF_LATENCY_SECONDS
    )
    rejoin_hist = obs_metrics.REGISTRY.histogram(
        obs_metrics.REJOIN_LATENCY_SECONDS
    )
    count_before = hist.count
    rejoin_count_before = rejoin_hist.count
    kill_at = max(n_requests // 3, 1)
    rejoin_at = max(2 * n_requests // 3, kill_at + 1)
    router, results, completed, _wall = run_stream(
        2, kill_at=kill_at, rejoin_at=rejoin_at
    )
    handoff_p99 = hist.quantile(0.99)
    rejoin_p99 = rejoin_hist.quantile(0.99)
    kill_ok = (
        completed == n_requests
        and router.handoffs >= 1
        and hist.count > count_before
        and router.rejoins >= 1
        and rejoin_hist.count > rejoin_count_before
    )
    all_ok &= kill_ok
    note(
        f"  [fleet] kill→rejoin drill (2 replicas, kill@{kill_at}, "
        f"rejoin@{rejoin_at}): completed {completed}/{n_requests}, "
        f"{router.handoffs} handoff(s), {router.adopted_total} adopted, "
        f"{router.rejoins} rejoin(s), "
        f"handoff p99 {handoff_p99 if handoff_p99 is None else round(handoff_p99, 5)}s, "
        f"rejoin p99 {rejoin_p99 if rejoin_p99 is None else round(rejoin_p99, 5)}s "
        + ("— OK" if kill_ok else "— RECOVERY MISS (regression)"),
    )
    row = {
        "rows": rows,
        "non_decreasing": non_decreasing,
        "handoff_p99_s": (
            round(handoff_p99, 6) if handoff_p99 is not None else None
        ),
        "rejoin_latency_s": (
            round(rejoin_p99, 6) if rejoin_p99 is not None else None
        ),
        "kill_completed": completed,
        "handoffs": router.handoffs,
        "adopted": router.adopted_total,
        "rejoins": router.rejoins,
    }
    return row, all_ok


def bench_collectives():
    """Static collective accounting for the artifact: psum/ppermute per
    iteration read from the jaxpr (``obs.static_cost``) on a 1×2 mesh of
    whatever devices this process has. THE regression this key pins: the
    classical sharded loop pays 2 psum per iteration, the pipelined
    recurrence 1. Single-device environments skip (``available: false``)
    rather than fake a mesh."""
    if len(jax.devices()) < 2:
        note("  [collectives] fewer than 2 devices: static accounting skipped")
        return {"available": False}, True
    from poisson_ellipse_tpu.obs import static_cost

    try:
        table = static_cost.collectives_table(
            Problem(M=40, N=40), engines=("xla", "pipelined"), mesh_shape=(1, 2)
        )
    except Exception as e:  # noqa: BLE001 — accounting must never kill the
        # artifact: the timing rows above already ran and must ship
        note(f"  [collectives] static accounting failed ({type(e).__name__}: {e})")
        return {"available": False, "error": str(e)}, True
    classical = table["engines"]["xla"]["psum_per_iter"]
    pipelined = table["engines"]["pipelined"]["psum_per_iter"]
    ok = classical == 2 and pipelined == 1
    note(
        f"  [collectives] static psum/iter (1x2 mesh): classical "
        f"{classical}, pipelined {pipelined} "
        + ("— OK (2 vs 1)" if ok else "— REGRESSION (expected 2 vs 1)"),
    )
    return table, ok


def main() -> int:
    from poisson_ellipse_tpu.runtime.compile_cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    note(f"devices: {jax.devices()}")
    headline_t, baseline, all_ok = None, None, True
    grid_rows = []
    for M, N, oracle, ref_t in GRIDS:
        t, ok, row = bench_grid(M, N, oracle, ref_t)
        all_ok &= ok
        grid_rows.append(row)
        if ref_t is not None:
            note(
                f"    vs stage4 1-GPU P100 ({ref_t}s): {ref_t / t:.2f}x",
            )
        if (M, N) == HEADLINE:
            headline_t, baseline = t, ref_t
    # BASELINE.json target configs (no reference numbers published).
    # The 8192² row is the config-4 grid on ONE chip (the xl engine
    # streams state beyond VMEM) — the reference reaches this size only
    # on a multi-node MPI cluster; pod weak-scaling remains
    # bench_multichip --real's job.
    config2, ok2 = bench_baseline_config(1024, 1024, "config2", amortised=True)
    north, okn = bench_baseline_config(4096, 4096, "north-star", amortised=False)
    xl8k, ok8 = bench_baseline_config(
        8192, 8192, "config4-1chip", amortised=False, repeat=1
    )
    pipe_row, okp = bench_pipelined_row()
    # the preconditioner study: mg-pcg/cheb-pcg vs the diag rows above
    # (ROADMAP item 1 — iteration reduction, l2 parity, wall-clock win)
    precond_rows, okpc = bench_precond(grid_rows)
    # full multigrid as the solver: O(N) F-cycle + verified handoff vs
    # mg-pcg per grid, work-units-per-point pin, ≥4096² headline row
    fmg_row, okfm = bench_fmg(precond_rows)
    # the closed-loop autotuner: tuned-vs-static wall clock per shape
    # (never-loses, measured) + registry round-trip
    tune_row, okat = bench_autotune()
    # the serving layer: lane-batched throughput + the cold-start split
    # (f32, before the f64 flip below)
    thr_rows, okt = bench_throughput()
    cold_row, okcs = bench_coldstart()
    # the continuous-batching front-end: sustained solves/sec + p50/p99
    # under a Poisson arrival stream vs the static-batch baseline
    serve_row, oksv = bench_serving()
    # the replicated fleet: aggregate solves/sec at 1/2/3 replicas +
    # journal-handoff latency p99 under a mid-stream replica kill
    fleet_row, okfl = bench_fleet()
    eps_rows, oke = bench_eps_sweep()
    # observability rows (f32, so they run before the f64 flip below):
    # on-device convergence telemetry + static collective accounting
    conv_row, okc, conv_solve = bench_convergence()
    coll_table, okl = bench_collectives()
    # spectral diagnostics: kappa + predicted-vs-actual iterations per
    # grid from the Lanczos-of-CG reconstruction (f32, pre-f64-flip);
    # the 400x600 history solve is bench_convergence's, not a re-run
    spec_rows, oks = bench_spectrum(precomputed={(400, 600): conv_solve})
    # resilience row: an injected NaN mid-solve must recover to oracle
    # parity through the guard (f32, before the f64 flip below)
    rec_row, okr = bench_recovery()
    # Krylov recycling: correlated stream vs cold solves — iteration
    # cut (≥2x pin) + solves/sec at equal analytic l2 (f32, pre-f64)
    rcy_row, okrc = bench_recycle()
    # ABFT overhead study: silent-corruption checks on vs off — ≤2%
    # T_solver and identical collective counts (f32, pre-f64-flip)
    abft_row, oka = bench_abft()
    # memory-bandwidth frontier: {f32, bf16-storage} × {pipelined,
    # sstep} at the HBM-bound grid — GB/s, T_solver, l2 parity and the
    # ≤0.6× modeled byte ratio (f32, pre-f64-flip)
    bw_row, okbw = bench_bandwidth()
    # geometry study: SDF-quadrature-vs-closed-form parity + overhead
    # and the composite-domain timing row (f32, pre-f64-flip)
    geom_row, okg = bench_geometry()
    # differentiable solving: grad-solves/sec through the scheduler +
    # adjoint-vs-primal iteration ratio per grid (f32, pre-f64-flip)
    grad_row, okgr = bench_grad()
    all_ok &= (
        ok2 & okn & ok8 & okp & okpc & okfm & okat & okt & okcs & oksv
        & okfl & oke & okc & okl & oks & okr & okrc & oka & okg & okgr
        & okbw
    )
    # f64 row last: resolve_dtype flips jax_enable_x64 process-globally,
    # which must not perturb the timed f32 rows above
    okf, f64_row = bench_f64_row()
    all_ok &= okf
    record = {
        "metric": "T_solver 800x1200 (989 PCG iters to 1e-6), f32, 1 chip",
        "value": round(headline_t, 5),
        "unit": "s",
        "vs_baseline": round(baseline / headline_t, 2),
        "valid": all_ok,
        # chip the run measured on, so the regenerated README
        # names the actual part instead of a hardcoded one
        "device": jax.devices()[0].device_kind,
        # machine-readable rows: tools/update_readme_bench.py
        # regenerates the README's measured table from these
        "grids": grid_rows,
        "config2": config2,
        "north_star": north,
        "config4_1chip": xl8k,
        "pipelined": pipe_row,
        # the preconditioner rows: mg-pcg (+ headline cheb-pcg) vs the
        # diag-PCG grid rows — iters/t_solver regression-gated per grid
        # by tools/bench_compare.py ([tool.bench_compare] precond-*)
        "precond": precond_rows,
        # full multigrid as the solver (mg.fmg): T_solver + work units
        # per grid point vs mg-pcg per grid, the constant-work pin, and
        # the ≥4096² headline row — gated by tools/bench_compare.py
        # ([tool.bench_compare] fmg-pct)
        "fmg": fmg_row,
        # the closed-loop autotuner (runtime.autotune): tuned-vs-static
        # wall clock per shape; a tuned config that loses to the static
        # default hard-fails the gate ([tool.bench_compare]
        # autotune-pct + the tuned_loses pin)
        "autotune": tune_row,
        # lane-batched serving throughput: solves/sec at lanes 1/8/32
        # under the marginal-cost protocol (batch.* engines)
        "throughput": thr_rows,
        # compile-vs-solve split, warm pool off/on: cold-start latency
        # as its own regression-checked number (runtime.compile_cache)
        "coldstart": cold_row,
        # continuous-batching serve layer: sustained solves/sec + p50/p99
        # latency under a Poisson arrival stream vs static batching
        # (serve.scheduler's retire-and-refill discipline)
        "serving": serve_row,
        # the replicated fleet: aggregate solves/sec at 1/2/3 replicas
        # (non-decreasing within the serving noise floor) + journal-
        # handoff latency p99 under a mid-stream replica kill, gated by
        # tools/bench_compare.py ([tool.bench_compare] fleet-agg-pct)
        "fleet": fleet_row,
        "eps_sweep": eps_rows,
        # on-device per-iteration telemetry summary (solve history=True)
        "convergence": conv_row,
        # static psum/ppermute accounting: the pipelined-1-vs-classical-2
        # property as a regression-checked artifact metric
        "collectives": coll_table,
        # Lanczos spectral diagnostics: kappa(M^-1 A) + predicted-vs-
        # actual iterations per grid (obs.spectrum), diffed between
        # rounds by tools/bench_compare.py
        "spectrum": spec_rows,
        # guarded-solve fault drill: injected NaN -> residual restart ->
        # oracle-parity reconvergence (resilience.guard)
        "recovery": rec_row,
        # Krylov recycling (solver.recycle): correlated-stream iteration
        # cut vs cold solves at equal analytic l2 + solves/sec — the
        # ≥2x cut is hard-pinned here AND by tools/bench_compare.py
        # ([tool.bench_compare] recycle-pct)
        "recycle": rcy_row,
        # ABFT silent-corruption checks: healthy-path overhead (≤2%
        # gate) with the 1-psum/iter cadence pinned identical on vs off
        "abft": abft_row,
        # memory-bandwidth frontier: {f32, bf16-storage} × {pipelined,
        # sstep} cells — measured GB/s + T_solver + analytic l2 per
        # cell, the ≤0.6× modeled byte-ratio gate, bf16-vs-f32 l2
        # parity via the guard's promotion rung; diffed between rounds
        # by tools/bench_compare.py ([tool.bench_compare] bandwidth-pct)
        "bandwidth": bw_row,
        # SDF geometry: quadrature-vs-closed-form parity (≤1e-12 frac
        # err, ±2 iters), host assembly overhead, and the composite-
        # domain (ellipse-minus-hole) solve row (geom.*)
        "geometry": geom_row,
        # differentiable solving (diff/): grad-solves/sec through the
        # scheduler (batched candidate lanes; gated by
        # tools/bench_compare.py [tool.bench_compare] grad-pct) +
        # adjoint-vs-primal iteration ratio per published grid
        "grad": grad_row,
        "f64": f64_row,
    }
    trace_event("bench_artifact", **record)
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
