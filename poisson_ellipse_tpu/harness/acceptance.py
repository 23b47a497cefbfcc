"""On-accelerator acceptance gate: every engine compiles and hits the oracles.

``python -m poisson_ellipse_tpu.harness.acceptance`` runs each solver
engine on the small reference grids and asserts the published weighted
iteration counts (15/26/50 @ 10²/20²/40², from the compiled reference
stage1 code), plus the sharded path over whatever device mesh exists.
On a TPU this is the real-compile gate the CPU test suite cannot be
(tests/conftest.py pins the CPU backend; the Pallas engines interpret
there) — run it on the chip to prove the Mosaic kernels still build and
agree with the reference before trusting a bench number. The reference
has no automated tests at all (SURVEY §4); its manual oracle — identical
iteration counts across implementations (Этап1-4 tables) — is exactly
what this gate automates across *engines*.

The preconditioner engines (``mg-pcg``/``cheb-pcg``) exist to *change*
the iteration count, so the reference oracle cannot apply to them; their
rows gate on the ROADMAP's pivot instead — converged, strictly fewer
iterations than the diagonal oracle, and l2-vs-analytic no more than
10% above the diagonal solve's (one-sided: more accurate never fails).
The s-step rows hit the oracle only with the f64 Gram x64 provides; in
an x64-off process (the chip's default) they gate on the measured band
of their f32-Gram counts (``F32_GRAM_CEILINGS``).

``--headline`` adds the 400×600 row (546 iterations) with the auto
engine. Exit code 0 iff every row passes.
"""

from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops.sstep_pcg import gram_dtype
from poisson_ellipse_tpu.solver.engine import (
    ENGINES,
    PRECOND_ENGINES,
    SSTEP_ENGINES,
    build_solver,
)

# (M, N) -> weighted-norm oracle iterations (reference stage1 code,
# compiled and run; see BASELINE.md "Iteration counts")
SMALL_ORACLES = {(10, 10): 15, (20, 20): 26, (40, 40): 50}
HEADLINE = ((400, 600), 546)
# x64 off (the chip's default) the s-step Gram accumulates in f32
# (ops.sstep_pcg.gram_dtype) and the block the s=4 recurrence stops in
# becomes a rounding fact: under 40 one-ulp RHS perturbations (CPU, PR
# 21) the counts spread over 15 / 26-33 / 50-67 at 10²/20²/40², the
# same for both stencils; the chip gave sstep 51, sstep-pallas 63 at
# 40². The s-step rows' band is [oracle, ceiling], each ceiling that
# spread's top + 1 — the one-bf16-pass contraction defect the chip
# showed (70 at 40²) still fails it.
F32_GRAM_CEILINGS = {(10, 10): 16, (20, 20): 34, (40, 40): 68}


def _diag_l2(M: int, N: int, _cache={}) -> float:
    """l2-vs-analytic of the diagonal-preconditioned reference solve —
    the parity yardstick for the preconditioner engines (cached: one
    extra small solve per grid, not per engine)."""
    from poisson_ellipse_tpu.utils.error import l2_error_vs_analytic

    if (M, N) not in _cache:
        problem = Problem(M=M, N=N)
        solver, args, _ = build_solver(problem, "xla", jnp.float32)
        _cache[(M, N)] = float(
            l2_error_vs_analytic(problem, solver(*args).w)
        )
    return _cache[(M, N)]


def _row(engine: str, M: int, N: int, oracle: int) -> tuple[bool, str]:
    problem = Problem(M=M, N=N)
    # the pipelined recurrence is a documented reordering: its contract
    # is the oracle ±2, not equality (ops.pipelined_pcg accuracy note)
    slack = 2 if engine.startswith("pipelined") else 0
    # the batched engines gate at 2 lanes (the lane plumbing must build,
    # not just the degenerate single-lane case); lane 0 is bit-identical
    # to the classical solve, so the classical oracle applies exactly —
    # and ±2 for the batched-pipelined reordering
    lanes = 2 if engine.startswith("batched") else 1
    slack = 2 if engine == "batched-pipelined" else slack
    try:
        solver, args, resolved = build_solver(
            problem, engine, jnp.float32, lanes=lanes
        )
        result = solver(*args)
        if lanes > 1:  # per-lane result: every lane must hit the oracle
            iters = int(jnp.max(result.iters))
            converged = bool(jnp.all(result.converged))
        else:
            iters = int(result.iters)
            converged = bool(result.converged)
        if engine in PRECOND_ENGINES:
            # the preconditioner engines exist to CHANGE the iteration
            # count, so the reference oracle pivots to the analytic
            # solution (ROADMAP item 1): converged, strictly fewer
            # iterations than the diagonal oracle, and l2-vs-analytic
            # no worse than +10% of the diagonal solve — the rule the
            # bench `precond` key enforces at the published grids.
            # (fmg never reaches this matrix: run_acceptance filters
            # it out below — its gates live in tests/test_fmg, the
            # graft-entry smoke check and the bench `fmg` key.)
            from poisson_ellipse_tpu.utils.error import (
                l2_error_vs_analytic,
            )

            l2 = float(l2_error_vs_analytic(problem, result.w))
            ref = _diag_l2(M, N)
            # one-sided: at equal δ the V-cycle often lands BELOW diag's
            # algebraic error — only worse-than-diag (>10%) is a miss
            ok = (
                converged and iters < oracle
                and ref > 0 and l2 <= ref * 1.10
            )
            note = (
                f"iters={iters} (< diag {oracle}) "
                f"l2={l2:.2e} (diag {ref:.2e})"
            )
            return ok, note
        lo, hi = oracle - slack, oracle + slack
        band = f"±{slack}" if slack else ""
        if (engine in SSTEP_ENGINES
                and jnp.dtype(gram_dtype(jnp.float32)) == jnp.float32):
            lo, hi = oracle, F32_GRAM_CEILINGS[(M, N)]
            band = f"..{hi}, f32 Gram"
        ok = converged and lo <= iters <= hi
        note = f"iters={iters} (oracle {oracle}{band})"
        if lanes > 1:
            note += f" [{lanes} lanes]"
        if resolved != engine:
            note += f" [auto->{resolved}]"
    except Exception as e:  # tpulint: disable=TPU009 — a build/compile failure IS the finding (reported as the row)
        ok, note = False, f"{type(e).__name__}: {e}"
    return ok, note


def _sharded_row(
    M: int, N: int, oracle: int, stencil_impl: str, devices
) -> tuple[bool, str]:
    from poisson_ellipse_tpu.parallel.mesh import make_mesh
    from poisson_ellipse_tpu.parallel.pcg_sharded import solve_sharded

    slack = 2 if stencil_impl == "pipelined" else 0
    try:
        result = solve_sharded(
            Problem(M=M, N=N), mesh=make_mesh(devices), dtype=jnp.float32,
            stencil_impl=stencil_impl,
        )
        iters = int(result.iters)
        ok = bool(result.converged) and abs(iters - oracle) <= slack
        note = (
            f"iters={iters} (oracle {oracle}"
            + (f"±{slack})" if slack else ")")
            + f" over {len(devices)} device(s)"
        )
    except Exception as e:  # tpulint: disable=TPU009 — the failure becomes the report row
        ok, note = False, f"{type(e).__name__}: {e}"
    return ok, note


def run_acceptance(headline: bool = False, out=sys.stderr,
                   grids=tuple(SMALL_ORACLES), devices=None) -> bool:
    """Print one row per (grid, engine) and return whether all passed.

    ``grids`` picks which of ``SMALL_ORACLES``' grids the engine rows
    run at; the sharded rows run at the last of them, over ``devices``
    (default: all)."""
    devices = jax.devices() if devices is None else list(devices)
    print(f"backend: {jax.default_backend()}  devices: {devices}",
          file=out)
    all_ok = True
    # fmg is gated elsewhere, not by the oracle matrix: its iteration
    # count is the verification-handoff count (not an oracle fact), and
    # each row would pay a Lanczos probe + F-cycle build per grid —
    # tests/test_fmg pins its l2 parity, __graft_entry__'s fmg smoke
    # check drives it through the real CLI, and the bench `fmg` key
    # gates it on the chip
    engines = [e for e in ENGINES if e not in ("auto", "fmg")]
    for M, N in grids:
        oracle = SMALL_ORACLES[(M, N)]
        for engine in engines:
            ok, note = _row(engine, M, N, oracle)
            all_ok &= ok
            print(f"  {'ok ' if ok else 'FAIL'} {M}x{N} {engine:9s} {note}",
                  file=out)
    M, N = grids[-1]
    for impl in ("xla", "pallas", "fused", "pipelined"):
        ok, note = _sharded_row(M, N, SMALL_ORACLES[(M, N)], impl,
                                devices)
        all_ok &= ok
        print(
            f"  {'ok ' if ok else 'FAIL'} {M}x{N} "
            f"{'sharded/' + impl:14s} {note}",
            file=out,
        )
    if headline:
        (M, N), oracle = HEADLINE
        ok, note = _row("auto", M, N, oracle)
        all_ok &= ok
        print(f"  {'ok ' if ok else 'FAIL'} {M}x{N} {'auto':9s} {note}",
              file=out)
    print("ACCEPTANCE " + ("PASS" if all_ok else "FAIL"), file=out)
    return all_ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m poisson_ellipse_tpu.harness.acceptance"
    )
    ap.add_argument(
        "--headline", action="store_true",
        help="also run 400x600 (546-iteration oracle) with the auto engine",
    )
    args = ap.parse_args(argv)
    from poisson_ellipse_tpu.runtime.compile_cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    return 0 if run_acceptance(headline=args.headline) else 1


if __name__ == "__main__":
    sys.exit(main())
