"""Split each f32 engine's l2 error into discretisation and algebraic parts.

``l2`` against the analytic solution mixes two errors: the grid's
(discretisation, what an exact solve of the discrete system still
misses) and the solve's (algebraic, how far the f32 iterate sits from
that exact discrete solution). Two f32 engines that differ only in
rounding should agree on the first and scatter in the second. This
script measures both, per engine, against an f64 ``xla`` solve of the
same grid run to a tight δ (the discrete solution):

    python -m tools.diag_precision 4096 4096 --engines xla,fused,xl
    python -m tools.diag_precision 40 60 --engines xla,fused  # CPU: interpret

An engine named ``sharded/<stencil>`` runs ``parallel.pcg_sharded``
with that stencil over a mesh of every visible device.

One JSON line per solve: iterations, ``l2`` (vs analytic), ``alg``
(weighted l2 distance to the f64 discrete solution, over all nodes) and
``l2_of_alg`` (that distance over the nodes ``l2`` counts). The f64
rows come last, under ``jax.enable_x64``: ``xla-f64`` at the problem's
own δ (what exact arithmetic gives at the same stopping rule) and the
tight reference itself (its stop may be the 1e-15 breakdown guard —
by then the step is far below δ).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from poisson_ellipse_tpu.models import ellipse
from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.parallel.mesh import make_mesh
from poisson_ellipse_tpu.parallel.pcg_sharded import build_sharded_solver
from poisson_ellipse_tpu.solver.engine import build_solver
from poisson_ellipse_tpu.utils.error import l2_error_vs_analytic

# δ of the f64 reference: four decades under the problems' 1e-6, where
# the step is far below any f32 engine's error (the solve may stop on the
# 1e-15 breakdown guard first, at a step of 2-4e-10 on the chip)
REF_DELTA = 1e-10


def _solve(problem: Problem, engine: str, dtype, warm: bool = True):
    if engine.startswith("sharded/"):  # over every visible device
        solver, args = build_sharded_solver(
            problem, make_mesh(jax.devices()), dtype,
            stencil_impl=engine.removeprefix("sharded/"),
        )
        resolved = engine
    else:
        solver, args, resolved = build_solver(problem, engine, dtype)
    if warm:  # compile + one untimed solve
        jax.block_until_ready(solver(*args))
    t0 = time.perf_counter()
    result = solver(*args)
    jax.block_until_ready(result)
    return result, resolved, time.perf_counter() - t0


def _in_d(problem: Problem) -> np.ndarray:
    x = problem.a1 + np.arange(problem.M + 1) * problem.h1
    y = problem.a2 + np.arange(problem.N + 1) * problem.h2
    return np.asarray(ellipse.is_in_d(x[:, None], y[None, :]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("M", type=int)
    ap.add_argument("N", type=int)
    ap.add_argument("--engines", default="xla,fused,xl")
    ap.add_argument("--skip-same", action="store_true",
                    help="no f64 solve at the problem's own δ (saves one "
                    "slow f64 solve at large grids)")
    ap.add_argument("--ref", help="an .npy to load the f64 reference from "
                    "(saved there when missing): one reference, many trees")
    args = ap.parse_args(argv)

    problem = Problem(M=args.M, N=args.N)
    hw = problem.h1 * problem.h2
    rows = []
    for engine in args.engines.split(","):
        res, resolved, solve_s = _solve(problem, engine, jnp.float32)
        rows.append(dict(engine=resolved, dtype="f32", iters=int(res.iters),
                         converged=bool(res.converged),
                         l2=float(l2_error_vs_analytic(problem, res.w)),
                         solve_s=solve_s, w=np.asarray(res.w, np.float64)))

    if args.ref and os.path.exists(args.ref):
        w_ref = np.load(args.ref)
    else:
        # f64 on the chip: compile + solve timed together, no warm-up
        with jax.enable_x64(True):
            f64_rows = []
            if not args.skip_same:
                same, _, s_same = _solve(problem, "xla", jnp.float64,
                                         warm=False)
                f64_rows.append(("xla-f64", same, s_same))
            tight_problem = dataclasses.replace(problem, delta=REF_DELTA)
            tight, _, s_tight = _solve(tight_problem, "xla", jnp.float64,
                                       warm=False)
            w_ref = np.asarray(tight.w)
            f64_rows.append(("xla-f64-ref", tight, s_tight))
            for name, res, s in f64_rows:
                rows.append(dict(
                    engine=name, dtype="f64", iters=int(res.iters),
                    converged=bool(res.converged),
                    breakdown=bool(res.breakdown), diff=float(res.diff),
                    delta=(REF_DELTA if name.endswith("ref")
                           else problem.delta),
                    l2=float(l2_error_vs_analytic(problem, res.w)),
                    solve_s=s, w=np.asarray(res.w)))
        if args.ref:
            np.save(args.ref, w_ref)

    in_d = _in_d(problem)
    for row in rows:
        err = row.pop("w") - w_ref
        row["alg"] = float(np.sqrt(np.sum(err * err) * hw))
        row["l2_of_alg"] = float(np.sqrt(np.sum(np.where(in_d, err, 0.0) ** 2)
                                         * hw))
        print(json.dumps({"grid": [args.M, args.N], **row}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
