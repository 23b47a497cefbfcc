"""One caller, one request at a time: every request goes through
``build_solver(problem, "auto")``, the solve and a fetch of ``w`` to the
host — what ``solver.engine.solve`` does, with the fetch a caller makes.

The requests draw their ε from the configuration's choices, each equally
often, in an order set by the seed. Set-up serves each distinct problem
once. The window closes on a request boundary.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from benchmark import traffic

# ε draws in one cycle of the closed loop; the window reuses the cycle
CYCLE = 4096


def serve_one(ctx, eps) -> dict:
    from poisson_ellipse_tpu.solver.engine import build_solver

    with ctx.span("build"):
        solver, args, _ = build_solver(ctx.problem(eps), engine="auto")
    with ctx.span("dispatch"):
        result = solver(*args)
    with ctx.span("wait"):
        w = np.asarray(result.w)
        iters, converged = int(result.iters), bool(result.converged)
    return {"eps": eps, "w": w, "iters": iters, "converged": converged}


def run(ctx) -> dict:
    for eps in ctx.config["eps_choices"]:
        serve_one(ctx, eps)
    requests = itertools.cycle(
        traffic.request_eps(ctx.config, ctx.seed, CYCLE))

    answers = []
    ctx.open_window()
    t0 = time.perf_counter()
    while True:
        answers.append(serve_one(ctx, next(requests)))
        elapsed = time.perf_counter() - t0
        if elapsed >= ctx.seconds:
            break
    ctx.close_window()

    return {
        "attempted": len(answers),
        "failed": sum(not a["converged"] for a in answers),
        "metrics": {"request_s": elapsed / len(answers)},
        "iters": [a["iters"] for a in answers],
        "answers": answers,
    }
