"""Back-to-back solves of one problem: the upstream's T_solver protocol.

Set-up builds the solver once — ``solver.engine.build_solver(problem,
"auto")`` on one chip, ``parallel.pcg_sharded.build_sharded_solver`` over
a mesh of the cell's chips otherwise — and runs one warm-up solve. The
window calls the built solver again and again, each call fenced with
``block_until_ready`` and its iterations and convergence read, and
closes on a solve boundary. ε is drawn once for the run from the seed.

Every solve's iterations and convergence are checked. The answers ``w``
compared with the reference are a sample of ``SAMPLE`` solves of the
window, drawn from the seed by reservoir sampling: the device holds at
most ``SAMPLE`` + 1 answers, not the whole window's, so that
``memory_peak_bytes`` stays the solver's own footprint.
"""

from __future__ import annotations

import time

from benchmark import traffic

SAMPLE = 2


def build(ctx, problem):
    if len(ctx.devices) == 1:
        from poisson_ellipse_tpu.solver.engine import build_solver

        solver, args, _ = build_solver(problem, engine="auto")
        return solver, args
    from poisson_ellipse_tpu.parallel.mesh import make_mesh
    from poisson_ellipse_tpu.parallel.pcg_sharded import build_sharded_solver

    return build_sharded_solver(problem, make_mesh(ctx.devices))


def run(ctx) -> dict:
    import jax

    eps = traffic.eps_for_run(ctx.config, ctx.seed)
    with ctx.span("build"):
        solver, args = build(ctx, ctx.problem(eps))
    warm = solver(*args)
    jax.block_until_ready(warm)
    warm_iters = int(warm.iters)
    del warm

    pick = traffic.rng_for(ctx.seed)
    solves, sample = [], []
    ctx.open_window()
    t0 = time.perf_counter()
    while True:
        with ctx.span("dispatch"):
            result = solver(*args)
        with ctx.span("wait"):
            jax.block_until_ready(result)
            iters, converged = int(result.iters), bool(result.converged)
        solves.append((iters, converged))
        answer = {"eps": eps, "w": result.w, "iters": iters,
                  "converged": converged}
        del result
        # each solve of the window lands in the sample with equal chance
        if len(sample) < SAMPLE:
            sample.append(answer)
        else:
            slot = int(pick.integers(len(solves)))
            if slot < SAMPLE:
                sample[slot] = answer
        del answer
        elapsed = time.perf_counter() - t0
        if elapsed >= ctx.seconds:
            break
    ctx.close_window()
    del solver, args

    failed = sum(not c or k != warm_iters for k, c in solves)
    # the comparison counts the sample's unconverged answers itself
    unanswered = (sum(not c for _, c in solves)
                  - sum(not a["converged"] for a in sample))
    return {
        "attempted": len(solves),
        "failed": failed,
        "metrics": {"solve_s": elapsed / len(solves)},
        "iters": [k for k, _ in solves],
        "eps": eps,
        "unanswered": unanswered,
        "answers": sample,
    }
