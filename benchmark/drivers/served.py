"""An open loop of independent users in front of ``serve.Scheduler``.

The scheduler is built with the program's defaults (lanes, chunk, queue
capacity, ``keep_solutions``) on one chip. On more, one scheduler drives
them all: ``Scheduler(mesh=...)`` over a mesh of the cell's chips, with
the default lanes on every chip, whole lanes sharded over the mesh.
Set-up pre-warms the configuration's
bucket and serves a few requests through it, so the chunk advance, the
refill and the retirement are compiled before the window.

One client thread interleaves ``submit`` at the scheduled arrival times
(``benchmark.traffic.schedule``), ``step()`` and ``collect()``. A
request's latency runs from its scheduled arrival to the ``collect()``
that hands back its result. After the window the client keeps stepping,
with no new arrivals, until every request due in the window has ended or
the mix's ``drain_s`` has passed; a request that never ends, or ends
other than completed and converged, counts as missing, at the latency it
had waited when the client gave up.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import traffic

WARM = "warm-"


def warm(ctx, sched) -> None:
    """Compile every program the window drives: a full bucket with a
    queue behind it, so lanes retire and refill. Bounded by the mix's
    ``drain_s``: a scheduler that never finishes shows in the window,
    which leaves out what warm-up requests still return there."""
    sched.prewarm(ctx.problem(None))
    choices = ctx.config["eps_choices"]
    n = 2 * sched.lanes + 1
    for i in range(n):
        sched.submit(ctx.problem(choices[i % len(choices)]),
                     request_id=WARM + str(i))
    done, t_end = 0, time.perf_counter() + float(ctx.mix["drain_s"])
    while done < n and time.perf_counter() < t_end:
        sched.step()
        done += len(sched.collect())


def build(ctx):
    from poisson_ellipse_tpu.serve import Scheduler

    if len(ctx.devices) == 1:
        return Scheduler()
    from poisson_ellipse_tpu.parallel.mesh import make_mesh
    from poisson_ellipse_tpu.serve.scheduler import DEFAULT_LANES

    return Scheduler(lanes=DEFAULT_LANES * len(ctx.devices),
                     mesh=make_mesh(ctx.devices))


def run(ctx) -> dict:
    sched = build(ctx)
    warm(ctx, sched)
    arrivals = traffic.schedule(ctx.config, ctx.mix, ctx.seed, ctx.seconds)
    results, lag = {}, []
    submitted = 0

    def collect(now):
        with ctx.span("collect"):
            for rid, res in sched.collect().items():
                if not rid.startswith(WARM):
                    results[int(rid)] = (res, now)

    ctx.open_window()
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= ctx.seconds:
            break
        while submitted < len(arrivals) and arrivals[submitted][0] <= now:
            t_due, eps = arrivals[submitted]
            with ctx.span("submit"):
                shed = sched.submit(ctx.problem(eps),
                                    request_id=str(submitted))
            if shed is not None:
                results[submitted] = (shed, now)
            lag.append(now - t_due)
            submitted += 1
        if len(results) < submitted:
            with ctx.span("scheduler.step"):
                sched.step()
        else:
            nxt = arrivals[submitted][0] if submitted < len(arrivals) \
                else ctx.seconds
            with ctx.span("sleep"):
                time.sleep(max(0.0, min(nxt, ctx.seconds) - now))
        collect(time.perf_counter() - t0)
    window_s = time.perf_counter() - t0
    ctx.close_window()
    completed_in_window = sum(
        res.outcome == "completed" and res.converged
        for res, _ in results.values())
    backlog = submitted - len(results)

    drain_end = window_s + float(ctx.mix["drain_s"])
    while len(results) < submitted:
        now = time.perf_counter() - t0
        if now >= drain_end:
            break
        sched.step()
        collect(time.perf_counter() - t0)
    gave_up = time.perf_counter() - t0
    del sched

    latency, answers, waits = [], [], []
    missing = 0
    for i in range(submitted):
        t_due, eps = arrivals[i]
        res, t_done = results.get(i, (None, gave_up))
        ok = res is not None and res.outcome == "completed" and res.converged
        latency.append(t_done - t_due)
        if not ok:
            missing += 1
            continue
        waits.append(res.time_in_queue_s)
        answers.append({"eps": eps, "w": res.w, "iters": res.iters,
                        "converged": res.converged})
    return {
        "attempted": submitted,
        "failed": missing,
        "metrics": {
            "latency_p50_s": float(np.percentile(latency, 50)),
            "solves_per_s": completed_in_window / window_s,
        },
        "latency_s": latency,
        "queue_wait_s": waits,
        "submit_lag_s": lag,
        "iters": [a["iters"] for a in answers],
        "unanswered": missing,
        "answers": answers,
        "notes": {"backlog_at_close": backlog,
                  "submitted_in_window": submitted,
                  "latency_percentiles_s": {
                      str(q): float(np.percentile(latency, q))
                      for q in (50, 90, 95, 99)}},
    }
