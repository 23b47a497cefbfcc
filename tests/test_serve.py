"""The continuous-batching serve layer (`serve/`) — ISSUE 7.

The contracts this file pins:

- admission is bounded and loud: a full queue sheds with
  ``retry_after_s`` (exit-code 5 contract), a deadline the projected
  wait already overruns is shed at the door;
- deadline semantics at chunk granularity: expiry while queued is shed
  un-dispatched; expiry mid-solve cancels at a chunk boundary with a
  partial result; expiry exactly at completion returns the result with
  no spurious miss (converged lanes retire first);
- the retry ladder walks quarantined lane → fresh lane → guarded
  single solve, each rung a classified outcome;
- the journal is write-ahead and replay-complete: a killed scheduler's
  admitted-but-unfinished requests are replayed by its successor, with
  double completion rejected at the journal;
- the chaos invariants hold under injected NaN + fake OOM + a
  mid-stream kill: zero lost, zero double-completed, all outcomes
  classified (seeded, ≥50 requests);
- the lane-refill chunk advance composes with the lane-sharded mesh at
  EXACTLY 1 psum/iter (jaxpr-pinned), refill included;
- every lifecycle event is request-addressed (trace schema v3) and the
  serving metrics (queue_depth, time_in_queue_seconds,
  deadline_miss_total, shed_total) land in the registry.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs import metrics as obs_metrics
from poisson_ellipse_tpu.obs import trace as obs_trace
from poisson_ellipse_tpu.resilience.faultinject import Fault, FaultPlan
from poisson_ellipse_tpu.serve import (
    DoubleCompletionError,
    RequestJournal,
    Scheduler,
    ServeRequest,
    run_chaos,
)


class FakeClock:
    """A hand-cranked monotonic clock: deadline semantics become
    deterministic instead of racing the test host."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def make_scheduler(**kw):
    kw.setdefault("lanes", 2)
    kw.setdefault("chunk", 8)
    kw.setdefault("backoff_base_s", 0.001)
    return Scheduler(**kw)


# -- admission / backpressure ------------------------------------------------


def test_queue_full_sheds_with_retry_after():
    sched = make_scheduler(queue_capacity=2)
    assert sched.submit(Problem(M=10, N=10)) is None
    assert sched.submit(Problem(M=10, N=10)) is None
    shed = sched.submit(Problem(M=10, N=10))
    assert shed is not None and shed.outcome == "shed"
    assert shed.detail == "queue-full"
    assert shed.retry_after_s > 0
    assert shed.exit_code == 5
    assert not shed.dispatched
    results = sched.drain()
    # the two admitted requests still complete; the shed one is terminal
    done = [r for r in results.values() if r.outcome == "completed"]
    assert len(done) == 2


def test_shed_at_admission_allows_same_id_resubmission():
    # "shed" promises "never queued, safe to resubmit after
    # retry_after_s" (the request.py outcome table) — the recorded shed
    # result must not make the honest resubmission read as a duplicate
    sched = make_scheduler(lanes=1, queue_capacity=1)
    assert sched.submit(Problem(M=10, N=10), request_id="first") is None
    shed = sched.submit(Problem(M=10, N=10), request_id="again")
    assert shed is not None and shed.outcome == "shed"
    sched.drain()
    assert sched.submit(Problem(M=10, N=10), request_id="again") is None
    assert sched.drain()["again"].outcome == "completed"


def test_journal_write_failure_retracts_the_admission(tmp_path):
    # write-ahead means a failed journal write must un-queue the
    # request through the queue API (depth gauge stays consistent),
    # not promise durability the disk refused
    sched = make_scheduler(journal=str(tmp_path / "j.journal"))

    def refuse(req):
        raise OSError("disk full")

    sched.journal.record_admit = refuse
    with pytest.raises(OSError):
        sched.submit(Problem(M=10, N=10))
    assert len(sched.queue) == 0
    assert obs_metrics.REGISTRY.gauge("queue_depth").value == 0


def test_infeasible_deadline_shed_at_admission():
    clock = FakeClock()
    sched = make_scheduler(clock=clock, idle=clock.advance)
    # projected wait is strictly positive, so a deadline of 0 from now
    # cannot be met — reject at the door, never queue
    shed = sched.submit(Problem(M=10, N=10), deadline_s=0.0)
    assert shed is not None and shed.outcome == "shed"
    assert shed.detail == "deadline-infeasible"


# -- deadline semantics (the satellite matrix) -------------------------------


def test_deadline_expiry_while_queued_is_shed_never_dispatched():
    clock = FakeClock()
    sched = make_scheduler(lanes=1, clock=clock, idle=clock.advance)
    # a long-running request occupies the single lane...
    sched.submit(Problem(M=12, N=12, delta=1e-7), request_id="hog")
    sched.step()
    # ...so this one waits in queue past its (feasible-at-admission)
    # deadline
    assert sched.submit(
        Problem(M=10, N=10), deadline_s=10.0, request_id="late"
    ) is None
    clock.advance(11.0)
    results = sched.drain()
    late = results["late"]
    assert late.outcome == "deadline-miss"
    assert late.detail == "expired-in-queue"
    assert not late.dispatched and not late.partial
    assert results["hog"].outcome == "completed"


def test_deadline_expiry_mid_solve_cancels_with_partial_result():
    clock = FakeClock()
    sched = make_scheduler(clock=clock, idle=clock.advance)
    sched.submit(Problem(M=12, N=12, delta=1e-7), deadline_s=5.0,
                 request_id="victim")
    sched.step()  # dispatched, some chunks done
    assert "victim" not in sched.results
    clock.advance(6.0)
    results = sched.drain()
    res = results["victim"]
    assert res.outcome == "deadline-miss"
    assert res.detail == "expired-mid-solve"
    assert res.dispatched and res.partial
    # the partial contract: progress up to the cancelling chunk boundary
    assert res.iters > 0 and np.isfinite(res.diff)
    assert res.w is not None  # the partial iterate, cropped


def test_deadline_expiry_exactly_at_completion_returns_result():
    clock = FakeClock()
    # chunk larger than the solve: the lane converges inside the first
    # chunk, and the deadline passes during it — at the boundary both
    # "converged" and "expired" are true, and converged must win
    sched = make_scheduler(chunk=4096, clock=clock, idle=clock.advance)
    sched.submit(Problem(M=10, N=10), deadline_s=1.0, request_id="edge")
    sched._fill_lanes()
    clock.advance(2.0)  # deadline passes while the chunk runs
    results = sched.drain()
    res = results["edge"]
    assert res.outcome == "completed"
    assert res.converged and res.w is not None
    assert res.detail is None  # no spurious miss recorded


def test_deadline_miss_metric_counts():
    obs_metrics.REGISTRY.reset()
    try:
        clock = FakeClock()
        sched = make_scheduler(clock=clock, idle=clock.advance)
        sched.submit(Problem(M=12, N=12, delta=1e-7), deadline_s=5.0)
        sched.step()
        clock.advance(6.0)
        sched.drain()
        snap = obs_metrics.REGISTRY.snapshot()
        assert snap["counters"]["deadline_miss_total"] == 1
        assert "time_in_queue_seconds" in snap["histograms"]
    finally:
        obs_metrics.REGISTRY.reset()


# -- retire / refill ---------------------------------------------------------


def test_mixed_shapes_pack_one_bucket_and_solve_their_own_problems():
    # 10x10 and 12x12 both bucket to 12x12: one executable, per-lane
    # h/δ/mask — each request must still match its own single solve
    from poisson_ellipse_tpu.solver.pcg import solve as pcg_solve

    sched = make_scheduler()
    sched.submit(Problem(M=10, N=10), request_id="small")
    sched.submit(Problem(M=12, N=12), request_id="big")
    results = sched.drain()
    assert len(sched._ctxs) == 1  # one bucket context served both
    for rid, M in (("small", 10), ("big", 12)):
        single = pcg_solve(Problem(M=M, N=M), jnp.float32)
        res = results[rid]
        assert res.outcome == "completed"
        assert res.w.shape == (M + 1, M + 1)
        np.testing.assert_allclose(
            res.w, np.asarray(single.w), rtol=0, atol=1e-5
        )


def test_refill_reuses_the_compiled_bucket_executable():
    from poisson_ellipse_tpu.serve import scheduler as sched_mod

    sched = make_scheduler(lanes=1)
    fn_cache_info_before = sched_mod._bucket_advance.cache_info()
    for i in range(3):
        sched.submit(Problem(M=10, N=10), request_id=f"r{i}")
    sched.drain()
    info = sched_mod._bucket_advance.cache_info()
    # one bucket build at most (possibly cached from an earlier test):
    # serving 3 sequential requests through one lane never rebuilds
    assert info.misses - fn_cache_info_before.misses <= 1


def test_iteration_cap_classifies_cap_outcome():
    sched = make_scheduler()
    # δ unreachable in 5 iterations: the per-request cap must end it
    sched.submit(Problem(M=12, N=12, delta=1e-12, max_iter=5),
                 request_id="capped")
    res = sched.drain()["capped"]
    assert res.outcome == "cap"
    assert res.iters == 5
    assert res.exit_code == 1


# -- the retry ladder --------------------------------------------------------


def test_nan_fault_retries_on_fresh_lane_and_completes():
    plan = FaultPlan(Fault("nan", at_iter=4, field="r",
                           request_id="victim"))
    sched = make_scheduler(faults=plan, max_retries=1)
    sched.submit(Problem(M=10, N=10), request_id="victim")
    sched.submit(Problem(M=10, N=10), request_id="bystander")
    results = sched.drain()
    assert results["victim"].outcome == "completed"
    assert results["victim"].attempts == 2  # one quarantine, one retry
    assert results["bystander"].outcome == "completed"
    assert results["bystander"].attempts == 1
    assert plan.faults[0].fired


def test_oom_fault_walks_ladder_and_completes():
    plan = FaultPlan(Fault("oom", at_iter=2, request_id="victim"))
    sched = make_scheduler(faults=plan, max_retries=1)
    sched.submit(Problem(M=10, N=10), request_id="victim")
    res = sched.drain()["victim"]
    assert res.outcome == "completed"
    assert res.attempts == 2


def test_total_s_spans_retries_from_first_admission():
    clock = FakeClock()
    plan = FaultPlan(Fault("nan", at_iter=2, field="r",
                           request_id="victim"))
    sched = make_scheduler(faults=plan, max_retries=1, clock=clock,
                           idle=clock.advance, backoff_base_s=0.5)
    sched.submit(Problem(M=10, N=10), request_id="victim")
    sched.step()  # first attempt on the lane
    clock.advance(10.0)  # time the failed attempt burns
    res = sched.drain()["victim"]
    assert res.outcome == "completed" and res.attempts == 2
    # end-to-end latency anchors on the FIRST admission: the 10 s lost
    # to the poisoned attempt counts (bench's p99 reads this field) —
    # only the per-visit queue-wait is allowed to reset on requeue
    assert res.total_s >= 10.0
    assert res.time_in_queue_s < 10.0


def test_requeue_overflow_failure_reports_dispatched_and_no_shed():
    plan = FaultPlan(Fault("nan", at_iter=2, field="r",
                           request_id="victim"))
    sched = make_scheduler(lanes=1, queue_capacity=1, faults=plan,
                           max_retries=1)
    sched.submit(Problem(M=10, N=10), request_id="victim")
    sched.step()  # victim on the lane
    sched.submit(Problem(M=10, N=10), request_id="filler")  # queue full
    shed_before = obs_metrics.REGISTRY.counter("shed_total").value
    results = sched.drain()
    res = results["victim"]
    assert res.outcome == "failed"
    assert res.detail == "requeue-shed-under-overload"
    # the request really ran before its lane died: consumers use
    # `dispatched` to separate "never ran" from "ran and failed"
    assert res.dispatched
    # and its terminal outcome is failed, not shed — the shed counter
    # must keep equalling the number of shed OUTCOMES
    assert obs_metrics.REGISTRY.counter("shed_total").value == shed_before
    assert results["filler"].outcome == "completed"


def test_guarded_fallback_queue_wait_excludes_solve_time(monkeypatch):
    clock = FakeClock()
    plan = FaultPlan(Fault("nan", at_iter=2, field="r",
                           request_id="victim", persistent=True))
    sched = make_scheduler(faults=plan, max_retries=0, clock=clock,
                           idle=clock.advance)
    from poisson_ellipse_tpu.resilience import guard as guard_mod

    real = guard_mod.guarded_solve

    def slow(*args, **kwargs):
        clock.advance(30.0)  # the fallback solve takes 30 fake seconds
        return real(*args, **kwargs)

    monkeypatch.setattr(guard_mod, "guarded_solve", slow)
    sched.submit(Problem(M=10, N=10), request_id="victim")
    res = sched.drain()["victim"]
    assert res.outcome == "completed" and res.detail == "guarded-fallback"
    # queue-wait accounting stops at the fallback's dispatch: the solve
    # is service time, not queueing — while total_s keeps the whole span
    assert res.time_in_queue_s < 30.0
    assert res.total_s >= 30.0


def test_persistent_fault_exhausts_budget_then_guarded_fallback():
    plan = FaultPlan(Fault("nan", at_iter=2, field="r",
                           request_id="victim", persistent=True))
    sched = make_scheduler(faults=plan, max_retries=2)
    sched.submit(Problem(M=10, N=10), request_id="victim")
    res = sched.drain()["victim"]
    # every laned attempt is poisoned; the final rung is the guarded
    # single solve, which the request-addressed fault cannot reach
    assert res.outcome == "completed"
    assert res.detail == "guarded-fallback"
    assert res.attempts == 4  # 1 initial + 2 retries + the fallback


# -- journal / replay --------------------------------------------------------


def test_journal_snapshot_is_atomic_and_replay_complete(tmp_path):
    path = tmp_path / "journal.json"
    sched = make_scheduler(journal=RequestJournal(path))
    for i in range(4):
        sched.submit(Problem(M=10, N=10), request_id=f"r{i}")
    sched.step()  # two in flight, two queued; then the "kill"
    assert not list(tmp_path.glob(".journal-*")), "no temp litter"
    successor = make_scheduler(journal=RequestJournal(path))
    assert successor.replay() == 4
    results = successor.drain()
    assert {results[f"r{i}"].outcome for i in range(4)} == {"completed"}
    journal = RequestJournal(path)
    assert journal.counts() == {
        "admitted": 4, "finished": 4, "unfinished": 0,
    }


def test_journal_refuses_double_completion(tmp_path):
    journal = RequestJournal(tmp_path / "j.json")
    req = ServeRequest(problem=Problem(M=10, N=10), request_id="once")
    journal.record_admit(req)
    journal.record_outcome("once", "completed")
    with pytest.raises(DoubleCompletionError):
        journal.record_outcome("once", "completed")
    with pytest.raises(DoubleCompletionError):
        journal.record_admit(req)
    with pytest.raises(KeyError):
        journal.record_outcome("never-admitted", "completed")


def test_replay_overflow_waits_in_backlog_never_terminally_shed(tmp_path):
    # a restart can arrive with more journaled admissions than one
    # queue's worth; the overflow re-enters in waves as lanes drain —
    # durably-acknowledged requests are never terminally shed by replay
    path = tmp_path / "journal.json"
    journal = RequestJournal(path)
    for i in range(6):
        journal.record_admit(
            ServeRequest(problem=Problem(M=10, N=10), request_id=f"r{i}")
        )
    successor = make_scheduler(
        journal=RequestJournal(path), queue_capacity=2, lanes=1,
    )
    assert successor.replay() == 6
    assert len(successor.queue) == 2 and len(successor._replay_backlog) == 4
    results = successor.drain()
    assert {results[f"r{i}"].outcome for i in range(6)} == {"completed"}
    assert RequestJournal(path).counts()["unfinished"] == 0


def test_journal_compacts_finished_records_to_o_live_snapshots(tmp_path):
    import json as _json

    path = tmp_path / "j.json"
    journal = RequestJournal(path)
    for i in range(5):
        journal.record_admit(
            ServeRequest(problem=Problem(M=10, N=10), request_id=f"r{i}")
        )
        journal.record_outcome(f"r{i}", "completed")
    journal.record_admit(
        ServeRequest(problem=Problem(M=10, N=10), request_id="live")
    )
    # the snapshot holds only the live admission; finished requests
    # survive as a durable counter, not ever-growing records
    with open(path, encoding="utf-8") as fh:
        snap = _json.load(fh)
    assert set(snap["requests"]) == {"live"}
    assert snap["finished"] == 5
    reloaded = RequestJournal(path)
    assert reloaded.counts() == {
        "admitted": 6, "finished": 5, "unfinished": 1,
    }
    assert [r.request_id for r in reloaded.unfinished(0.0)] == ["live"]
    assert journal.state_of("r0") == {"state": "done"}
    assert journal.state_of("r0-nonexistent") is None


def test_duplicate_request_id_is_refused_without_touching_the_original(
        tmp_path):
    # a second live submission under the same id can never get its own
    # outcome slot: it must be refused at the door — not crash the serve
    # loop with a DoubleCompletionError, not overwrite the original
    sched = make_scheduler(journal=RequestJournal(tmp_path / "j.json"))
    assert sched.submit(Problem(M=10, N=10), request_id="dup") is None
    refused = sched.submit(Problem(M=12, N=12), request_id="dup")
    assert refused is not None and refused.outcome == "shed"
    assert refused.detail == "duplicate-request-id"
    results = sched.drain()
    assert results["dup"].outcome == "completed"
    # terminal ids stay refused too (the journal remembers)
    refused = sched.submit(Problem(M=10, N=10), request_id="dup")
    assert refused is not None and refused.detail == "duplicate-request-id"
    assert results["dup"].outcome == "completed"


def test_replay_infeasible_deadline_is_a_miss_not_a_shed(tmp_path):
    # an acknowledged admission whose restarted deadline budget can no
    # longer be met is a deadline-miss (exit 4) — "shed" would invite
    # resubmission of an id the journal already owns
    journal = RequestJournal(tmp_path / "j.json")
    req = ServeRequest(problem=Problem(M=10, N=10), request_id="r0",
                       deadline=0.0)
    req.enqueued_t = 0.0  # deadline_left_s journals as 0
    journal.record_admit(req)
    clock = FakeClock()
    successor = make_scheduler(
        journal=RequestJournal(tmp_path / "j.json"), clock=clock,
        idle=clock.advance,
    )
    shed_before = obs_metrics.REGISTRY.counter("shed_total").value
    successor.replay()
    res = successor.drain()["r0"]
    assert res.outcome == "deadline-miss"
    assert res.detail == "replay-deadline-infeasible"
    assert not res.dispatched
    # classified deadline-miss, so no shed event/counter may fire —
    # shed_total always equals the number of shed outcomes
    assert obs_metrics.REGISTRY.counter("shed_total").value == shed_before


def test_idle_bucket_rebases_its_iteration_clock():
    # the serve carry's global k only moves forward; a long-lived
    # server must rebase it between requests or walk into ITER_CEILING
    # and wedge. After a drain the bucket must sit at k == 0 again.
    sched = make_scheduler()
    sched.submit(Problem(M=10, N=10), request_id="r0")
    sched.drain()
    (ctx,) = sched._ctxs.values()
    assert int(ctx.state[0]) == 0
    # and a second stream through the same rebased bucket still works
    sched.submit(Problem(M=10, N=10), request_id="r1")
    assert sched.drain()["r1"].outcome == "completed"


def test_collect_evicts_results(tmp_path):
    # the hand-off path a long-lived server drains through: collect()
    # empties the scheduler's buffer (solutions included) — results
    # must not accumulate for the process lifetime
    sched = make_scheduler()
    sched.submit(Problem(M=10, N=10), request_id="r0")
    sched.drain()
    first = sched.collect()
    assert first["r0"].outcome == "completed"
    assert sched.results == {} and sched.collect() == {}
    sched.submit(Problem(M=10, N=10), request_id="r1")
    sched.drain()
    assert set(sched.collect()) == {"r1"}


def test_replayed_deadline_budget_restarts(tmp_path):
    clock = FakeClock(100.0)
    sched = make_scheduler(journal=RequestJournal(tmp_path / "j.json"),
                           clock=clock, idle=clock.advance)
    sched.submit(Problem(M=10, N=10), deadline_s=60.0, request_id="r0")
    # replay in a "new process": the journaled remaining budget applies
    # from the new clock, not the dead one's absolute deadline
    clock2 = FakeClock(0.0)
    successor = make_scheduler(
        journal=RequestJournal(tmp_path / "j.json"), clock=clock2,
        idle=clock2.advance,
    )
    assert successor.replay() == 1
    req = successor.queue.pop_ready(clock2())
    assert req.deadline == pytest.approx(60.0, abs=1.0)


# -- chaos: the acceptance invariants ----------------------------------------


def test_chaos_fifty_requests_nan_oom_kill_zero_lost(tmp_path):
    report = run_chaos(
        n_requests=50, seed=7,
        journal_path=os.path.join(tmp_path, "chaos.json"),
    )
    assert report.ok, (
        f"lost={report.lost} doubled={report.double_completed} "
        f"unclassified={report.unclassified}"
    )
    assert report.killed and report.replayed >= 1
    assert report.faults_fired == 2  # the NaN lane and the fake OOM
    assert sum(report.counts.values()) == 50
    assert set(report.counts) <= {
        "completed", "cap", "failed", "deadline-miss", "shed",
    }
    # the injected faults must not have cost the victims their results
    assert report.outcomes["chaos-0002"] == "completed"
    assert report.outcomes["chaos-0005"] == "completed"


def test_chaos_is_seed_deterministic(tmp_path):
    r1 = run_chaos(n_requests=10, seed=3,
                   journal_path=os.path.join(tmp_path, "c1.json"))
    r2 = run_chaos(n_requests=10, seed=3,
                   journal_path=os.path.join(tmp_path, "c2.json"))
    assert r1.outcomes == r2.outcomes
    assert r1.counts == r2.counts


def test_chaos_mesh_kill_zero_lost_zero_double(tmp_path):
    """The device-kill drill (`harness chaos --mesh`): a simulated
    device loss takes out every live batch carry mid-stream — every
    in-flight request re-enters through the journal/retry ladder — and
    the process kill + replay rides on top. Zero lost, zero doubled,
    all classified, across BOTH failure modes."""
    report = run_chaos(
        n_requests=24, seed=11,
        journal_path=os.path.join(tmp_path, "chaos.json"),
        mesh_kill_request=5,
    )
    assert report.ok, (
        f"lost={report.lost} doubled={report.double_completed} "
        f"unclassified={report.unclassified}"
    )
    assert report.mesh_killed and report.killed
    assert sum(report.counts.values()) == 24
    # the request hosting the killed device must still end classified
    assert report.outcomes["chaos-0005"] in {
        "completed", "cap", "failed", "deadline-miss",
    }


def test_chaos_mesh_kill_is_seed_deterministic(tmp_path):
    kw = dict(n_requests=14, seed=5, mesh_kill_request=4)
    r1 = run_chaos(journal_path=os.path.join(tmp_path, "m1.json"), **kw)
    r2 = run_chaos(journal_path=os.path.join(tmp_path, "m2.json"), **kw)
    assert r1.outcomes == r2.outcomes
    assert r1.mesh_killed and r2.mesh_killed


def test_scheduler_device_loss_reenters_in_flight(tmp_path):
    """Unit form of the drill: a device_loss fault fired mid-batch drops
    every batch context, and each in-flight request walks the retry
    ladder to a terminal outcome — nothing lost, nothing doubled."""
    from poisson_ellipse_tpu.resilience.faultinject import Fault

    sched = Scheduler(
        lanes=2, chunk=4, max_retries=1, backoff_base_s=0.0,
        journal=RequestJournal(os.path.join(tmp_path, "j.json")),
        faults=FaultPlan(
            Fault("device_loss", at_iter=1, device=0, request_id="dl-0")
        ),
    )
    for i in range(3):
        assert sched.submit(Problem(M=10, N=10), request_id=f"dl-{i}") is None
    results = sched.drain()
    assert set(results) == {"dl-0", "dl-1", "dl-2"}
    assert all(r.outcome == "completed" for r in results.values())
    # the kill really fired: attempts reflect the re-entry
    assert any(r.attempts > 1 for r in results.values())


# -- lane-sharded composition: the 1-psum pin --------------------------------


def test_sharded_chunk_advance_exactly_one_psum_per_iteration():
    from poisson_ellipse_tpu.obs.static_cost import (
        COLLECTIVE_PRIMS,
        loop_primitive_counts,
    )
    from poisson_ellipse_tpu.parallel.batched_sharded import (
        build_sharded_chunk_advance,
    )
    from poisson_ellipse_tpu.parallel.mesh import make_mesh
    from poisson_ellipse_tpu.serve.scheduler import _BatchCtx

    mesh = make_mesh(jax.devices()[:2])
    ctx = _BatchCtx((12, 12), lanes=2, dtype=jnp.float32, norm="weighted",
                    mesh=mesh)
    fn, _ = build_sharded_chunk_advance((12, 12), mesh=mesh, lanes=2)
    args = (ctx.a3, ctx.b3, ctx.mask, ctx.h1, ctx.h2, ctx.delta,
            ctx.state, jnp.asarray(8, jnp.int32))
    counts = loop_primitive_counts(fn, args, COLLECTIVE_PRIMS)
    # the refill machinery is host-side between chunks: the loop body
    # still carries exactly the one convergence-word psum
    assert counts["psum"] + counts["psum_invariant"] == 1
    assert counts["ppermute"] == 0


def test_scheduler_on_mesh_serves_and_refills():
    from poisson_ellipse_tpu.parallel.mesh import make_mesh
    from poisson_ellipse_tpu.solver.pcg import solve as pcg_solve

    mesh = make_mesh(jax.devices()[:2])
    sched = make_scheduler(mesh=mesh)
    for i in range(3):  # 3 requests over 2 lanes forces one refill
        sched.submit(Problem(M=12, N=12), request_id=f"r{i}")
    results = sched.drain()
    single = pcg_solve(Problem(M=12, N=12), jnp.float32)
    for i in range(3):
        res = results[f"r{i}"]
        assert res.outcome == "completed"
        assert res.iters == int(single.iters)
        np.testing.assert_allclose(
            res.w, np.asarray(single.w), rtol=0, atol=5e-6
        )


def _lane_arrays(ctx) -> tuple:
    return ctx.a3, ctx.b3, ctx.mask, ctx.h1, ctx.h2, ctx.delta, ctx.state


def _misplaced(arrays, layout) -> list:
    """Which of a batch's per-lane arrays (``_lane_arrays``) lie anywhere
    but in ``layout``."""
    *operands, state = arrays
    names = ["a3", "b3", "mask", "h1", "h2", "delta"] + [
        f"state[{i}]" for i in range(len(state))]
    return [name for name, x, sharding in zip(
                names, operands + list(state), layout.operands + layout.state)
            if not x.sharding.is_equivalent_to(sharding, x.ndim)]


def test_scheduler_on_mesh_keeps_every_lane_on_its_own_chip():
    """Operand stacks, spacings, δ and every carry field stay in the
    chunk advance's lane layout through refills, advances and
    retirements: no stack sits whole on one chip, to be spread over the
    mesh again at every chunk."""
    from poisson_ellipse_tpu.parallel.batched_sharded import lane_layout
    from poisson_ellipse_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices()[:4])
    layout = lane_layout(mesh)
    sched = make_scheduler(lanes=8, mesh=mesh)
    for i in range(10):  # more requests than lanes: retirements refill
        sched.submit(Problem(M=12, N=12), request_id=f"p{i}")
    sched.step()
    (ctx,) = sched._ctxs.values()
    assert _misplaced(_lane_arrays(ctx), layout) == []
    while not sched.results:
        sched.step()
    assert _misplaced(_lane_arrays(ctx), layout) == []
    results = sched.drain()
    assert {r.outcome for r in results.values()} == {"completed"}
    assert _misplaced(_lane_arrays(ctx), layout) == []


def test_mesh_refill_and_park_write_one_lane_and_move_none_between_chips():
    """The mesh's refill scatter and lane park compile with no all-gather
    and no all-to-all, write exactly the lane they are given (whichever
    chip owns it) and keep every output in the lane layout."""
    from poisson_ellipse_tpu.obs.static_cost import compiled_collectives
    from poisson_ellipse_tpu.parallel.mesh import make_mesh
    from poisson_ellipse_tpu.serve.scheduler import (
        _BatchCtx,
        _mesh_park,
        _mesh_refill_scatter,
    )

    ctx = _BatchCtx((12, 12), lanes=8, dtype=jnp.float32, norm="weighted",
                    mesh=make_mesh(jax.devices()[:4]))
    scatter = _mesh_refill_scatter(ctx.layout)
    lane_shape = ctx.a3.shape[1:]
    unit = tuple(np.full((1,) + x.shape[1:], 7, x.dtype) if x.ndim
                 else np.zeros((), x.dtype) for x in ctx.state)
    new = np.ones(lane_shape, np.float32)
    scalars = [jnp.float32(2.0)] * 3
    args = (*_lane_arrays(ctx), unit, new, new, new, *scalars)
    park = _mesh_park(ctx.layout)
    for program in (scatter.lower(*args, jnp.int32(0)),
                    park.lower(ctx.state, jnp.int32(0))):
        counts = compiled_collectives(program.compile().as_text())
        assert counts["all-gather"] == counts["all-to-all"] == 0, counts
    for lane in (0, 5):  # on the first chip, and on the third
        out = scatter(*args, jnp.asarray(lane, jnp.int32))
        a3, h1, w = (np.asarray(x) for x in (out[0], out[3], out[6][1]))
        others = np.arange(8) != lane
        assert (a3[lane] == 1).all() and (a3[others] == 0).all()
        assert h1[lane] == 2 and (h1[others] == 1).all()
        assert (w[lane] == 7).all() and (w[others] == 0).all()
        assert _misplaced(out, ctx.layout) == []
        parked = park(out[6], lane)
        w, bd = np.asarray(parked[1]), np.asarray(parked[7])
        assert (w == 0).all() and bd.all()
        assert _misplaced(out[:6] + (parked,), ctx.layout) == []


def test_mesh_drain_of_the_six_eps_matches_the_plain_reference():
    """The cell's six ε at 40x60 through a 2x2-mesh scheduler (2 lanes a
    chip, so the six requests land on three chips) against the plain
    PCG of ``benchmark/reference.py``, within the configuration's own
    ``w_rel_err`` limit: the reference assembles in float64 on the host
    and iterates in float32 on its own, so it agrees with the program to
    the float32 rounding of two different summation orders, not bit for
    bit (the cell's full-size runs read 1.17e-5 against its 2e-4).
    Against the one-chip scheduler at the same lanes a chip the answers
    are bit for bit, at the same iteration counts."""
    import json

    from benchmark import reference
    from poisson_ellipse_tpu.parallel.mesh import make_mesh

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "ellipse-400x600-2x2.json")) as fh:
        config = dict(json.load(fh), grid=[40, 60])
    a1, b1, a2, b2 = config["box"]

    def problem(eps):
        return Problem(M=40, N=60, a1=a1, b1=b1, a2=a2, b2=b2,
                       f_val=config["f"], delta=config["delta"],
                       norm=config["norm"], eps=eps)

    def drain(sched):
        for i, eps in enumerate(config["eps_choices"]):
            assert sched.submit(problem(eps), request_id=str(i)) is None
        return sched.drain()

    mesh = drain(Scheduler(lanes=8, mesh=make_mesh(jax.devices()[:4])))
    single = drain(Scheduler(lanes=2))
    for i, eps in enumerate(config["eps_choices"]):
        got, one = mesh[str(i)], single[str(i)]
        assert got.outcome == "completed" and got.converged
        assert got.iters == one.iters
        np.testing.assert_array_equal(got.w, one.w)
        w_ref, _, conv = reference.solve(reference.problem_spec(config, eps))
        assert conv
        err = np.linalg.norm(got.w - w_ref) / np.linalg.norm(w_ref)
        assert err <= config["limits"]["w_rel_err"], (eps, err)


# -- observability -----------------------------------------------------------


def test_lifecycle_events_are_request_addressed_schema_v3(tmp_path):
    path = tmp_path / "serve.jsonl"
    obs_trace.start(str(path))
    try:
        plan = FaultPlan(Fault("nan", at_iter=4, field="r",
                               request_id="victim"))
        sched = make_scheduler(queue_capacity=1, faults=plan,
                               max_retries=1)
        sched.submit(Problem(M=10, N=10), request_id="victim")
        sched.submit(Problem(M=10, N=10), request_id="overflow")
        sched.drain()
    finally:
        obs_trace.stop()
    assert obs_trace.validate_file(str(path)) == []
    records = obs_trace.read_jsonl(str(path))
    by_name: dict[str, list] = {}
    for rec in records:
        by_name.setdefault(rec["name"], []).append(rec)
    for name in ("serve:admit", "serve:refill", "serve:retire",
                 "serve:shed", "serve:fault", "serve:retry"):
        assert name in by_name, f"missing {name}"
        assert all(r.get("request_id") for r in by_name[name]), (
            f"{name} events must carry request_id"
        )
    # the shed event names the overflow request
    assert by_name["serve:shed"][0]["request_id"] == "overflow"


def test_queue_depth_and_shed_metrics():
    obs_metrics.REGISTRY.reset()
    try:
        sched = make_scheduler(queue_capacity=1)
        sched.submit(Problem(M=10, N=10))
        sched.submit(Problem(M=10, N=10))  # shed
        snap = obs_metrics.REGISTRY.snapshot()
        assert snap["counters"]["shed_total"] == 1
        assert snap["gauges"]["queue_depth"] == 1
        sched.drain()
        snap = obs_metrics.REGISTRY.snapshot()
        assert snap["gauges"]["queue_depth"] == 0
        assert snap["counters"]["serve_completed_total"] == 1
    finally:
        obs_metrics.REGISTRY.reset()


# -- CLI ---------------------------------------------------------------------


def test_cli_serve_subcommand(tmp_path, capsys):
    import json

    from poisson_ellipse_tpu.harness.__main__ import main

    trace = tmp_path / "serve.jsonl"
    rc = main([
        "serve", "--requests", "3", "--grids", "10x10", "--rate", "1000",
        "--trace", str(trace), "--json",
    ])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["outcomes"] == {"completed": 3}
    assert rec["solves_per_sec"] > 0
    assert obs_trace.validate_file(str(trace)) == []


def test_cli_chaos_subcommand(capsys):
    import json

    from poisson_ellipse_tpu.harness.__main__ import main

    rc = main(["chaos", "--requests", "10", "--seed", "2", "--json"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["ok"] is True
    assert rec["killed"] is True and rec["replayed"] >= 0
    assert sum(rec["counts"].values()) == 10


def test_cli_serve_rejects_bad_args(capsys):
    from poisson_ellipse_tpu.harness.__main__ import main

    assert main(["serve", "--requests", "0"]) == 2
    assert main(["serve", "--replay"]) == 2
    assert main(["serve", "--rate", "0"]) == 2
    assert main(["serve", "--rate", "-5"]) == 2
