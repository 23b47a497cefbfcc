"""Chaos harness: the serving invariants proven under injected failure.

A serving layer's correctness claims are global, not per-request —
*zero lost* (every admitted request reaches a terminal state, across
kills), *zero double-completed* (no request finishes twice, across
replays), *all classified* (every terminal state is one of the named
outcomes). None of those can be unit-tested one code path at a time;
they have to survive a hostile stream. This module drives one: a
seeded Poisson arrival process of mixed shapes through the scheduler
while ``resilience.faultinject`` poisons lanes (request-addressed NaN),
fakes ``RESOURCE_EXHAUSTED`` on dispatch, and kills the server
mid-stream — the restarted scheduler replays the journal and the
stream keeps going. Everything is deterministic in ``seed``: the same
chaos reproduces bit-for-bit, which is what makes a failing run
debuggable instead of an anecdote.

``run_chaos`` is the single entry shared by ``tests/test_serve.py``
and the ``harness chaos`` subcommand.
"""

from __future__ import annotations

import dataclasses
import random
import time
from typing import Optional, Sequence

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs import trace as obs_trace
from poisson_ellipse_tpu.resilience.faultinject import Fault, FaultPlan
from poisson_ellipse_tpu.serve.journal import RequestJournal
from poisson_ellipse_tpu.serve.request import OUTCOMES, ServeRequest
from poisson_ellipse_tpu.serve.scheduler import Scheduler

DEFAULT_GRIDS = ((10, 10), (12, 12), (8, 8))


@dataclasses.dataclass
class ChaosReport:
    """One chaos run's verdict: the invariant booleans plus the
    evidence behind them."""

    n_requests: int
    outcomes: dict            # request_id -> outcome
    counts: dict              # outcome -> count
    lost: list                # submitted ids with no terminal outcome
    double_completed: list    # ids with >1 terminal outcome
    unclassified: list        # ids whose outcome is not in OUTCOMES
    replayed: int
    killed: bool
    faults_fired: int
    wall_s: float
    mesh_killed: bool = False  # a device-loss drill ran mid-stream
    # the fleet drill's evidence (replicas > 1): handoffs executed,
    # requests adopted by survivors, fenced zombie writes observed and
    # rejected, and whether the zombie-resurrection drill ran
    replicas: int = 1
    handoffs: int = 0
    adopted: int = 0
    stale_writes_rejected: int = 0
    zombie_drill: bool = False
    # the grad-kind drill (differentiable serving): grad requests in
    # the stream, and ids that completed WITHOUT a gradient — a grad
    # completion missing its payload is a classification failure
    grad_requests: int = 0
    grad_missing_payload: list = dataclasses.field(default_factory=list)
    # the survivability drills' evidence: rejoins executed, redirect
    # sheds issued by draining schedulers (unrecorded by design —
    # counted so zero-lost stays provable across a kill-mid-drain),
    # ids co-owned by two live replicas at ANY boundary (must stay
    # empty: the cross-epoch co-ownership violation), starvation
    # episodes observed and the tenants whose episodes outnumbered
    # their announcements (starved SILENTLY — must stay empty), and
    # per-tenant outcome counts for the mixed-tenant stream
    rejoins: int = 0
    drain_shed: int = 0
    co_owned: list = dataclasses.field(default_factory=list)
    starvation_events: int = 0
    starved_silent: list = dataclasses.field(default_factory=list)
    tenants: dict = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not (self.lost or self.double_completed or self.unclassified
                    or self.grad_missing_payload or self.co_owned
                    or self.starved_silent)

    def json_dict(self) -> dict:
        out = dataclasses.asdict(self)
        out["ok"] = self.ok
        return out


def _chaos_id(i: int) -> str:
    return f"chaos-{i:04d}"


def run_chaos(
    n_requests: int = 50,
    seed: int = 0,
    grids: Sequence[tuple[int, int]] = DEFAULT_GRIDS,
    rate_per_s: float = 400.0,
    lanes: int = 4,
    chunk: int = 8,
    queue_capacity: int = 128,
    journal_path=None,
    kill_after: Optional[int] = None,
    nan_request: Optional[int] = 2,
    oom_request: Optional[int] = 5,
    deadline_s: Optional[float] = None,
    max_retries: int = 2,
    mesh_kill_request: Optional[int] = None,
    malformed_request: Optional[int] = None,
    degenerate_request: Optional[int] = None,
    grad_requests: Sequence[int] = (),
    replicas: int = 1,
    replica_kill: Optional[int] = None,
    kill_during_handoff: bool = False,
    zombie: bool = False,
    lease_s: float = 0.25,
    replica_rejoin: Optional[int] = None,
    replica_kill_again: Optional[int] = None,
    lease_store_outage: Optional[int] = None,
    lease_store_outage_s: float = 0.05,
    tenant_mix: Optional[Sequence[tuple[str, int]]] = None,
    class_quotas: Optional[dict] = None,
    starvation_after_s: Optional[float] = None,
    warm_start: bool = False,
    poison_request: Optional[int] = None,
) -> ChaosReport:
    """Drive one seeded chaos stream; see the module docstring.

    ``kill_after`` (default: ``n_requests // 2``) is the request index
    after which the server is killed — the Scheduler object is dropped
    with requests queued and in flight, exactly what SIGKILL leaves
    behind — and a fresh scheduler on the same journal replays.
    ``nan_request`` / ``oom_request`` pick which request indices get a
    request-addressed NaN-poisoned lane and a fake RESOURCE_EXHAUSTED
    (None disables either). Requires ``journal_path`` when a kill is
    scheduled (the replay is the point).

    ``mesh_kill_request`` arms the DEVICE-kill drill (the ``harness
    chaos --mesh`` flag): when that request is in flight, a simulated
    device loss takes out every live batch carry at once — every
    in-flight request re-enters through the journal/retry ladder
    (``Scheduler._degrade_mesh``) — and the zero-lost/zero-double/
    all-classified invariants are asserted across a device kill, not
    just a process kill.

    ``grad_requests`` names arrival indices that become ``grad=True``
    requests (differentiable serving, ``diff.serving``): each runs two
    consecutive lane solves (primal + IFT adjoint over the same
    operator) and must terminally complete WITH its ``(value, grad)``
    payload — a completed grad request missing the gradient fails the
    report (``grad_missing_payload``). Kill/replay interleaves with the
    two-stage lifecycle like any other request: the replayed recompute
    is deterministic, so the invariants extend unchanged (the
    mid-adjoint kill → identical-gradient pin lives in
    ``tests/test_diff.py``, where the kill instant is surgical).

    ``malformed_request`` / ``degenerate_request`` arm the GEOMETRY
    drill: the named request's geometry spec is swapped at admission
    (``faultinject.malformed_spec`` / ``degenerate_geometry``). The
    malformed one must end in the terminal classified ``invalid``
    outcome without ever touching a lane; the degenerate (sliver-cut)
    one must pass the gate and SOLVE cleanly under the clamp — and in
    both cases every OTHER request's lane runs clean (zero poisoning,
    asserted by the same global invariants).

    ``replicas > 1`` switches the stream onto a FLEET
    (``fleet.FleetRouter``): same seeded arrivals, same invariant
    triple, but the failures are replica-scale. ``replica_kill`` names
    the arrival index at which replica 0 is SIGKILLed (its journal
    hands off to the survivors); ``kill_during_handoff`` additionally
    kills replica 1 at the same boundary — the adopted-but-not-yet-run
    requests must survive the second kill because adoption is
    journal-first; ``zombie`` arms the replica-hang drill instead of a
    kill (lease expires while the process lives, work is handed off,
    and the resurrected zombie's completion attempt MUST be rejected
    by its fenced journal — the observed-and-rejected stale write is
    part of the report). The per-request NaN/OOM faults keep firing on
    whichever replica hosts their victims — one plan, fleet-wide.

    The SURVIVABILITY drills (all fleet-only, all opt-in — the default
    drill set is unchanged): ``replica_rejoin`` names the arrival index
    at which the killed/fenced replica 0 re-enters as a fresh
    incarnation (``FleetRouter.rejoin_replica`` — archived-journal
    replay, warm-pool pre-warm, new epoch); ``replica_kill_again``
    kills the REJOINED incarnation at a later index, proving the
    kill→rejoin→kill-again ladder keeps zero-lost/zero-double;
    ``lease_store_outage`` arms a coordination-service outage of
    ``lease_store_outage_s`` seconds starting at that arrival index
    (deaths inside the window defer their fence+handoff; admissions
    past the grace window shed classified ``fleet-unavailable``);
    ``tenant_mix`` is a sequence of ``(tenant, priority)`` classes the
    seeded stream draws from (with optional ``class_quotas`` /
    ``starvation_after_s`` passed to every replica's queue) — the
    report adds per-tenant outcome counts and pins that no tenant
    starved silently. At EVERY boundary the router's co-ownership
    audit runs; any id live-owned by two replicas fails the report.

    ``warm_start`` runs the whole drill with the per-bucket recycle
    pools ON (``runtime.solvecache``) — the zero-lost/zero-double/
    all-classified triple must hold unchanged with recycling enabled,
    and replays still run cold (the journal contract).
    ``poison_request`` names the arrival index whose solve-cache
    consult is replaced with a deliberately wrong entry
    (``faultinject.cache_poison``): the victim must still terminate
    classified — extra iterations are the only allowed cost.
    """
    if n_requests < 1:
        raise ValueError("need at least one request")
    if rate_per_s <= 0:
        raise ValueError("rate_per_s must be > 0")
    if replicas > 1:
        # the single-scheduler drills do not arm on the fleet path —
        # refuse them LOUDLY rather than report invariants a drill
        # that never ran cannot have tested (replica_kill is the
        # fleet's kill; mesh/geometry drills are single-scheduler)
        dropped = {
            "kill_after": kill_after,
            "mesh_kill_request": mesh_kill_request,
            "malformed_request": malformed_request,
            "degenerate_request": degenerate_request,
            "grad_requests": tuple(grad_requests) or None,
        }
        armed = [k for k, v in dropped.items() if v is not None]
        if armed:
            raise ValueError(
                f"{', '.join(armed)} are single-scheduler drills the "
                "fleet path (replicas > 1) does not run — use "
                "replica_kill/kill_during_handoff/zombie for fleet "
                "failure modes, or replicas=1 for these"
            )
        return _run_fleet_chaos(
            n_requests=n_requests, seed=seed, grids=grids,
            rate_per_s=rate_per_s, lanes=lanes, chunk=chunk,
            queue_capacity=queue_capacity, journal_path=journal_path,
            nan_request=nan_request, oom_request=oom_request,
            deadline_s=deadline_s, max_retries=max_retries,
            replicas=replicas, replica_kill=replica_kill,
            kill_during_handoff=kill_during_handoff, zombie=zombie,
            lease_s=lease_s, replica_rejoin=replica_rejoin,
            replica_kill_again=replica_kill_again,
            lease_store_outage=lease_store_outage,
            lease_store_outage_s=lease_store_outage_s,
            tenant_mix=tenant_mix, class_quotas=class_quotas,
            starvation_after_s=starvation_after_s,
        )
    fleet_only = {
        "replica_rejoin": replica_rejoin,
        "replica_kill_again": replica_kill_again,
        "lease_store_outage": lease_store_outage,
        "tenant_mix": tenant_mix,
        "class_quotas": class_quotas,
        "starvation_after_s": starvation_after_s,
    }
    armed_fleet = [k for k, v in fleet_only.items() if v is not None]
    if armed_fleet:
        raise ValueError(
            f"{', '.join(armed_fleet)} are fleet drills the "
            "single-scheduler path (replicas == 1) does not run — "
            "use replicas > 1"
        )
    if kill_after is None:
        kill_after = n_requests // 2
    kill = kill_after is not None and 0 < kill_after < n_requests
    if kill and journal_path is None:
        raise ValueError(
            "a kill/restart chaos run needs journal_path (replay is the "
            "invariant under test)"
        )
    rng = random.Random(seed)
    faults = []
    if nan_request is not None and nan_request < n_requests:
        faults.append(Fault(
            "nan", at_iter=4, field="r", request_id=_chaos_id(nan_request),
        ))
    if oom_request is not None and oom_request < n_requests:
        faults.append(Fault(
            "oom", at_iter=2, request_id=_chaos_id(oom_request),
        ))
    if mesh_kill_request is not None and mesh_kill_request < n_requests:
        faults.append(Fault(
            "device_loss", at_iter=1, device=0,
            request_id=_chaos_id(mesh_kill_request),
        ))
    if malformed_request is not None and malformed_request < n_requests:
        faults.append(Fault(
            "malformed_spec", request_id=_chaos_id(malformed_request),
        ))
    if degenerate_request is not None and degenerate_request < n_requests:
        faults.append(Fault(
            "degenerate_geometry",
            request_id=_chaos_id(degenerate_request),
        ))
    if poison_request is not None and poison_request < n_requests:
        if not warm_start:
            raise ValueError(
                "poison_request targets the solve-cache consult; it needs "
                "warm_start=True (a cache-off drill has no consult to "
                "poison)"
            )
        faults.append(Fault(
            "cache_poison", request_id=_chaos_id(poison_request),
        ))

    def make_scheduler():
        return Scheduler(
            lanes=lanes, chunk=chunk, queue_capacity=queue_capacity,
            max_retries=max_retries, backoff_base_s=0.001,
            journal=(
                RequestJournal(journal_path) if journal_path is not None
                else None
            ),
            faults=FaultPlan(*faults),
            keep_solutions=False,
            warm_start=warm_start,
        )

    t0 = time.monotonic()
    sched = make_scheduler()
    results: dict[str, object] = {}
    completions_seen: dict[str, int] = {}

    def harvest(s: Scheduler):
        for rid, res in s.results.items():
            if rid in results:
                completions_seen[rid] = completions_seen.get(rid, 1) + 1
            results[rid] = res

    replayed = 0
    # the arrival stream: exponential gaps, mixed shapes; between
    # arrivals the scheduler keeps chewing chunks. Gaps are capped so a
    # low rate cannot stall the harness; outcomes stay deterministic in
    # the seed (arrival order and fault addressing are seed-driven, the
    # sleep only paces the wall clock)
    for i in range(n_requests):
        if kill and i == kill_after:
            # SIGKILL semantics: harvest what the dead server already
            # finished (its journal has it), drop it mid-flight, replay
            harvest(sched)
            obs_trace.event("serve:chaos-kill", at_request=i)
            sched = make_scheduler()
            replayed = sched.replay()
        time.sleep(min(rng.expovariate(rate_per_s), 0.01))
        M, N = rng.choice(list(grids))
        is_grad = i in grad_requests
        req = ServeRequest(
            problem=Problem(M=M, N=N),
            deadline=(
                None if deadline_s is None
                else sched.clock() + deadline_s
            ),
            max_retries=max_retries,
            # the grad kind rides the same stream: two lane solves
            # (primal + IFT adjoint) ending in (value, grad) — the
            # invariants extend to it unchanged, plus payload presence
            grad=is_grad,
            geometry=(
                {"kind": "ellipse", "cx": 0.05, "cy": -0.02, "rx": 0.9,
                 "ry": 0.45} if is_grad else None
            ),
            objective={"kind": "energy"} if is_grad else None,
        )
        req.request_id = _chaos_id(i)
        sched.submit_request(req)
        # a couple of chunks between arrivals, like a busy server
        sched.step()
    sched.drain()
    harvest(sched)

    submitted = [_chaos_id(i) for i in range(n_requests)]
    outcomes = {
        rid: results[rid].outcome for rid in submitted if rid in results
    }
    lost = [rid for rid in submitted if rid not in outcomes]
    unclassified = [
        rid for rid, out in outcomes.items() if out not in OUTCOMES
    ]
    double = sorted(rid for rid, n in completions_seen.items() if n > 1)
    counts: dict[str, int] = {}
    for out in outcomes.values():
        counts[out] = counts.get(out, 0) + 1
    grad_missing = [
        _chaos_id(i) for i in grad_requests
        if i < n_requests
        and outcomes.get(_chaos_id(i)) == "completed"
        and getattr(results[_chaos_id(i)], "grad", None) is None
    ]
    report = ChaosReport(
        n_requests=n_requests,
        outcomes=outcomes,
        counts=counts,
        lost=lost,
        double_completed=double,
        unclassified=unclassified,
        replayed=replayed,
        killed=kill,
        faults_fired=sum(1 for f in faults if f.fired),
        wall_s=time.monotonic() - t0,
        mesh_killed=any(
            f.kind == "device_loss" and f.fired for f in faults
        ),
        grad_requests=sum(1 for i in grad_requests if i < n_requests),
        grad_missing_payload=grad_missing,
        drain_shed=sched.drain_sheds,
    )
    obs_trace.event("serve:chaos-report", **report.json_dict())
    return report


def _run_fleet_chaos(
    n_requests: int,
    seed: int,
    grids,
    rate_per_s: float,
    lanes: int,
    chunk: int,
    queue_capacity: int,
    journal_path,
    nan_request: Optional[int],
    oom_request: Optional[int],
    deadline_s: Optional[float],
    max_retries: int,
    replicas: int,
    replica_kill: Optional[int],
    kill_during_handoff: bool,
    zombie: bool,
    lease_s: float,
    replica_rejoin: Optional[int],
    replica_kill_again: Optional[int],
    lease_store_outage: Optional[int],
    lease_store_outage_s: float,
    tenant_mix,
    class_quotas: Optional[dict],
    starvation_after_s: Optional[float],
) -> ChaosReport:
    """The fleet half of :func:`run_chaos` (see its docstring).

    ``journal_path`` names the fleet's journal DIRECTORY (one ledger per
    replica) and is mandatory — the handoff under test IS the journals.
    The kill/hang indices are seed-independent constants of the call,
    so the whole drill is deterministic per (seed, parameters): same
    arrivals, same victim, same handoff boundary, same outcomes.
    """
    from poisson_ellipse_tpu.fleet import FleetRouter, StaleLeaseError
    from poisson_ellipse_tpu.resilience import faultinject
    from poisson_ellipse_tpu.resilience.errors import FleetUnavailableError
    from poisson_ellipse_tpu.serve.request import ServeResult

    if journal_path is None:
        raise ValueError(
            "fleet chaos needs journal_path (a directory: the "
            "journal-backed handoff is the invariant under test)"
        )
    if kill_during_handoff and replicas < 3:
        raise ValueError(
            "kill_during_handoff kills TWO replicas at one boundary; "
            "the drill needs replicas >= 3 so an adopter survives "
            "(with 2 the stream would just hit the exit-9 total-loss "
            "path, which is its own drill)"
        )
    if kill_during_handoff and zombie and replica_kill is None:
        raise ValueError(
            "kill_during_handoff rides the replica_kill drill's "
            "handoff boundary; combining it with zombie needs an "
            "explicit replica_kill index (zombie alone arms no kill)"
        )
    if replica_kill is None and not zombie:
        replica_kill = n_requests // 2
    victim_boundary = replica_kill if replica_kill is not None else (
        max(n_requests // 3, 1) if zombie else None
    )
    if replica_rejoin is not None:
        if victim_boundary is None:
            raise ValueError(
                "replica_rejoin needs a victim: arm replica_kill or "
                "zombie so there is a dead incarnation to rejoin"
            )
        if not victim_boundary < replica_rejoin < n_requests:
            raise ValueError(
                f"replica_rejoin={replica_rejoin} must land strictly "
                f"after the victim boundary ({victim_boundary}) and "
                f"before the stream ends ({n_requests})"
            )
    if replica_kill_again is not None:
        if replica_rejoin is None:
            raise ValueError(
                "replica_kill_again kills the REJOINED incarnation: it "
                "needs replica_rejoin"
            )
        if not replica_rejoin < replica_kill_again < n_requests:
            raise ValueError(
                f"replica_kill_again={replica_kill_again} must land "
                f"strictly after replica_rejoin ({replica_rejoin}) and "
                f"before the stream ends ({n_requests})"
            )
    rng = random.Random(seed)
    faults = []
    if nan_request is not None and nan_request < n_requests:
        faults.append(Fault(
            "nan", at_iter=4, field="r", request_id=_chaos_id(nan_request),
        ))
    if oom_request is not None and oom_request < n_requests:
        faults.append(Fault(
            "oom", at_iter=2, request_id=_chaos_id(oom_request),
        ))
    if replica_kill is not None and 0 < replica_kill < n_requests:
        faults.append(faultinject.replica_kill(
            at_request=replica_kill, replica=0,
        ))
    hang_at = None
    if zombie:
        hang_at = max(n_requests // 3, 1)
        faults.append(faultinject.replica_hang(
            delay_s=float("inf"), at_request=hang_at, replica=0,
        ))
    if lease_store_outage is not None and \
            0 < lease_store_outage < n_requests:
        faults.append(faultinject.lease_store_outage(
            lease_store_outage_s, at_request=lease_store_outage,
        ))
    plan = FaultPlan(*faults)

    t0 = time.monotonic()
    router = FleetRouter(
        replicas=replicas,
        journal_dir=journal_path,
        lease_s=lease_s,
        faults=plan,
        lanes=lanes,
        chunk=chunk,
        queue_capacity=queue_capacity,
        max_retries=max_retries,
        backoff_base_s=0.001,
        keep_solutions=False,
        class_quotas=class_quotas,
        starvation_after_s=starvation_after_s,
        # the per-replica schedulers share the ONE plan, so the
        # request-addressed faults fire on whichever replica hosts
        # their victim — and fire once, fleet-wide
    )
    results: dict[str, object] = {}

    def harvest():
        # double detection lives in the ROUTER's delivery ledger
        # (FleetRouter.harvest: each terminal record passes exactly
        # once, so a second delivery per id IS the bug), not in an
        # object-identity heuristic that a dict merge could launder
        results.update(router.harvest())

    stale_rejected = 0
    second_killed = False
    killed_again = False
    rejoin_due = replica_rejoin
    tenant_of: dict[str, str] = {}
    co_owned: set[str] = set()
    for i in range(n_requests):
        time.sleep(min(rng.expovariate(rate_per_s), 0.01))
        M, N = rng.choice(list(grids))
        tenant, priority = (
            ("default", 1) if tenant_mix is None
            else rng.choice(list(tenant_mix))
        )
        tenant_of[_chaos_id(i)] = tenant
        req_kw = dict(
            deadline_s=deadline_s, max_retries=max_retries,
            request_id=_chaos_id(i), tenant=tenant, priority=priority,
        )
        try:
            router.submit(Problem(M=M, N=N), **req_kw)
        except FleetUnavailableError as e:
            # total loss mid-stream must stay CLASSIFIED inside the
            # report (the invariant is "all classified", and a crashed
            # harness asserts nothing): the refused request records as
            # a shed — it was never admitted anywhere, loudly
            results[_chaos_id(i)] = ServeResult(
                request_id=_chaos_id(i), outcome="shed",
                detail="fleet-unavailable",
                retry_after_s=e.retry_after_s,
            )
        if kill_during_handoff and replica_kill is not None and \
                i >= replica_kill and not second_killed:
            # the second kill lands at the SAME boundary the first
            # handoff finished on: the adopted-but-not-yet-run requests
            # are owned only by replica 1's journal — journal-first
            # adoption is what keeps them alive through this
            second_killed = True
            router.kill_replica(1)
        if zombie and hang_at is not None and i == hang_at:
            # fast-forward the HUNG replica's lease into the past (the
            # deterministic stand-in for "its renewals stopped a lease
            # ago") — sleeping the wall clock instead would also lapse
            # the healthy replicas' leases and turn the drill racy; the
            # honest wall-clock expiry path is pinned with a FakeClock
            # in tests/test_fleet.py
            hung = router._by_id(0)
            if hung is not None and hung.live:
                hung.lease.deadline = router.clock() - 1.0
        router.step()
        harvest()
        if rejoin_due is not None and i >= rejoin_due:
            victim = router._by_id(0)
            if victim is not None and not victim.live:
                try:
                    router.rejoin_replica(0)
                    rejoin_due = None
                except FleetUnavailableError:
                    # a lease-store outage refuses the rejoin (minting
                    # an incarnation needs the store): retry at the
                    # next boundary — recovery re-arms it
                    pass
        if (replica_kill_again is not None and i >= replica_kill_again
                and rejoin_due is None and not killed_again):
            # the second kill hits the REJOINED incarnation: the ladder
            # under test is kill → rejoin → kill-again, with zero
            # lost/double across BOTH epochs of replica 0
            killed_again = True
            router.kill_replica(0)
        # the cross-epoch co-ownership audit, every boundary: any id
        # live-owned twice at ANY instant is evidence, even if a later
        # completion would hide it from an end-of-run check
        co_owned.update(router.audit_ownership())
    # zombie resurrection: the hang clears, the dead-but-alive replica
    # runs its own serve loop again — every completion it attempts must
    # be rejected by its fenced journal, never delivered
    zombie_rep = router.zombies.get(0)
    if zombie and zombie_rep is not None:
        zombie_rep.hung_until = 0.0
        for _ in range(500):
            try:
                if not zombie_rep.resurrect_step():
                    break
            except StaleLeaseError:
                stale_rejected += 1
                break
    if rejoin_due is not None:
        # the stream ended with the rejoin still owed (a long outage):
        # one last attempt after a store probe, so the drill is judged
        # on the recovered fleet rather than a mid-outage snapshot
        victim = router._by_id(0)
        if victim is not None and not victim.live:
            try:
                router.rejoin_replica(0)
            except FleetUnavailableError:
                pass
    try:
        router.drain()
    except FleetUnavailableError:
        # every replica died with admitted work stranded: the report —
        # not an exception — is the verdict, and the stranded ids show
        # up in `lost`, which is exactly what that scenario IS
        pass
    harvest()
    co_owned.update(router.audit_ownership())

    submitted = [_chaos_id(i) for i in range(n_requests)]
    outcomes = {
        rid: results[rid].outcome for rid in submitted if rid in results
    }
    lost = [rid for rid in submitted if rid not in outcomes]
    unclassified = [
        rid for rid, out in outcomes.items() if out not in OUTCOMES
    ]
    double = sorted(set(router.double_delivered))
    counts: dict[str, int] = {}
    for out in outcomes.values():
        counts[out] = counts.get(out, 0) + 1
    episodes, announced = router.starvation_counts()
    starved_silent = sorted(
        t for t, n in episodes.items() if n > announced.get(t, 0)
    )
    tenants: dict[str, dict] = {}
    if tenant_mix is not None:
        for rid, out in outcomes.items():
            per = tenants.setdefault(tenant_of.get(rid, "default"), {})
            per[out] = per.get(out, 0) + 1
    report = ChaosReport(
        n_requests=n_requests,
        outcomes=outcomes,
        counts=counts,
        lost=lost,
        double_completed=double,
        unclassified=unclassified,
        replayed=router.adopted_total,
        killed=any(
            f.kind == "replica_kill" and f.fired for f in faults
        ) or second_killed or killed_again,
        faults_fired=sum(1 for f in faults if f.fired),
        wall_s=time.monotonic() - t0,
        replicas=replicas,
        handoffs=router.handoffs,
        adopted=router.adopted_total,
        stale_writes_rejected=stale_rejected,
        zombie_drill=zombie,
        rejoins=router.rejoins,
        drain_shed=router.drain_shed_total(),
        co_owned=sorted(co_owned),
        starvation_events=sum(episodes.values()),
        starved_silent=starved_silent,
        tenants=tenants,
    )
    obs_trace.event("serve:fleet-chaos-report", **report.json_dict())
    return report
