"""Journal-backed request handoff: a dead replica's work moves, not dies.

The crash-safe journal (PR 7, ``serve.journal``) made a single process
restartable: admitted-but-unfinished requests replay into the SAME
scheduler after a kill. The fleet promotes exactly that machinery one
level: when a replica is declared dead (lease missed, SIGKILL, fenced
zombie), its on-disk journal — the durable truth, reopened fresh the
way a restart would — is replayed into the SURVIVORS' admission instead.

The invariants the replay preserves:

- **remaining-deadline budget** — the journal stores
  ``deadline_left_s`` (remaining seconds at admission), and
  ``ServeRequest.from_spec`` restarts that budget from the handoff
  clock: a request admitted with 60 s to live is adopted with its
  budget intact, exactly as a same-process replay would grant it (the
  PR 7 contract, unchanged by crossing a replica boundary).
- **zero lost** — adoption is journal-first (``Scheduler.adopt_request``
  writes the survivor's ledger BEFORE queueing), and capacity overflow
  goes to the survivor's replay-backlog waves, never a terminal shed —
  so a second kill mid-handoff finds every adopted request durably
  owned by someone and hands it off again.
- **zero double** — the dead replica's token was fenced BEFORE this
  replay started (``fleet.router`` orders it so), which closes both
  races: a zombie completing a request the survivor now owns is
  rejected at its journal, and a request the dead replica already
  finished was compacted out of its snapshot and is simply not here to
  replay.

Handoff latency (journal open → last adoption) is measured per handoff
(``handoff_latency_seconds`` histogram) — it is the fleet's
recovery-time story.
"""

from __future__ import annotations

import time

from poisson_ellipse_tpu.obs import metrics as obs_metrics
from poisson_ellipse_tpu.obs import trace as obs_trace
from poisson_ellipse_tpu.serve.journal import RequestJournal


def handoff_journal(journal_path, survivors, clock=time.monotonic,
                    dead_replica: int | None = None,
                    skip=None) -> tuple[int, int]:
    """Replay a dead replica's journal into ``survivors``' admission.

    ``journal_path`` is reopened from disk — SIGKILL semantics: whatever
    the dead process held in memory is gone, the ledger is the truth.
    ``survivors`` is an ordered list of live :class:`~.replica.Replica`
    objects (the router passes them affinity-sorted per request).
    Returns ``(adopted, abandoned)``. Only a sweep that ADOPTED work
    counts as a handoff in the metrics — an empty journal's or an
    abandoning sweep's latency sample would pull the recovery-time p99
    toward zero, and "handoffs >= 1" gates must not be satisfiable by
    a no-op.

    Adoption is CLASS-AWARE: unfinished requests replay
    highest-priority first (earliest deadline within a class), so a
    dying replica's important work is re-owned before recovery spends
    time on batch work — under a second failure mid-handoff, what got
    adopted is what mattered most.

    ``skip`` (a predicate on the rebuilt request) drops entries some
    LIVE owner already holds — the REJOIN handshake passes it, because
    a journal archived at death time still lists requests the death
    handoff moved to survivors, and re-adopting those would co-own a
    request across epochs. Skipped entries count as neither adopted
    nor abandoned (they are owned elsewhere, not lost).
    """
    t0 = clock()
    now = clock()
    ledger = RequestJournal(journal_path)
    reqs = sorted(
        ledger.unfinished(now),
        key=lambda r: (
            -r.priority,
            r.deadline if r.deadline is not None else float("inf"),
        ),
    )
    if skip is not None:
        reqs = [r for r in reqs if not skip(r)]
    adopted = 0
    abandoned = 0
    for req in reqs:
        target = _pick_survivor(survivors, req)
        if target is None:
            # no LIVE survivor at all: the requests stay in the dead
            # ledger (and the dead scheduler's queue), which is what
            # makes the router's drain classify the total loss as
            # exit 9 instead of returning a result set missing them —
            # and the abandonment is loud, never a silent truncation
            abandoned = len(reqs) - adopted
            obs_trace.event(
                "fleet:handoff-abandoned",
                from_replica=dead_replica,
                abandoned=abandoned,
            )
            break
        target.scheduler.adopt_request(req)
        adopted += 1
        obs_trace.event(
            "fleet:handoff",
            request_id=req.request_id,
            from_replica=dead_replica,
            to_replica=target.replica_id,
            deadline_left_s=(
                None if req.deadline is None
                else round(req.deadline - now, 6)
            ),
        )
    latency = clock() - t0
    if adopted > 0:
        # only a sweep that MOVED work is a handoff: an empty journal's
        # ~µs sweep would dilute the recovery-time p99 toward zero and
        # let "handoffs >= 1" gates pass on a recovery of nothing
        obs_metrics.counter(obs_metrics.FLEET_HANDOFF_TOTAL).inc()
        obs_metrics.histogram(
            obs_metrics.HANDOFF_LATENCY_SECONDS
        ).observe(latency)
    obs_metrics.counter(
        obs_metrics.FLEET_HANDOFF_REQUESTS_TOTAL
    ).inc(adopted)
    obs_trace.event(
        "fleet:handoff-done",
        from_replica=dead_replica,
        adopted=adopted,
        abandoned=abandoned,
        unfinished=len(reqs),
        latency_s=round(latency, 6),
    )
    return adopted, abandoned


def _pick_survivor(survivors, req):
    """The adoption target: the router's shared routing order
    (``replica.routing_load_key`` — free lanes, then warm affinity,
    then load) applied to the handoff path, so recovery traffic neither
    cold-starts the idle replica nor buries the warm one. A DRAINING
    survivor is a last resort, not a refusal: drain's "stop admitting"
    covers new client work, while a handed-off request is
    already-acknowledged fleet work — parking it on a draining replica
    (which finishes everything it owns before exiting) preserves
    zero-lost through a shutdown that races a death."""
    from poisson_ellipse_tpu.fleet.replica import routing_load_key
    from poisson_ellipse_tpu.runtime.compile_cache import warm_affinity_key

    candidates = [s for s in survivors if s.live and not s.draining]
    if not candidates:
        candidates = [s for s in survivors if s.live]
    if not candidates:
        return None
    key = warm_affinity_key(req.problem.M, req.problem.N, req.problem.norm)
    return min(candidates, key=lambda s: routing_load_key(s, key))
