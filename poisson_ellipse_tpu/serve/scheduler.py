"""Continuous-batching scheduler: retire-and-refill at chunk boundaries.

PR 5's batched engines freeze a finished lane in place until the whole
batch drains — fine for a fixed workload, wrong for a server, where a
converged lane is a free slot someone is queueing for. This scheduler
generalizes the in-loop freeze-out mask of ``batch.batched_pcg`` from
*freeze* to *swap-in*: between chunks (the only place the host touches
the carry anyway — the resilience chunk stance), a finished lane's
slice of the carry is re-initialised with the next queued request's
embedded operands, and the same compiled bucket executable keeps
running — **no recompile**, because shapes are the only compile-time
facts (every per-request number — h1, h2, δ, the mask, the RHS — is a
traced operand, the ``runtime.compile_cache`` embedding made per-lane).
This is Orca-style iteration-level scheduling (Yu et al., OSDI '22)
with PCG chunks in place of decode steps.

The robustness envelope around the packing loop:

- **Admission** — bounded queue, backpressure, deadline-aware shedding
  (``serve.queue``); every rejection carries ``retry_after_s``.
- **Deadlines** — enforced at chunk granularity: expiry while queued is
  shed un-dispatched; expiry mid-solve cancels at the chunk boundary
  with a partial result (the ``run_report_partial`` stance per
  request); a request that converges at the same boundary its deadline
  passes gets its result (converged lanes retire *first* — no spurious
  miss).
- **Retries** — a per-request budget with exponential backoff walking
  the degradation ladder: quarantined/broken lane → resubmit on a
  fresh lane → guarded single solve (``resilience.guard``) as the final
  rung; whatever the ladder ends in is a classified outcome.
- **Durability** — a crash-safe request journal (``serve.journal``):
  admissions are journaled before they are acknowledged, so a killed
  server replays every admitted-but-unfinished request on restart.
- **Observability** — every admission/refill/retirement/shed/retry/
  replay is a request-addressed ``obs.trace`` event (schema v3) and an
  ``obs.metrics`` counter/histogram (``queue_depth``,
  ``time_in_queue_seconds``, ``deadline_miss_total``, ``shed_total``),
  exported via the ``--metrics`` OpenMetrics path.

Refill only targets the **classical** batched engine: a refilled lane
must be bit-identical to the same request solved on a fresh lane
(pinned in ``tests/test_batched.py``), and only the classical carry
round-trips exactly through ``init_state`` — the pipelined recurrence
seeds a multi-term history a mid-stream re-init would perturb.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map

from poisson_ellipse_tpu.batch import batched_pcg
from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs import metrics as obs_metrics
from poisson_ellipse_tpu.obs import trace as obs_trace
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.resilience.errors import SolveError
from poisson_ellipse_tpu.resilience.faultinject import Fault, FaultPlan
from poisson_ellipse_tpu.runtime.compile_cache import grid_bucket
from poisson_ellipse_tpu.runtime.solvecache import SolveCache, solve_key
from poisson_ellipse_tpu.serve.journal import RequestJournal
from poisson_ellipse_tpu.serve.queue import AdmissionQueue
from poisson_ellipse_tpu.serve.request import ServeRequest, ServeResult

# the serve carry's global iteration ceiling: requests come and go, the
# batch's clock only moves forward — per-request caps are enforced
# host-side against each lane's swap-in offset
ITER_CEILING = 1 << 30

# classical batched carry layout (mirrors batch.driver._LAYOUT["batched"])
_IDX = {
    "k": 0, "w": 1, "r": 2, "p": 3, "zr": 4, "diff": 5,
    "conv": 6, "bd": 7, "quar": 8, "iters": 9,
}
_FIELDS = {"w": 1, "r": 2, "p": 3, "zr": 4}

DEFAULT_LANES = 4
DEFAULT_CHUNK = 16


@functools.lru_cache(maxsize=32)
def _bucket_advance(Mb: int, Nb: int, dtype_name: str, norm: str):
    """The bucket executable: ONE jitted chunk-advance per (bucket,
    dtype, norm), shared by every scheduler in the process. Operands,
    per-lane h/δ, masks, carry and bound are all traced arguments, so
    retire/refill/replay never retrace — the TPU010 stance, per bucket.
    """
    proto = Problem(M=Mb, N=Nb, norm=norm, max_iter=ITER_CEILING)

    def fn(a3, b3, mask, h1, h2, delta, state, limit):
        # the rhs positional only supplies a dtype to advance(); the
        # carry's own w plays that role here (rhs lives in r at init)
        return batched_pcg.advance(
            proto, a3, b3, state[1], state, limit=limit, mask=mask,
            h1=h1, h2=h2, delta=delta,
        )

    # no donation: the carry is re-read at every boundary for the
    # retire/refill host work
    return jax.jit(fn), proto  # tpulint: disable=TPU004


def _set_lane(stack, lane, value):
    return stack.at[lane].set(value)


def _scatter_lane(a3, b3, mask, h1, h2, delta, state, unit,
                  a_p, b_p, m_p, h1v, h2v, dv, lane, put=_set_lane):
    """One dispatch per refill: every operand slice and carry field of
    the lane scattered together. The serving target regime is
    dispatch-bound TPUs, where fifteen per-refill ``.at[].set`` round
    trips would eat the continuous-batching win; ``lane`` is traced, so
    shapes are the only compile keys (one build per bucket). Pure
    copies — bit-identical to the unfused form by construction."""
    a3 = put(a3, lane, a_p)
    b3 = put(b3, lane, b_p)
    mask = put(mask, lane, m_p)
    h1 = put(h1, lane, h1v)
    h2 = put(h2, lane, h2v)
    delta = put(delta, lane, dv)
    state = tuple(
        s if i == _IDX["k"] else put(s, lane, u[0])
        for i, (s, u) in enumerate(zip(state, unit))
    )
    return a3, b3, mask, h1, h2, delta, state


# no donation, matching _bucket_advance: the host re-reads the carry
# at every boundary, and CPU/CI backends would only warn
_refill_scatter = jax.jit(_scatter_lane)  # tpulint: disable=TPU004


def _park_state(state, lane, put=_set_lane):
    """The carry with lane ``lane`` parked: zeroed iterate, residual and
    direction, breakdown flag raised so the loop freezes the lane until
    the next refill."""
    state = list(state)
    for name in ("w", "r", "p"):
        idx = _IDX[name]
        state[idx] = put(state[idx], lane,
                         jnp.zeros(state[idx].shape[1:], state[idx].dtype))
    for name, value in (("zr", 0.0), ("conv", False), ("bd", True),
                        ("quar", False)):
        state[_IDX[name]] = put(state[_IDX[name]], lane, value)
    return tuple(state)


def _put_own_lane(stack, lane, value):
    """``_set_lane`` on one chip's shard of a lane-sharded stack, inside
    ``shard_map``: the chip that owns lane ``lane`` writes it, every
    other chip rewrites one of its own lanes with its old value, so no
    lane moves between chips. (Left to the partitioner, a lane index
    known only at run time gathers the whole stack onto every TPU chip,
    as each eager ``.at[lane].set`` does.)"""
    from poisson_ellipse_tpu.parallel.batched_sharded import MESH_AXES

    n = stack.shape[0]
    at = lane - lax.axis_index(MESH_AXES) * n
    mine = (at >= 0) & (at < n)
    at = jnp.clip(at, 0, n - 1)
    return lax.dynamic_update_index_in_dim(
        stack, jnp.where(mine, value, stack[at]), at, 0
    )


@functools.lru_cache(maxsize=8)
def _mesh_refill_scatter(layout):
    """``_scatter_lane`` on a mesh, in ``layout``
    (``parallel.batched_sharded.lane_layout``), each lane written on
    its own chip."""
    specs = tuple(s.spec for s in layout.operands) + (
        tuple(s.spec for s in layout.state),
    )
    mapped = shard_map(
        functools.partial(_scatter_lane, put=_put_own_lane),
        mesh=layout.stack.mesh,
        # the new lane's values come in whole to every chip
        in_specs=specs + (layout.replicated.spec,) * 8,
        out_specs=specs,
    )
    return jax.jit(mapped, out_shardings=layout.operands + (layout.state,))


@functools.lru_cache(maxsize=8)
def _mesh_park(layout):
    """``_park_state`` on a mesh, in ``layout``, each lane parked on its
    own chip."""
    specs = tuple(s.spec for s in layout.state)
    mapped = shard_map(
        functools.partial(_park_state, put=_put_own_lane),
        mesh=layout.stack.mesh,
        in_specs=(specs, layout.replicated.spec),
        out_specs=specs,
    )
    return jax.jit(mapped, out_shardings=layout.state)


def _fetch_lane(stack, lane: int) -> np.ndarray:
    """Lane ``lane`` of a per-lane stack, read from the shard that holds
    it: indexing a lane-sharded array would gather the lane onto every
    chip first."""
    for shard in stack.addressable_shards:
        rows = range(*shard.index[0].indices(stack.shape[0]))
        if lane in rows:
            return np.asarray(shard.data[lane - rows.start])
    raise IndexError(f"lane {lane} lies on no local device")


def embed_operands(problem: Problem, bucket: tuple[int, int], np_dtype,
                   a, b, rhs):
    """THE pad-and-mask bucket embedding: zero-padded operands plus the
    interior mask of the true problem (the ``runtime.compile_cache``
    layout, sliced per lane). One definition — ordinary requests
    (``_embed_request``) and grad-kind stages (``diff.serving.GradJob.
    embed``) must stay layout-identical by construction, not by
    parallel maintenance."""
    Mb, Nb = bucket
    g1, g2 = problem.M + 1, problem.N + 1
    pad2 = ((0, Mb + 1 - g1), (0, Nb + 1 - g2))
    mask = np.zeros((Mb + 1, Nb + 1), np_dtype)
    mask[1 : problem.M, 1 : problem.N] = 1.0
    return (
        np.pad(a, pad2).astype(np_dtype),
        np.pad(b, pad2).astype(np_dtype),
        np.pad(rhs, pad2).astype(np_dtype),
        mask,
    )


def _embed_request(problem: Problem, bucket: tuple[int, int], np_dtype,
                   geometry=None, theta=None):
    """Pad-and-mask one request into a bucket via ``embed_operands``.
    ``geometry``/``theta`` select the SDF quadrature assembly — a
    host-side operand fact, so an arbitrary domain rides the SAME
    bucket executable (shapes are the only compile keys)."""
    a, b, r = assembly.assemble_numpy(problem, geometry=geometry,
                                      theta=theta)
    return embed_operands(problem, bucket, np_dtype, a, b, r)


class _InFlight:
    """One dispatched request: which lane hosts it and at which global
    iteration it swapped in (``base_k`` — per-request iteration counts
    are ``iters[lane] - base_k``)."""

    __slots__ = ("req", "lane", "base_k", "t_dispatch", "cache_key",
                 "rhs_pad")

    def __init__(self, req: ServeRequest, lane: int, base_k: int,
                 t_dispatch: float):
        self.req = req
        self.lane = lane
        self.base_k = base_k
        self.t_dispatch = t_dispatch
        # warm-start bookkeeping (None when the pool was not consulted):
        # the request's solve-cache key and its EMBEDDED rhs — what the
        # retirement path needs to deposit the converged lane back into
        # the bucket's pool
        self.cache_key: Optional[str] = None
        self.rhs_pad = None


class _BatchCtx:
    """One grid bucket's live batch: the compiled advance, the carry,
    the per-lane operand stack, and the slot table."""

    def __init__(self, bucket: tuple[int, int], lanes: int, dtype, norm: str,
                 mesh=None):
        self.bucket = bucket
        self.norm = norm
        if mesh is not None:
            from poisson_ellipse_tpu.parallel.batched_sharded import (
                build_sharded_chunk_advance,
                lane_layout,
            )

            self.fn, self.proto = build_sharded_chunk_advance(
                bucket, mesh=mesh, lanes=lanes, norm=norm,
                iter_ceiling=ITER_CEILING,
            )
            # every per-lane array lives on the chip that owns the lane
            self.layout = lane_layout(mesh)
            self.scatter = _mesh_refill_scatter(self.layout)
            self.park = _mesh_park(self.layout)
            stack, vector = self.layout.stack, self.layout.vector
        else:
            self.fn, self.proto = _bucket_advance(
                bucket[0], bucket[1], jnp.dtype(dtype).name, norm
            )
            self.layout = None
            self.scatter = _refill_scatter
            self.park = _park_state
            stack = vector = None
        g = (lanes, bucket[0] + 1, bucket[1] + 1)
        zeros3 = jnp.zeros(g, dtype, device=stack)
        self.a3 = zeros3
        self.b3 = zeros3
        self.mask = zeros3
        self.h1 = jnp.ones((lanes,), dtype, device=vector)
        self.h2 = jnp.ones((lanes,), dtype, device=vector)
        self.delta = jnp.full((lanes,), 1e-6, dtype, device=vector)
        state = list(batched_pcg.init_state(
            self.proto, self.a3, self.b3, zeros3, mask=self.mask,
            h1=self.h1, h2=self.h2,
        ))
        # every lane starts parked: the breakdown flag freezes it until
        # a refill swaps a request in (zero-RHS lanes would otherwise
        # burn one iteration reaching the same flag)
        state[_IDX["bd"]] = jnp.ones((lanes,), bool, device=vector)
        self.state = tuple(state)
        if self.layout is not None:
            self.state = jax.device_put(self.state, self.layout.state)
        self.slots: list[Optional[_InFlight]] = [None] * lanes
        # per-bucket chunk override (None = the scheduler-wide default);
        # set at admission from the autotune registry (Scheduler._ctx_for)
        self.chunk: Optional[int] = None
        # the bucket's recycle pool (``runtime.solvecache``): bounded on
        # both axes, owned by THIS context — a mesh degrade/rejoin drops
        # the context (_degrade_mesh's _ctxs.clear()) and the pool dies
        # with it, so rebuilt batches never warm-start from state that
        # predates the event. None when the scheduler runs cache-off.
        self.pool: Optional[SolveCache] = None

    @property
    def active(self) -> bool:
        return any(s is not None for s in self.slots)

    def free_lane(self) -> Optional[int]:
        for lane, slot in enumerate(self.slots):
            if slot is None:
                return lane
        return None


class Scheduler:
    """The continuous-batching serve loop (see module docstring).

    ``clock`` is injectable (monotonic seconds) so deadline semantics
    are deterministically testable; ``idle`` is what ``drain`` calls
    when every queued request is waiting out a retry backoff (default
    ``time.sleep`` — pass the fake clock's ``advance`` in tests).
    ``faults`` takes request-addressed injections
    (``Fault(request_id=...)``); ``mesh`` routes the chunk advance
    through the lane-sharded composition (1 psum/iter, jaxpr-pinned).
    """

    def __init__(
        self,
        lanes: int = DEFAULT_LANES,
        chunk: int = DEFAULT_CHUNK,
        queue_capacity: int = 64,
        dtype=jnp.float32,
        max_retries: int = 1,
        backoff_base_s: float = 0.01,
        journal: RequestJournal | str | None = None,
        clock: Callable[[], float] = time.monotonic,
        idle: Callable[[float], None] = time.sleep,
        faults: Optional[FaultPlan] = None,
        keep_solutions: bool = True,
        mesh=None,
        class_quotas: Optional[dict] = None,
        starvation_after_s: Optional[float] = None,
        warm_start: bool = False,
    ):
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        if lanes < 1:
            raise ValueError("lanes must be >= 1")
        self.lanes = lanes
        self.chunk = chunk
        self.dtype = dtype
        self.max_retries = max_retries
        self.backoff_base_s = backoff_base_s
        self.clock = clock
        self.idle = idle
        self.faults = faults if faults is not None else FaultPlan()
        self.keep_solutions = keep_solutions
        self.mesh = mesh
        # warm_start=True turns on the per-bucket recycle pools: fresh
        # attempt-0 requests consult their bucket's SolveCache for a
        # nearest-neighbour x0 (the semantic cache), converged lanes
        # deposit back. OFF by default: a warm-started lane's solution
        # bits legitimately differ from a cold solve's (same l2, fewer
        # iterations), so the bit-parity pins of the cold serving path
        # stay the default contract and recycling is an explicit opt-in.
        self.warm_start = warm_start
        self.journal = (
            RequestJournal(journal) if isinstance(journal, (str, bytes))
            or hasattr(journal, "__fspath__") else journal
        )
        self.queue = AdmissionQueue(
            queue_capacity, lanes, clock=clock,
            class_quotas=class_quotas,
            starvation_after_s=starvation_after_s,
        )
        self.results: dict[str, ServeResult] = {}
        self._ctxs: dict[tuple, _BatchCtx] = {}
        # grad-kind lifecycle state (diff.serving.GradJob) keyed by
        # request id: host-only, NEVER journaled — a replayed grad
        # request rebuilds its job deterministically, which is what
        # makes the replayed gradient identical (chaos invariant)
        self._grad_jobs: dict[str, object] = {}
        self._np_dtype = assembly.numpy_dtype(dtype)
        # journaled requests recovered by replay() that exceeded queue
        # capacity: fed back into the queue in waves as it drains —
        # never terminally shed (the write-ahead promise outlives one
        # queue's worth of backlog)
        self._replay_backlog: list[ServeRequest] = []
        # graceful-drain latch (begin_drain): a draining scheduler
        # refuses NEW admissions with a redirectable shed but finishes
        # everything already admitted — the fleet's replica-drain hook
        # and the harness's SIGTERM path both flip it
        self.draining = False
        # how many redirect sheds the draining latch issued: those
        # results are deliberately NOT recorded (begin_drain docstring),
        # so without this count a replica killed mid-drain would leave
        # them invisible to the chaos report's zero-lost accounting
        self.drain_sheds = 0

    # -- admission -----------------------------------------------------------

    def submit(self, problem: Problem, deadline_s: float | None = None,
               max_retries: int | None = None,
               request_id: str | None = None,
               tenant: str = "default",
               priority: int = 1) -> Optional[ServeResult]:
        """Admit one request. Returns ``None`` on acceptance, or the
        terminal ``shed`` result (with ``retry_after_s``) when the
        admission policy rejects it."""
        req = ServeRequest(
            problem=problem,
            deadline=(
                None if deadline_s is None else self.clock() + deadline_s
            ),
            max_retries=(
                self.max_retries if max_retries is None else max_retries
            ),
            tenant=tenant,
            priority=priority,
        )
        if request_id is not None:
            req.request_id = request_id
        return self.submit_request(req)

    def _apply_admission_faults(self, req: ServeRequest) -> None:
        """Fire request-addressed ADMISSION faults (``malformed_spec`` /
        ``degenerate_geometry``): the request's geometry spec is swapped
        BEFORE validation, so the drill exercises the real gate."""
        from poisson_ellipse_tpu.resilience import faultinject

        for fault in self.faults.faults:
            if (fault.fired or fault.request_id != req.request_id
                    or fault.kind not in faultinject.ADMISSION_KINDS):
                continue
            fault.fired = True
            obs_trace.event(
                "serve:fault", request_id=req.request_id, lane=None,
                kind=fault.kind, at_iter=0,
            )
            if fault.kind == "malformed_spec":
                req.geometry = dict(faultinject.MALFORMED_SPEC)
            else:
                req.geometry = faultinject.sliver_spec()
                req.theta = fault.theta
            req._geom_obj = None

    def _validate_geometry(self, req: ServeRequest) -> Optional[ServeResult]:
        """The admission rung of the geometry gate: a request carrying a
        geometry spec is validated host-side AT ADMISSION — a bad one
        ends in the terminal classified ``invalid`` outcome (exit 8)
        without ever being journaled or dispatched. Mid-solve geometry
        failure is structurally impossible: no lane sees operands that
        did not pass this gate. Runs AFTER the bounded queue's capacity
        check: validation is real host work (quadrature assembly + the
        Lanczos probe), and overload must hit the cheap backpressure
        reject first, not an unmetered validation grinder."""
        if req.geometry is None:
            return None
        from poisson_ellipse_tpu.geom import validate as geom_validate
        from poisson_ellipse_tpu.resilience.errors import (
            InvalidGeometryError,
        )

        try:
            geom_validate.validate(
                req.problem, req.geometry_sdf(), theta=req.theta
            )
        except InvalidGeometryError as e:
            result = ServeResult(
                request_id=req.request_id, outcome="invalid",
                detail=e.reason,
            )
            self.results[req.request_id] = result
            obs_metrics.counter("invalid_geometry_total").inc()
            obs_trace.event(
                "serve:invalid-geometry", request_id=req.request_id,
                reason=e.reason,
            )
            return result
        return None

    def _validate_objective(self, req: ServeRequest) -> Optional[ServeResult]:
        """The grad kind's admission rung: a malformed objective spec
        ends terminally ``invalid`` at the door — same stance as the
        geometry gate, so no lane ever hosts a request whose cotangent
        evaluation would throw at a chunk boundary."""
        if not req.grad:
            return None
        from poisson_ellipse_tpu.diff.objectives import objective_from_spec

        try:
            objective_from_spec(req.objective, req.problem)
        except (ValueError, TypeError) as e:
            # TypeError belt: the objectives layer classifies malformed
            # payloads as ValueError, but an admission gate must never
            # let a client payload crash the scheduler step
            result = ServeResult(
                request_id=req.request_id, outcome="invalid",
                detail=f"objective: {e}",
            )
            self.results[req.request_id] = result
            obs_trace.event(
                "serve:invalid-objective", request_id=req.request_id,
                reason=str(e),
            )
            return result
        return None

    def begin_drain(self) -> None:
        """The graceful-shutdown hook: stop admitting, keep working.

        New submissions are refused with a shed carrying the projected
        wait as ``retry_after_s`` (and detail ``draining``) WITHOUT
        being recorded as this scheduler's terminal outcome — the
        rejection is a redirect for the caller (the fleet router's next
        replica, a SIGTERM'd CLI's client), not a lifecycle event of a
        request this scheduler never owned. Everything already admitted
        (queued, backlogged, in flight) still runs to a classified
        terminal state through the normal ``drain()``."""
        if not self.draining:
            self.draining = True
            obs_trace.event(
                "serve:drain-begin",
                queued=len(self.queue),
                in_flight=sum(
                    1 for c in self._ctxs.values()
                    for s in c.slots if s is not None
                ),
            )

    def adopt_request(self, req: ServeRequest) -> None:
        """Adopt a handed-off request from a dead peer's journal
        (``fleet.handoff``): journal-first (the write-ahead promise
        transfers to THIS scheduler before anything acknowledges the
        handoff), then the replay backlog's wave machinery — an adopted
        request is never terminally shed on capacity, exactly like a
        replayed one."""
        if self.journal is not None:
            self.journal.record_admit(req)
        req.replayed = True
        self._replay_backlog.append(req)
        self._admit_replay_wave()

    def submit_request(self, req: ServeRequest) -> Optional[ServeResult]:
        if self.draining:
            # the redirect shed stays unrecorded (begin_drain), but it
            # is COUNTED: drain_sheds is what keeps the chaos report's
            # zero-lost accounting provable when this replica is killed
            # mid-drain
            self.drain_sheds += 1
            return ServeResult(
                request_id=req.request_id, outcome="shed",
                detail="draining",
                retry_after_s=self.queue.projected_wait(),
            )
        prior = self.results.get(req.request_id)
        if prior is not None and prior.outcome == "shed" and not prior.dispatched:
            # shed-at-admission is "safe to resubmit after retry_after_s"
            # (the request.py outcome table): the resubmission supersedes
            # the rejection record instead of reading as a duplicate —
            # nothing was journaled or dispatched, so nothing can double
            del self.results[req.request_id]
        if self._knows(req.request_id):
            # a second live (or already-terminal) submission under the
            # same id can never get its own outcome slot — refuse it at
            # the door WITHOUT touching the original's lifecycle (no
            # results entry, no journal write: recording it would
            # overwrite or double-complete the first)
            return ServeResult(
                request_id=req.request_id, outcome="shed",
                detail="duplicate-request-id",
            )
        self._apply_admission_faults(req)
        accepted, retry_after, reason = self.queue.admit(req)
        self._classify_evicted()
        if not accepted:
            result = ServeResult(
                request_id=req.request_id, outcome="shed", detail=reason,
                retry_after_s=retry_after,
            )
            self.results[req.request_id] = result
            return result
        invalid = self._validate_geometry(req)
        if invalid is not None:
            # compensate the admit: the request leaves the queue before
            # anything durable (journal) or dispatchable sees it
            self.queue.retract(req, "invalid-geometry")
            return invalid
        invalid = self._validate_objective(req)
        if invalid is not None:
            self.queue.retract(req, "invalid-objective")
            return invalid
        if self.journal is not None:
            # write-ahead: the admission is acknowledged only once the
            # journal holds it; a failed journal write un-queues the
            # request and surfaces the error instead of promising
            # durability the disk refused
            try:
                self.journal.record_admit(req)
            except BaseException:
                self.queue.retract(req, "journal-write-failed")
                raise
        return None

    def _knows(self, request_id: str) -> bool:
        """Whether an id is already spoken for: queued, backlogged,
        in flight, terminal in the result buffer, or journaled (a
        collected-and-evicted result keeps its journal trail)."""
        return (
            request_id in self.results
            or self.owns_request(request_id)
        )

    def owns_request(self, request_id: str) -> bool:
        """Whether this scheduler owns the id's LIFECYCLE: queued,
        backlogged, in flight, journaled, or terminal — except a
        recorded shed that was never dispatched, which is a rejection
        the outcome table promises is safe to resubmit, not ownership.
        The fleet router's duplicate gate reads this (dead replicas
        included: a since-killed replica's journal still remembers what
        it finished, which is exactly what blocks a client retry from
        double-completing an already-delivered request)."""
        prior = self.results.get(request_id)
        if (prior is not None and prior.outcome == "shed"
                and not prior.dispatched):
            prior = None
        return (
            prior is not None
            or self.queue.holds(request_id)
            or any(r.request_id == request_id for r in self._replay_backlog)
            or self._slot_of(request_id) is not None
            or (
                self.journal is not None
                and self.journal.state_of(request_id) is not None
            )
        )

    def owned_live_ids(self) -> set[str]:
        """Ids whose lifecycle is LIVE here — queued, backlogged, in a
        lane, or journal-admitted (terminal/compacted records excluded).
        The fleet router's cross-epoch co-ownership audit intersects
        these sets across live replicas; any overlap is the split-brain
        the fencing machinery exists to prevent."""
        ids = set(self.queue.request_ids())
        ids.update(r.request_id for r in self._replay_backlog)
        ids.update(
            slot.req.request_id
            for ctx in self._ctxs.values()
            for slot in ctx.slots
            if slot is not None
        )
        if self.journal is not None:
            ids.update(self.journal.admitted_ids())
        return ids

    def prewarm(self, problem: Problem) -> None:
        """Build (or touch) the batch context for ``problem``'s compile
        bucket WITHOUT admitting anything: the warm-pool pre-warming
        hook a fleet rejoin uses to hand a fresh incarnation the
        router's observed shape mix before it takes traffic, so its
        first real requests land on warm contexts instead of paying
        cold compiles on the serving path."""
        self._ctx_for(ServeRequest(problem=problem))

    def replay(self) -> int:
        """Recover every journaled admitted-but-unfinished request (a
        restarted server's first act). Requests beyond the bounded
        queue's capacity wait in a replay backlog and re-enter in waves
        as the queue drains — an acknowledged admission is never
        terminally shed just because the restart arrived with more
        backlog than one queue's worth (the write-ahead promise).
        Returns the number of requests recovered."""
        if self.journal is None:
            raise ValueError("replay needs a journal-backed scheduler")
        reqs = self.journal.unfinished(self.clock())
        for req in reqs:
            # replays run cold (ServeRequest.replayed): the cache is
            # never journaled, so a replayed outcome must not depend on
            # what it held — bit-identical regardless of cache state
            req.replayed = True
            obs_trace.event(
                "serve:replay", request_id=req.request_id,
                grid=[req.problem.M, req.problem.N],
            )
        self._replay_backlog.extend(reqs)
        self._admit_replay_wave()
        return len(reqs)

    def _admit_replay_wave(self) -> None:
        """Move backlogged replay requests into the queue while it has
        room. A request whose restarted deadline budget is already
        infeasible ends ``deadline-miss`` — NOT ``shed``: shed means
        "never admitted, safe to resubmit", and these were durably
        acknowledged (a resubmit under the same id would be refused as
        a duplicate). Capacity overflow is deferred, never terminal."""
        while self._replay_backlog and len(self.queue) < self.queue.capacity:
            req = self._replay_backlog.pop(0)
            accepted, retry_after, reason = self.queue.admit(
                req, record_shed=False
            )
            self._classify_evicted()
            if not accepted:
                self._finish_queued(
                    req, "deadline-miss", detail=f"replay-{reason}",
                    retry_after=retry_after,
                )

    def _classify_evicted(self) -> None:
        """Give every queue-preemption victim (``AdmissionQueue``'s
        ``take_evicted``) its classified terminal: ``shed`` with detail
        ``preempted-by-priority``. The victim WAS journaled at its own
        admission, so the terminal is journaled too (the admit record
        must not replay as a lost request after a crash) — which means
        a preempted id cannot be resubmitted into the same process;
        clients retry with a fresh id, exactly as for any journaled
        terminal."""
        for victim in self.queue.take_evicted():
            self._finish_queued(
                victim, "shed", detail="preempted-by-priority",
                retry_after=self.queue.projected_wait(),
            )

    # -- the serve loop ------------------------------------------------------

    def step(self) -> bool:
        """One chunk across every active bucket: shed expired queued
        requests, refill free lanes, inject due faults, advance, retire.
        Returns True while work remains (in flight or queued).

        Traced as ``serve.step``, holding a ``serve.refill`` per lane
        swapped in, a ``serve.advance`` (faults, limit, dispatch) and a
        ``serve.boundary`` (the flag reads in ``serve.flags``,
        retirements, rebase) per bucket."""
        with obs_trace.span("serve.step"):
            now = self.clock()
            for req in self.queue.expire(now):
                self._finish_queued(
                    req, "deadline-miss", detail="expired-in-queue"
                )
            self._admit_replay_wave()
            self._fill_lanes()
            # lanes just drained the queue — top it back up so the next
            # boundary dispatches from a full line, not a replay-starved one
            self._admit_replay_wave()
            for ctx in list(self._ctxs.values()):
                if not ctx.active or ctx not in self._ctxs.values():
                    continue
                with obs_trace.span("serve.advance"):
                    self._apply_faults(ctx)
                    if not ctx.active or ctx not in self._ctxs.values():
                        continue
                    k = int(ctx.state[0])
                    # the chunk stops early at the nearest per-request
                    # iteration cap (the FaultPlan.next_stop idiom): caps
                    # land exactly, not at the next multiple of `chunk`
                    limit_val = min(k + (ctx.chunk or self.chunk),
                                    ITER_CEILING)
                    for slot in ctx.slots:
                        if slot is not None:
                            limit_val = min(
                                limit_val,
                                slot.base_k + slot.req.problem.max_iterations,
                            )
                    limit = jnp.asarray(max(limit_val, k + 1), jnp.int32)
                    try:
                        ctx.state = ctx.fn(
                            ctx.a3, ctx.b3, ctx.mask, ctx.h1, ctx.h2,
                            ctx.delta, ctx.state, limit,
                        )
                    except Exception as e:  # noqa: BLE001 — classified; unknowns re-raised
                        from poisson_ellipse_tpu.resilience.errors import (
                            classify_error,
                        )

                        if classify_error(e) != "device-loss":
                            raise
                        self._degrade_mesh(
                            "device-loss", getattr(e, "device", None)
                        )
                        continue
                with obs_trace.span("serve.boundary"):
                    self._boundary(ctx)
            return bool(
                len(self.queue) or self._replay_backlog
            ) or any(c.active for c in self._ctxs.values())

    def drain(self, max_steps: int = 100_000) -> dict[str, ServeResult]:
        """Step until every admitted request is terminal. When the only
        remaining work is backoff-parked retries, waits them out via
        ``idle``. ``max_steps`` is a runaway backstop, not a policy."""
        steps = 0
        while True:
            in_flight = any(c.active for c in self._ctxs.values())
            if (not in_flight and not len(self.queue)
                    and not self._replay_backlog):
                return self.results
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"drain exceeded {max_steps} steps with work pending"
                )
            progressed_work = self.step()
            if progressed_work and not any(
                c.active for c in self._ctxs.values()
            ) and len(self.queue):
                wait = self.queue.next_ready_in(self.clock())
                if wait is not None:
                    self.idle(wait)

    def collect(self) -> dict[str, ServeResult]:
        """Hand off and evict every terminal result recorded so far.

        ``results`` (and the solution arrays it retains under
        ``keep_solutions``) otherwise grows for the scheduler's
        lifetime — the unbounded-memory failure mode the admission
        queue exists to prevent, reintroduced at the exit. A long-lived
        server must drain results through here (the ``harness serve``
        loop does); ``drain()`` keeps returning the accumulated dict
        for one-shot callers that read it after the stream ends."""
        out = self.results
        self.results = {}
        return out

    # -- refill --------------------------------------------------------------

    def _ctx_for(self, req: ServeRequest) -> _BatchCtx:
        bucket = grid_bucket(req.problem.M, req.problem.N)
        key = (bucket, req.problem.norm)
        ctx = self._ctxs.get(key)
        if ctx is None:
            ctx = _BatchCtx(
                bucket, self.lanes, self.dtype, req.problem.norm,
                mesh=self.mesh,
            )
            # warm-pool admission is where the autotuner's persisted
            # knobs land on the serving path: a tuned per-shape chunk
            # (sized ~4 refill boundaries per predicted solve) overrides
            # the scheduler-wide default for this bucket's context; no
            # registry → ctx.chunk stays None and nothing changes
            from poisson_ellipse_tpu.runtime import autotune

            # keyed on the request's geometry too: a tuned config is
            # never consulted for a domain it was not tuned for
            tuned = autotune.lookup(req.problem, self.dtype,
                                    geometry=req.geometry)
            if tuned is not None and tuned.knobs.get("chunk"):
                ctx.chunk = int(tuned.knobs["chunk"])
                obs_trace.event(
                    "autotune:serve-chunk", bucket=list(bucket),
                    chunk=ctx.chunk,
                )
            if self.warm_start:
                ctx.pool = SolveCache()
            self._ctxs[key] = ctx
        return ctx

    def _fill_lanes(self) -> None:
        now = self.clock()
        deferred = []
        while True:
            req = self.queue.pop_ready(now)
            if req is None:
                break
            ctx = self._ctx_for(req)
            lane = ctx.free_lane()
            if lane is None:
                deferred.append(req)
                continue
            with obs_trace.span("serve.refill", request_id=req.request_id,
                                lane=lane):
                self._refill_lane(ctx, lane, req)
        for req in reversed(deferred):
            self.queue.push_front(req)

    def _refill_lane(self, ctx: _BatchCtx, lane: int,
                     req: ServeRequest) -> None:
        """Swap a request into a free lane between chunks: embed its
        operands into the lane's slices and re-initialise the lane's
        carry from ``init_state`` — the freeze mask generalized to
        swap-in. Per-lane arithmetic is lane-decoupled, so the refilled
        lane's trajectory is bit-identical to a fresh lane-0 solve of
        the same embedding (pinned in ``tests/test_batched.py``)."""
        p = req.problem
        x0_p, cache_key = None, None
        if req.grad:
            # grad kind: the job's differentiably-assembled operands
            # (primal stage) or the normalised cotangent RHS over the
            # same operator (adjoint stage) — still just a lane
            a_p, b_p, r_p, m_p = self._grad_job(req).embed(
                ctx.bucket, self._np_dtype
            )
        else:
            a_p, b_p, r_p, m_p = _embed_request(
                p, ctx.bucket, self._np_dtype,
                geometry=req.geometry_sdf(), theta=req.theta,
            )
            if self.warm_start:
                x0_p, cache_key = self._consult_pool(
                    ctx, req, a_p, b_p, r_p
                )
        # the lane's fresh carry comes from the same eager init_state
        # every other entry path uses (the bit-parity pin's reference);
        # the scatter into the batch is one fused dispatch
        unit = batched_pcg.init_state(
            ctx.proto, jnp.asarray(a_p)[None], jnp.asarray(b_p)[None],
            jnp.asarray(r_p)[None], mask=jnp.asarray(m_p)[None],
            h1=p.h1, h2=p.h2,
            x0=None if x0_p is None else jnp.asarray(x0_p)[None],
        )
        (ctx.a3, ctx.b3, ctx.mask, ctx.h1, ctx.h2, ctx.delta,
         ctx.state) = ctx.scatter(
            ctx.a3, ctx.b3, ctx.mask, ctx.h1, ctx.h2, ctx.delta,
            ctx.state, unit, a_p, b_p, m_p,
            jnp.asarray(p.h1, ctx.h1.dtype), jnp.asarray(p.h2, ctx.h2.dtype),
            jnp.asarray(p.delta, ctx.delta.dtype),
            jnp.asarray(lane, jnp.int32),
        )
        base_k = int(ctx.state[_IDX["k"]])
        now = self.clock()
        slot = _InFlight(req, lane, base_k, now)
        if cache_key is not None:
            # remember what the retirement deposit needs: the key and
            # the embedded rhs the pool sketches on
            slot.cache_key = cache_key
            slot.rhs_pad = r_p
        ctx.slots[lane] = slot
        req.dispatched = True
        if req.enqueued_t is not None:
            obs_metrics.histogram("time_in_queue_seconds").observe(
                now - req.enqueued_t
            )
        obs_metrics.counter("serve_refills_total").inc()
        obs_trace.event(
            "serve:refill", request_id=req.request_id, lane=lane,
            base_k=base_k, attempt=req.attempt,
            bucket=list(ctx.bucket),
        )

    def _consult_pool(self, ctx: _BatchCtx, req: ServeRequest,
                      a_p, b_p, r_p):
        """The warm-start consult (``warm_start=True`` refills only):
        look the request up in its bucket's recycle pool and admit the
        nearest-neighbour hit through the true-residual check. Returns
        ``(x0 or None, cache_key)`` — the key always, so the retirement
        deposit works even on a miss.

        Only FRESH work consults: attempt-0 (a retried request's lane
        already went bad once — run it cold), never replays (the journal
        contract: a replayed outcome must not depend on cache state).
        Everything downstream is defensive — ``check_warm_start`` drops
        non-finite seeds and flags bad ones (``recycle:bad-hit``), and
        the batched init verifies by true residual — so the worst any
        entry (including a ``cache_poison``-injected one) costs is
        iterations."""
        from poisson_ellipse_tpu.solver import recycle

        p = req.problem
        key = solve_key(p, self.dtype, geometry=req.geometry)
        x0, dist = None, None
        if (ctx.pool is not None and req.attempt == 0
                and not req.replayed):
            x0, dist = ctx.pool.lookup(key, r_p)
        poisoned = self._cache_poison_fault(req)
        if poisoned:
            from poisson_ellipse_tpu.resilience import faultinject

            x0 = faultinject.poisoned_guess(r_p.shape, self._np_dtype)
        if x0 is None:
            return None, key
        # validate on the TRUE grid (the zero-extension pad slices off
        # exactly): the ratio is measured against the request's own
        # operator and spacings, not the bucket's
        g1, g2 = p.M + 1, p.N + 1
        x0 = np.asarray(x0, self._np_dtype)
        checked, ratio = recycle.check_warm_start(
            p, a_p[:g1, :g2], b_p[:g1, :g2], r_p[:g1, :g2],
            jnp.asarray(x0[:g1, :g2]), source="solvecache",
            request_id=req.request_id,
        )
        if checked is None:
            return None, key
        out = np.zeros_like(r_p)
        out[:g1, :g2] = np.asarray(checked)
        obs_metrics.counter("solvecache_hit_total").inc()
        obs_trace.event(
            "recycle:hit", request_id=req.request_id,
            distance=dist, ratio=ratio, poisoned=poisoned,
        )
        return out, key

    def _cache_poison_fault(self, req: ServeRequest) -> bool:
        """Fire a pending ``cache_poison`` fault addressed to ``req``
        (one-shot, like every injection): the consult's answer gets
        replaced with a deliberately wrong solution."""
        from poisson_ellipse_tpu.resilience import faultinject

        for fault in self.faults.faults:
            if (fault.fired or fault.request_id != req.request_id
                    or fault.kind not in faultinject.CACHE_KINDS):
                continue
            fault.fired = True
            obs_trace.event(
                "serve:fault", request_id=req.request_id, lane=None,
                kind=fault.kind, at_iter=0,
            )
            return True
        return False

    def _park_lane(self, ctx: _BatchCtx, lane: int) -> None:
        """Return a lane to the parked pool (``_park_state``)."""
        ctx.state = ctx.park(ctx.state, lane)
        ctx.slots[lane] = None

    # -- retirement ----------------------------------------------------------

    def _boundary(self, ctx: _BatchCtx) -> None:
        """The chunk-boundary host read: retire finished lanes, each in a
        ``serve.retire`` span whose ``outcome`` is what the boundary saw.
        Ordering is the deadline contract — converged lanes first (a
        result beats a miss at the same boundary), then fault
        retirement into the retry ladder, then deadline cancels, then
        per-request iteration caps."""
        # on a mesh each read gathers the flags from every chip
        with obs_trace.span("serve.flags"):
            conv = np.asarray(ctx.state[_IDX["conv"]])
            bd = np.asarray(ctx.state[_IDX["bd"]])
            quar = np.asarray(ctx.state[_IDX["quar"]])
            iters = np.asarray(ctx.state[_IDX["iters"]])
            diffs = np.asarray(ctx.state[_IDX["diff"]])
        now = self.clock()
        for lane, slot in enumerate(ctx.slots):
            if slot is None:
                continue
            req = slot.req
            req_iters = int(iters[lane]) - slot.base_k
            diff = float(diffs[lane])
            if conv[lane]:
                outcome = "converged"
            elif quar[lane] or bd[lane]:
                outcome = "lane-quarantine" if quar[lane] else "breakdown"
            elif req.deadline is not None and now > req.deadline:
                outcome = "deadline-miss"
            elif req_iters >= req.problem.max_iterations:
                outcome = "cap"
            else:
                continue
            with obs_trace.span("serve.retire", request_id=req.request_id,
                                lane=lane, outcome=outcome):
                if outcome == "converged" and req.grad:
                    self._grad_boundary(ctx, lane, slot, req_iters, diff)
                elif outcome == "converged":
                    self._finish(
                        ctx, lane, slot, "completed", iters=req_iters,
                        diff=diff, converged=True,
                    )
                elif outcome == "deadline-miss":
                    self._finish(
                        ctx, lane, slot, "deadline-miss", iters=req_iters,
                        diff=diff, partial=True, detail="expired-mid-solve",
                    )
                elif outcome == "cap":
                    self._finish(
                        ctx, lane, slot, "cap", iters=req_iters, diff=diff
                    )
                else:
                    self._park_lane(ctx, lane)
                    self._retry_or_fallback(slot, outcome)
        # rebase the batch's global clock: k only moves forward, and a
        # hot bucket on a long-lived server would otherwise walk it
        # into ITER_CEILING (~2^30 iterations ≈ 2M solves) and wedge —
        # limit could no longer exceed k, so no lane would ever advance
        # or retire again. The shift is uniform across k / per-lane
        # iters / slot base_k (iters tracks global k for active lanes),
        # so every per-request count and cap is invariant under it.
        if ctx.active:
            base = min(s.base_k for s in ctx.slots if s is not None)
        else:
            base = int(ctx.state[_IDX["k"]])
        if base > 0:
            state = list(ctx.state)
            state[_IDX["k"]] = state[_IDX["k"]] - base
            state[_IDX["iters"]] = state[_IDX["iters"]] - base
            ctx.state = tuple(state)
            for s in ctx.slots:
                if s is not None:
                    s.base_k -= base

    # -- the grad kind (diff.serving) ----------------------------------------

    def _grad_job(self, req: ServeRequest):
        """The request's GradJob, built on first dispatch (and rebuilt
        deterministically after a replay — the job is host state, the
        journal holds only the request spec)."""
        job = self._grad_jobs.get(req.request_id)
        if job is None:
            from poisson_ellipse_tpu.diff.serving import GradJob

            job = GradJob(req)
            self._grad_jobs[req.request_id] = job
        return job

    def _grad_boundary(self, ctx: _BatchCtx, lane: int, slot: _InFlight,
                       req_iters: int, diff: float) -> None:
        """A grad request's lane converged: either stage the adjoint
        (primal done — the cotangent becomes the next lane's RHS) or
        terminally complete with (value, grad) (adjoint done)."""
        req = slot.req
        job = self._grad_job(req)
        g1, g2 = req.problem.M + 1, req.problem.N + 1
        u = _fetch_lane(ctx.state[_IDX["w"]], lane)[:g1, :g2].copy()
        if job.stage == "primal":
            pending = job.absorb_primal(u, req_iters)
            self._park_lane(ctx, lane)
            if pending:
                obs_trace.event(
                    "diff:adjoint-dispatch", request_id=req.request_id,
                    lane=lane, primal_iters=req_iters,
                    value=job.value,
                )
                # the adjoint is an ordinary queued dispatch: it lands
                # on whatever lane frees next (retire-and-refill), and
                # deadline expiry still applies while it waits. Re-entry
                # goes through the replay-backlog waves, NOT push_front:
                # the request holds no queue slot right now, so a full
                # queue's maxlen backstop would silently evict someone
                # else's admission — the backlog is the never-shed lane
                # for work the scheduler already owns
                self._replay_backlog.append(req)
                self._admit_replay_wave()
            else:
                # zero cotangent — the gradient is exactly zero; no
                # second solve to pay for
                self._grad_finish(req, slot, job, job.zero_grad(),
                                  iters=req_iters, diff=diff, lane=lane)
            return
        grad = job.finish(u, req_iters)
        self._park_lane(ctx, lane)
        self._grad_finish(req, slot, job, grad,
                          iters=job.primal_iters + req_iters, diff=diff,
                          lane=lane)

    def _grad_finish(self, req: ServeRequest, slot: _InFlight, job,
                     grad, iters: int, diff: float, lane: int) -> None:
        now = self.clock()
        self.queue.observe_service(now - slot.t_dispatch)
        result = ServeResult(
            request_id=req.request_id, outcome="completed", iters=iters,
            diff=diff, converged=True, dispatched=True,
            attempts=req.attempt + 1,
            time_in_queue_s=(
                slot.t_dispatch - req.enqueued_t
                if req.enqueued_t is not None else 0.0
            ),
            total_s=self._span_s(req, now),
            detail="grad",
            w=(np.asarray(job.u).copy()
               if self.keep_solutions and job.u is not None else None),
            value=job.value,
            grad=np.asarray(grad, np.float64).tolist(),
        )
        obs_metrics.counter("grad_completed_total").inc()
        self._record_terminal(result, lane=lane)

    @staticmethod
    def _span_s(req: ServeRequest, now: float) -> float:
        """End-to-end seconds since the request's FIRST admission:
        ``admitted_t`` survives retry requeues, which re-stamp
        ``enqueued_t`` for the per-visit queue-wait histogram."""
        anchor = (
            req.admitted_t if req.admitted_t is not None else req.enqueued_t
        )
        return now - anchor if anchor is not None else 0.0

    def _finish(self, ctx: _BatchCtx, lane: int, slot: _InFlight,
                outcome: str, iters: int = 0, diff: float = float("inf"),
                converged: bool = False, partial: bool = False,
                detail: str | None = None) -> None:
        req = slot.req
        now = self.clock()
        w = None
        if self.keep_solutions and (converged or partial):
            g1, g2 = req.problem.M + 1, req.problem.N + 1
            w = _fetch_lane(ctx.state[_IDX["w"]], lane)[:g1, :g2].copy()
        if (converged and slot.cache_key is not None
                and ctx.pool is not None):
            # the deposit half of the recycle pool: a converged lane's
            # EMBEDDED solution under its cache key, sketched on the
            # same embedded rhs a future consult will sketch on
            ctx.pool.put(
                slot.cache_key, slot.rhs_pad,
                _fetch_lane(ctx.state[_IDX["w"]], lane).copy(),
                iters=iters,
            )
        self._park_lane(ctx, lane)
        self.queue.observe_service(now - slot.t_dispatch)
        result = ServeResult(
            request_id=req.request_id, outcome=outcome, iters=iters,
            diff=diff, converged=converged, partial=partial,
            dispatched=True, attempts=req.attempt + 1,
            time_in_queue_s=(
                slot.t_dispatch - req.enqueued_t
                if req.enqueued_t is not None else 0.0
            ),
            total_s=self._span_s(req, now),
            detail=detail, w=w,
        )
        self._record_terminal(result, lane=lane)

    def _finish_queued(self, req: ServeRequest, outcome: str,
                       detail: str | None = None,
                       retry_after: float | None = None) -> None:
        """Terminate a request while it is off-lane (queued expiry,
        replay shed, a failed fallback). ``dispatched`` reports the
        request's history, not this moment: a fresh expired-in-queue
        request was never dispatched (the satellite contract), while a
        retried or fallen-back one really did run on a lane first."""
        now = self.clock()
        result = ServeResult(
            request_id=req.request_id, outcome=outcome,
            dispatched=req.dispatched,
            attempts=req.attempt,
            time_in_queue_s=(
                now - req.enqueued_t if req.enqueued_t is not None else 0.0
            ),
            total_s=self._span_s(req, now),
            detail=detail, retry_after_s=retry_after,
        )
        self._record_terminal(result)

    def _record_terminal(self, result: ServeResult,
                         lane: int | None = None) -> None:
        # journal FIRST: the terminal record lives where the durability
        # promise does, and a fenced journal (fleet.replica) rejecting a
        # zombie's stale write must abort the completion BEFORE it lands
        # in the result buffer a harvester could read
        if self.journal is not None:
            self.journal.record_outcome(
                result.request_id, result.outcome, detail=result.detail
            )
        self.results[result.request_id] = result
        # a terminal grad request's host lifecycle state goes with it
        # (deadline-miss/cap/failed included — replay rebuilds)
        self._grad_jobs.pop(result.request_id, None)
        if result.outcome == "deadline-miss":
            obs_metrics.counter("deadline_miss_total").inc()
        elif result.outcome == "completed":
            obs_metrics.counter("serve_completed_total").inc()
        obs_trace.event(
            "serve:retire", request_id=result.request_id, lane=lane,
            outcome=result.outcome, iters=result.iters,
            attempts=result.attempts, partial=result.partial,
            detail=result.detail,
        )

    # -- the retry ladder ----------------------------------------------------

    def _retry_or_fallback(self, slot: _InFlight, cause: str) -> None:
        """Walk the degradation ladder for a request whose lane went
        bad: within budget, back off exponentially and resubmit on a
        fresh lane; past it, fall to the guarded single solve — the
        rung where the full recovery machinery of ``resilience.guard``
        takes over. Every rung ends in a classified outcome."""
        req = slot.req
        req.attempt += 1
        if req.grad:
            # the lane's carry is gone; a grad request restarts its
            # two-stage lifecycle from the primal (deterministic, so
            # the eventual gradient is unchanged)
            job = self._grad_jobs.get(req.request_id)
            if job is not None:
                job.reset()
        if req.attempt <= req.max_retries:
            backoff = self.backoff_base_s * (2 ** (req.attempt - 1))
            req.not_before = self.clock() + backoff
            obs_metrics.counter("serve_retries_total").inc()
            obs_trace.event(
                "serve:retry", request_id=req.request_id, cause=cause,
                attempt=req.attempt, backoff_s=round(backoff, 4),
            )
            if not self.queue.requeue(req):
                self._finish_queued(
                    req, "failed", detail="requeue-shed-under-overload"
                )
            return
        self._guarded_fallback(req, cause)

    def _guarded_fallback(self, req: ServeRequest, cause: str) -> None:
        """The ladder's last rung: one guarded single solve of the true
        (un-embedded) problem, with the remaining deadline budget as the
        guard's timeout."""
        from poisson_ellipse_tpu.resilience.guard import guarded_solve

        # the fallback's dispatch instant: queue-wait accounting stops
        # here — the solve itself must not read as time spent queued
        t_dispatch = self.clock()
        timeout = None
        if req.deadline is not None:
            timeout = req.deadline - t_dispatch
            if timeout <= 0:
                self._finish_queued(
                    req, "deadline-miss",
                    detail=f"expired-before-fallback ({cause})",
                )
                return
        obs_trace.event(
            "serve:fallback", request_id=req.request_id, cause=cause,
            attempt=req.attempt,
        )
        if req.grad:
            # the grad kind's last rung: the un-laned implicit solve
            # (diff.serving.solve_grad_direct) — deterministic, so the
            # fallback quotes the same (value, grad) a lane pair would
            from poisson_ellipse_tpu.diff.serving import solve_grad_direct

            try:
                value, grad, iters = solve_grad_direct(req)
            except Exception:  # tpulint: disable=TPU009 — classified below
                self._finish_queued(
                    req, "failed", detail=f"grad-fallback-error ({cause})"
                )
                return
            now = self.clock()
            if req.deadline is not None and now > req.deadline:
                # the implicit solve is not chunk-cancellable (yet), so
                # the deadline is enforced at its granularity: a late
                # gradient is classified, never delivered as completed
                self._finish_queued(
                    req, "deadline-miss",
                    detail=f"grad-fallback-exceeded-deadline ({cause})",
                )
                return
            self._record_terminal(ServeResult(
                request_id=req.request_id, outcome="completed",
                iters=iters, diff=0.0, converged=True, dispatched=True,
                attempts=req.attempt + 1,
                time_in_queue_s=(
                    t_dispatch - req.enqueued_t
                    if req.enqueued_t is not None else 0.0
                ),
                total_s=self._span_s(req, now),
                detail="grad-guarded-fallback",
                value=value, grad=np.asarray(grad).tolist(),
            ))
            return
        try:
            guarded = guarded_solve(
                req.problem, "xla", self.dtype, chunk=self.chunk,
                timeout=timeout, geometry=req.geometry_sdf(),
                theta=req.theta,
                # already validated at admission; never re-gate mid-ladder
                validate_geometry=False,
            )
        except SolveError as e:
            outcome = (
                "deadline-miss" if e.classification == "timeout" else
                "failed"
            )
            self._finish_queued(
                req, outcome,
                detail=f"guarded-fallback-{e.classification}",
            )
            return
        result = guarded.result
        now = self.clock()
        res = ServeResult(
            request_id=req.request_id,
            outcome="completed" if bool(result.converged) else "cap",
            iters=int(result.iters), diff=float(result.diff),
            converged=bool(result.converged), dispatched=True,
            attempts=req.attempt + 1,
            time_in_queue_s=(
                t_dispatch - req.enqueued_t
                if req.enqueued_t is not None else 0.0
            ),
            total_s=self._span_s(req, now),
            detail="guarded-fallback",
            w=(
                np.asarray(result.w).copy()
                if self.keep_solutions and bool(result.converged) else None
            ),
        )
        self._record_terminal(res)

    # -- mesh degradation ----------------------------------------------------

    def _degrade_mesh(self, cause: str, device: int | None) -> None:
        """A device under the batch died: every live batch carry died
        with it (the mesh's arrays are unrecoverable), but no REQUEST
        does — each in-flight request re-enters through the same
        journal-backed retry ladder a lane fault uses, so the chaos
        invariants (zero lost / zero double) hold across a device kill
        exactly as they do across a process kill. A sharded scheduler
        also shrinks its mesh (``parallel.elastic``) so rebuilt batch
        contexts land on the survivors; shapes are compile keys, so the
        rebuilds warm naturally."""
        in_flight = [
            slot
            for ctx in self._ctxs.values()
            for slot in ctx.slots
            if slot is not None
        ]
        obs_trace.event(
            "degrade:mesh",
            cause=cause,
            lost_devices=[device] if device is not None else [],
            in_flight=len(in_flight),
        )
        obs_metrics.counter("mesh_degrade_total").inc()
        # the carries are gone: drop every batch context; _ctx_for
        # rebuilds on demand (on the shrunk mesh, when sharded)
        self._ctxs.clear()
        if self.mesh is not None and device is not None:
            from poisson_ellipse_tpu.parallel.elastic import shrink_mesh
            from poisson_ellipse_tpu.resilience.errors import (
                DeviceLossError,
            )

            try:
                self.mesh = shrink_mesh(self.mesh, [device])
            except DeviceLossError:
                # no mesh left: the single-device path still serves
                self.mesh = None
        for slot in in_flight:
            self._retry_or_fallback(slot, cause)

    # -- fault injection -----------------------------------------------------

    def _slot_of(self, request_id: str):
        for ctx in self._ctxs.values():
            for slot in ctx.slots:
                if slot is not None and slot.req.request_id == request_id:
                    return ctx, slot
        return None

    def _apply_faults(self, ctx: _BatchCtx) -> None:
        """Fire request-addressed faults due at this boundary.
        ``at_iter`` counts the request's own iterations; injection lands
        at the first chunk boundary at or past it (the chunk-granular
        form of the guard's exact-iteration injection). ``oom`` is a
        dispatch-level failure — the lane is freed and the request walks
        the retry ladder; carry faults corrupt the lane slice and let
        the in-loop quarantine detect them."""
        if not self.faults:
            return
        iters = None
        for fault in list(self.faults.faults):
            if fault.fired or fault.request_id is None:
                continue
            located = self._slot_of(fault.request_id)
            if located is None or located[0] is not ctx:
                continue
            _, slot = located
            if iters is None:
                iters = np.asarray(ctx.state[_IDX["iters"]])
            req_iters = int(iters[slot.lane]) - slot.base_k
            if req_iters < fault.at_iter:
                continue
            if not fault.persistent:
                fault.fired = True
            obs_trace.event(
                "serve:fault", request_id=fault.request_id,
                lane=slot.lane, kind=fault.kind, at_iter=fault.at_iter,
            )
            if fault.kind == "oom":
                # what a real RESOURCE_EXHAUSTED on the dispatch looks
                # like to the scheduler: the lane is lost, the request
                # is not — straight onto the retry ladder
                self._park_lane(ctx, slot.lane)
                self._retry_or_fallback(slot, "oom")
                continue
            if fault.kind == "device_loss":
                # a whole device under the batch: every in-flight
                # request (this context's and the others') re-enters;
                # the addressed request only picks WHEN the kill lands
                self._degrade_mesh("device-loss", fault.device)
                return
            lane_fault = Fault(
                fault.kind, at_iter=fault.at_iter, field=fault.field,
                rows=fault.rows, lane=slot.lane,
            )
            from poisson_ellipse_tpu.resilience import faultinject

            ctx.state = faultinject._corrupt(
                list(ctx.state), lane_fault, _FIELDS, _IDX["bd"],
                _IDX["zr"],
            )
