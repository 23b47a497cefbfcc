"""Engine selection: one entry point over the single-chip solver engines.

The reference's ``main`` always runs its fastest implementation — stage4
launches every CUDA kernel each iteration (``poisson_mpi_cuda2.cu:985-1038``,
``:846-939``). The TPU framework has five single-chip engines with different
capacity/perf envelopes; this module is the policy that picks the fastest
one that fits, so every product entry point (bench, CLI, harness) gets the
best path by default:

  engine       capacity (f32)                measured vs XLA (bench chip)
  ---------    ---------------------------   ----------------------------
  resident     whole solve in VMEM           4.0-5.8x  (<= ~1100x1650)
  streamed     state in VMEM, ops streamed   1.6-2.0x  (<= ~2400x3200)
  xl           state AND ops tile-streamed   ~1.2x     (any grid size)
  fused        two-kernel HBM iteration      ~1.2x     (small-mid grids)
  xla          lax.while_loop, XLA-fused     1.0x      (any grid, any dtype)
  pallas       XLA loop + per-op Pallas      ~1.0x     (comparison engine:
               stencil kernel                           stage4's kernel-per-
                                                        op structure)
  pipelined    Ghysels-Vanroose recurrence:  ~1.0x     (any grid, any dtype;
               ONE fused dot bundle/iter,              iters within +-2 of
               stencil overlaps it                     xla, not bitwise)
  pipelined-   pipelined recurrence driving  ~1.0x     (f32/bf16; the
  pallas       the fused stencil+partials              one-VMEM-pass form
               Pallas kernel                           of the same loop)
  batched      B independent lanes in ONE    per-lane  (lanes= selects B;
               fused while_loop, per-lane    cost      the throughput
               masked updates + quarantine   amortised engine — batch.*)
  batched-     the same lanes through the    as above  (one stacked (8,B)
  pipelined    pipelined recurrence                    dot bundle/iter)
  mg-pcg       classical loop, z = V-cycle   O(10¹)    (the iteration-
               over coarsened coefficients   iters at  count killer —
               w/ Chebyshev smoothers        any grid  mg.*; ~8× more
                                                       HBM/iter)
  cheb-pcg     classical loop, z = degree-k  ~k× fewer (the cheap first
               Chebyshev polynomial in D⁻¹A  iters     rung; bounds from
                                                       obs.spectrum)
  sstep        s-step (communication-        ~1.0x     (s∈{2,4} iters per
               avoiding) recurrence:                   matrix-powers round;
               matrix-powers basis + Gram              sharded: 1 psum +
               in ONE stacked reduction                one s-deep halo per
               per s iterations                        s iters — the mesh-
                                                       latency frontier)
  sstep-       the same blocks driving the   ~1.0x     (storage_dtype= runs
  pallas       Pallas stencil chain                    the mixed kernels)
  fmg          ONE full-multigrid F-cycle    O(N)      (the asymptotic-work
               + the VERIFIED mg-pcg         work,     killer — mg.fmg;
               handoff against δ             const/pt  handoff iters ~ 1)

Every STORAGE_ENGINES member additionally takes ``storage_dtype=`` —
bf16 state/operand storage with f32 compute (``ops.precision``), the
HBM-bandwidth lever; accuracy is recovered through the guard's
bf16→f32→f64 escalation ladder (``resilience.guard``), not assumed.

Policy (``select_engine``): resident if the whole working set fits VMEM;
else streamed if the state fits; else xl. f64 always takes xla — the
Pallas engines are f32/bf16 (TPU f64 is emulated, and the XLA path is the
only one with an f64 story). ``fused`` never wins outright on the bench
chip so auto never picks it, but it remains selectable for comparison.
The ``pipelined`` pair restructures the *recurrence* (one fused reduction
per iteration instead of two serialized ones — ``ops.pipelined_pcg``);
on one chip that trades ~2x the streamed passes for half the
reduce→broadcast barriers, a wash at the bench grids, so auto never
picks it either — its payoff is the sharded path, where the single
stacked psum halves the collectives per iteration
(``parallel.pipelined_sharded``) and it IS the mesh engine of choice at
collective-latency-bound scale. Iteration counts land within ±2 of xla
(a documented reordering, not bitwise — see ``ops.pipelined_pcg``).

Past the streamed gate (~2400x3200 f32; e.g. the 4096² north-star grid,
whose state alone is ~200 MB) solves are HBM-bandwidth-bound; the xl
kernel restructures the iteration below the XLA loop's traffic floor
(z-state + deferred w-update: ~12.1 array-passes/iter vs ~13, at a
higher achieved fraction of peak — measured 4.28 s vs 5.16 s at 4096²).
The framework's *scaling* answer at that size remains the sharded mesh
path (``parallel.pcg_sharded``), which divides the state over devices
until it is VMEM-resident again.
"""

from __future__ import annotations

import jax.numpy as jnp

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs import trace as obs_trace
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.solver.pcg import PCGResult, pcg

# the Pallas engine modules import solver.pcg at their top level (which
# runs this package's __init__), so they are imported lazily here

# ONE engine-capability table: every per-engine fact the framework used
# to scatter across parallel tuples (the old ENGINES / STORAGE_ENGINES /
# HISTORY_ENGINES / PRECOND_KIND_BY_ENGINE / auto-ladder quintet, each
# hand-maintained) lives in exactly one row here, and every consumer —
# build_solver's dispatch, the guard, the harness, obs.static_cost AND
# the autotuner (runtime.autotune, which reads ``tunables``) — derives
# from it. Registering a new engine means adding ONE row.
#
#   family    — "loop" (XLA while_loop), "megakernel" (VMEM scalar
#               state), "batched" (per-lane), "precond" (V-cycle/Cheb
#               preconditioned classical loop), "sstep", "fmg"
#   storage   — accepts the storage-vs-compute split (ops.precision)
#   history   — can record the obs.convergence buffers
#   capacity  — rung on the "auto" capacity ladder (0 = tried first),
#               None = auto never picks it (opt-in engines)
#   precond_kind — the mg.* preconditioner kind the engine's modeled
#               extra traffic / fallback ladder keys on (None = diag)
#   tunables  — the engine's autotunable knobs with their static
#               defaults (what runtime.autotune turns and what tpulint
#               TPU019 fences from being hardcoded at call sites)
#   contracts — the engine's jaxpr-level structural guarantees, checked
#               by the declarative contract matrix (analysis.contracts;
#               `python -m poisson_ellipse_tpu.analysis`). Keys are
#               deviations from analysis.contracts.CONTRACT_DEFAULTS:
#                 sharded_psum     — psums per sharded while body
#                                    (None = the engine has no sharded
#                                    form; the matrix skips that cell)
#                 sharded_halo     — halo exchanges per sharded body
#                                    (each is 4 ppermutes), "precond"
#                                    = stencil + the V-cycle/Chebyshev
#                                    budget (mg_sharded.
#                                    halos_per_precond), None = the
#                                    count is deliberately unpinned
#                                    (pipelined's replacement branch)
#                 batched_psum/_halo — the lane-sharded cadence
#                 abft             — the ABFT stepper must add ZERO
#                                    collectives (on/off identity)
#                 guard            — the guard adapter family whose
#                                    chunk advance must trace the
#                                    byte-identical unguarded jaxpr
#                 storage_identity — storage_dtype=None must trace the
#                                    byte-identical pre-storage jaxpr
#                 storage_narrow   — a bf16-storage body must widen on
#                                    load and narrow on store
#                 history_resident — history=True stays device-resident
#                                    (no callbacks), history=False adds
#                                    no dynamic_update_slice
#                 fcycle_budget    — whole-trace ppermute budget
#                                    (halos_per_fcycle) applies
#                 fleet_chaos      — the kill→rejoin fleet drill's
#                                    survivability invariants hold and
#                                    the chaos verdict is sensitive to
#                                    each of them
#                 recycle          — recycle=None/x0=None trace the
#                                    byte-identical default jaxpr and
#                                    the sharded deflated init folds k
#                                    deflation dots into one stacked
#                                    psum (2 total, zero loop bodies)
#               A row WITHOUT this key is itself a finding: registering
#               an engine means declaring its structural contract.
ENGINE_CAPS = {
    "resident": dict(family="megakernel", storage=False, history=False,
                     capacity=0, precond_kind=None, tunables={},
                     contracts={}),
    "streamed": dict(family="megakernel", storage=True, history=False,
                     capacity=1, precond_kind=None, tunables={},
                     contracts={}),
    "xl": dict(family="megakernel", storage=True, history=False,
               capacity=2, precond_kind=None, tunables={},
               contracts={}),
    "xla": dict(family="loop", storage=True, history=True,
                capacity=3, precond_kind=None, tunables={},
                contracts=dict(sharded_psum=2, sharded_halo=1, abft=True,
                               guard="classical", storage_identity=True,
                               storage_narrow=True, history_resident=True,
                               fleet_chaos=True, recycle=True)),
    "fused": dict(family="loop", storage=False, history=True,
                  capacity=None, precond_kind=None, tunables={},
                  contracts=dict(sharded_psum=2, sharded_halo=1,
                                 history_resident=True)),
    "pallas": dict(family="loop", storage=True, history=True,
                   capacity=None, precond_kind=None, tunables={},
                   contracts=dict(sharded_psum=2, sharded_halo=1,
                                  history_resident=True)),
    "pipelined": dict(family="loop", storage=True, history=True,
                      capacity=None, precond_kind=None, tunables={},
                      contracts=dict(sharded_psum=1, abft=True,
                                     guard="pipelined",
                                     storage_identity=True,
                                     storage_narrow=True,
                                     history_resident=True)),
    "pipelined-pallas": dict(family="loop", storage=True, history=True,
                             capacity=None, precond_kind=None, tunables={},
                             contracts=dict(history_resident=True)),
    "batched": dict(family="batched", storage=True, history=False,
                    capacity=None, precond_kind=None,
                    tunables={"chunk": 16},
                    contracts=dict(batched_psum=1, batched_halo=0)),
    "batched-pipelined": dict(family="batched", storage=False,
                              history=False, capacity=None,
                              precond_kind=None, tunables={"chunk": 16},
                              contracts=dict(batched_psum=1,
                                             batched_halo=0)),
    "mg-pcg": dict(family="precond", storage=False, history=True,
                   capacity=None, precond_kind="mg",
                   tunables={"levels": None, "nu": 2, "coarse_degree": 24},
                   contracts=dict(sharded_psum=2, sharded_halo="precond",
                                  abft=True)),
    "cheb-pcg": dict(family="precond", storage=False, history=True,
                     capacity=None, precond_kind="cheb",
                     tunables={"cheb_degree": 12},
                     contracts=dict(sharded_psum=2, sharded_halo="precond",
                                    abft=True)),
    "sstep": dict(family="sstep", storage=True, history=False,
                  capacity=None, precond_kind=None,
                  tunables={"sstep_s": 4},
                  contracts=dict(sharded_psum=1, sharded_halo=1, abft=True,
                                 storage_narrow=True)),
    "sstep-pallas": dict(family="sstep", storage=True, history=False,
                         capacity=None, precond_kind=None,
                         tunables={"sstep_s": 4},
                         contracts={}),
    # full multigrid as the SOLVER (mg.fmg): one O(N) F-cycle + the
    # verified mg-pcg handoff. precond_kind "mg" keys its traffic model
    # and guard fallback ladder on the V-cycle's; family "fmg" keeps it
    # out of the precond dispatch branch (it has its own builder).
    "fmg": dict(family="fmg", storage=False, history=True,
                capacity=None, precond_kind="mg",
                tunables={"levels": None, "nu": 2, "coarse_degree": 24,
                          "n_vcycles": 2},
                contracts=dict(sharded_psum=2, sharded_halo="precond",
                               fcycle_budget=True)),
}

# engines with a mesh-sharded form (a declared sharded collective
# cadence): the tuple obs.static_cost and the harness gate sharded-mode
# requests against — derived from the contract metadata, not
# hand-maintained alongside it.
SHARDED_ENGINES = tuple(
    e for e, c in ENGINE_CAPS.items()
    if c["contracts"].get("sharded_psum") is not None
)

ENGINES = ("auto",) + tuple(ENGINE_CAPS)

# the s-step (communication-avoiding) engines: s iterations per
# matrix-powers round, ONE stacked reduction (and, sharded, ONE psum +
# one s-deep halo) per s iterations — ops.sstep_pcg /
# parallel.sstep_sharded. "auto" never picks them (opt-in, like the
# preconditioner engines): their payoff is collective latency and HBM
# passes at mesh/bandwidth-bound scale, not small-grid wall clock.
SSTEP_ENGINES = tuple(
    e for e, c in ENGINE_CAPS.items() if c["family"] == "sstep"
)

# engines that accept the storage-vs-compute split (ops.precision):
# state and/or streamed operands at bf16 width in HBM, f32 compute.
# The loop engines narrow everything; streamed/xl narrow their operand
# streams (their state is VMEM-resident / kept full-width); batched
# narrows the lane fields. The guard's escalation ladder (bf16→f32→f64)
# is the product path for accuracy recovery (resilience.guard).
STORAGE_ENGINES = tuple(
    e for e, c in ENGINE_CAPS.items() if c["storage"]
)

# the preconditioner engines (mg.*): the classical fused loop with the
# diagonal preconditioner swapped for the multigrid V-cycle / Chebyshev
# polynomial — same PCGResult contract, O(grid)→O(1)-ish iteration
# counts. "auto" never picks them by default: auto optimises
# per-iteration cost at a FIXED iteration count; these change the
# iteration count itself and are opt-in per run/bench — unless the
# autotuner has a persisted, regression-gated winner for the shape
# (runtime.autotune; consulted below). The engine-name ↔ mg-kind
# mapping derives from the capability table — every consumer (harness,
# guard, static_cost, mg.engine) imports it from here, once.
PRECOND_KIND_BY_ENGINE = {
    e: c["precond_kind"] for e, c in ENGINE_CAPS.items()
    if c["family"] == "precond"
}
PRECOND_ENGINE_BY_KIND = {v: k for k, v in PRECOND_KIND_BY_ENGINE.items()}
PRECOND_ENGINES = tuple(PRECOND_KIND_BY_ENGINE)

# the lane-batched throughput engines (batch.*): one dispatch runs
# ``lanes`` independent solves; results are per-lane (BatchedPCGResult)
BATCHED_ENGINES = tuple(
    e for e, c in ENGINE_CAPS.items() if c["family"] == "batched"
)

# engines that can record on-device convergence history
# (``history=True`` → (PCGResult, obs.ConvergenceTrace)): the XLA-loop
# engines. The VMEM mega-kernels keep their scalars in kernel scratch,
# the batched engines carry per-lane recurrences — neither records.
# "auto" resolves to xla under history=True. The single source of truth
# for every history consumer (harness diagnose, obs.spectrum callers).
HISTORY_ENGINES = ("auto",) + tuple(
    e for e, c in ENGINE_CAPS.items() if c["history"]
)

# the runtime capacity ladder "auto" walks (and _warm_with_degradation
# degrades down on RESOURCE_EXHAUSTED): capability-table rungs in order
CAPACITY_LADDER = tuple(sorted(
    (e for e, c in ENGINE_CAPS.items() if c["capacity"] is not None),
    key=lambda e: ENGINE_CAPS[e]["capacity"],
))


def select_engine(problem: Problem, dtype=jnp.float32, device=None) -> str:
    """The concrete engine "auto" resolves to for this problem/dtype.

    The capacity gates scale with ``device``'s VMEM size
    (``utils.device``'s device_kind table; default: the default-backend
    device), so a larger-VMEM part keeps the resident/streamed engines
    up to proportionally larger grids instead of silently under-
    selecting with the bench part's budgets.
    """
    from poisson_ellipse_tpu.ops.resident_pcg import fits_resident
    from poisson_ellipse_tpu.ops.streamed_pcg import fits_streamed

    if jnp.dtype(dtype).itemsize >= 8:
        return "xla"
    if fits_resident(problem, dtype, device):
        return "resident"
    if fits_streamed(problem, dtype, device):
        return "streamed"
    # past the streamed gate the state itself exceeds VMEM: the xl
    # kernel streams state AND operands (12.1 passes/iter at ~72% of
    # HBM peak vs the XLA loop's 13 at ~67% — measured 4.28 s vs 5.16 s
    # at the 4096² north-star grid)
    return "xl"


def build_solver(
    problem: Problem, engine: str = "auto", dtype=jnp.float32, interpret=None,
    history: bool = False, lanes: int = 1, geometry=None, theta=None,
    validate_geometry: bool = True, storage_dtype=None, sstep_s: int = 4,
    tuned_knobs: dict | None = None,
):
    """(jitted solver, args, resolved_engine) for a single-chip solve.

    ``geometry`` selects an arbitrary SDF domain (a ``geom.sdf`` shape
    or its JSON spec): the operands are assembled through the bisection
    quadrature (``geom.quadrature``) with the degenerate-cut clamp at
    ``theta``, and — unless ``validate_geometry=False`` — the
    admissibility gate (``geom.validate``) runs FIRST, raising the
    classified ``InvalidGeometryError`` (exit 8) before anything is
    built or dispatched. ``geometry=None`` (default) keeps the
    closed-form ellipse bit-identical to every pre-geometry release.
    Every engine accepts the same ``geometry=``; the assembly is a
    host-side operand fact, not an engine property.

    ``lanes`` selects the batch width of the lane-batched engines
    (``batched`` / ``batched-pipelined``): their solver runs ``lanes``
    independent problems per dispatch — args end with a lane-stacked
    RHS — and returns a per-lane :class:`~poisson_ellipse_tpu.batch.
    BatchedPCGResult` instead of a ``PCGResult``. Every other engine
    requires ``lanes == 1``.

    All engines share the PCGResult contract and the f64-host-assembled,
    rounded-once operand fidelity, so swapping engines changes speed, not
    iteration counts (verified against the published oracles).

    ``history=True`` builds the solver in convergence-telemetry form: it
    returns ``(PCGResult, obs.ConvergenceTrace)`` with the per-iteration
    (zr, diff, α, β) series recorded on device (``obs.convergence``).
    Supported by the XLA-loop engines (xla, pallas, fused, pipelined,
    pipelined-pallas) — the VMEM mega-kernel engines (resident, streamed,
    xl) keep their scalars in kernel scratch, so "auto" with history
    resolves to xla (the reference-trajectory engine) and an explicit
    mega-kernel request fails loudly.

    ``tuned_knobs`` is the autotune registry's knob dict for this shape
    (``runtime.autotune``): the multigrid builders apply
    levels/ν/degrees/n_vcycles, the s-step branch reads sstep_s —
    passed explicitly by the tuner's measurement path and filled
    automatically when "auto" consults a persisted config, so the
    configuration that was scored is the configuration that runs.

    "auto" degrades only on memory: the capacity gates are budgets
    measured on the bench part, so on a TPU auto AOT-compiles the pick
    and, when the compiler or allocator reports memory exhaustion
    (``resilience.errors.is_oom_error``), falls down the chain (resident
    → streamed → xl → xla) with a ``RuntimeWarning``. Any other failure
    — a kernel Mosaic refuses — is raised: it is a bug, not a capacity
    fact. Explicitly requested engines always fail loudly.

    Traced as one ``engine.build`` span per call (``engine`` the name
    asked for); ``auto``'s probe is its ``engine.lower`` and
    ``engine.compile`` spans, the host assembly ``assembly.host``.
    """
    with obs_trace.span("engine.build", engine=engine):
        return build_solver_body(
            problem, engine, dtype, interpret, history, lanes, geometry,
            theta, validate_geometry, storage_dtype, sstep_s, tuned_knobs,
        )


def build_solver_body(problem, engine, dtype, interpret, history, lanes,
                      geometry, theta, validate_geometry, storage_dtype,
                      sstep_s, tuned_knobs):
    """``build_solver``'s body: ``auto`` builds each rung of its ladder
    through here, inside the one ``engine.build`` span of its call."""
    if lanes != 1 and engine not in BATCHED_ENGINES:
        raise ValueError(
            f"engine {engine!r} runs one solve per dispatch; lanes={lanes} "
            "needs the lane-batched engines ('batched' / "
            "'batched-pipelined')"
        )
    if storage_dtype is not None:
        from poisson_ellipse_tpu.ops.precision import resolve_storage_dtype

        # resolve early: a bad name or a widening request fails here,
        # and storage == compute normalises to None (the identity path)
        storage_dtype = resolve_storage_dtype(storage_dtype, dtype)
    if storage_dtype is not None and engine not in STORAGE_ENGINES:
        raise ValueError(
            f"engine {engine!r} has no storage-dtype form; choose from "
            f"{', '.join(STORAGE_ENGINES)} (or drop --storage-dtype)"
        )
    if geometry is not None:
        from poisson_ellipse_tpu.geom import sdf as geom_sdf
        from poisson_ellipse_tpu.geom import validate as geom_validate

        if isinstance(geometry, dict):
            geometry = geom_sdf.from_spec(geometry)  # classifies malformed
        if validate_geometry:
            # the admissibility gate: a bad problem fails HERE, with the
            # classified exit-8 error, before any build/compile/dispatch
            geom_validate.validate(problem, geometry, theta=theta)
    if engine in BATCHED_ENGINES:
        if history:
            raise ValueError(
                "the batched engines carry per-lane scalar recurrences, "
                "not the obs.convergence ring buffers; use a single-lane "
                "engine for history=True"
            )
        if lanes < 1:
            raise ValueError("lanes must be >= 1")
        import jax

        from poisson_ellipse_tpu.batch import (
            batched_operands,
            pcg_batched,
            pcg_batched_pipelined,
        )

        if engine == "batched":
            run = lambda a, b, rhs: pcg_batched(
                problem, a, b, rhs, storage_dtype=storage_dtype
            )
        else:
            run = lambda a, b, rhs: pcg_batched_pipelined(problem, a, b, rhs)
        args = batched_operands(problem, lanes, dtype, geometry=geometry,
                                theta=theta)
        # no donation: the build-once-call-many contract re-feeds these
        # operands on every dispatch (the timing protocols re-dispatch)
        solver = jax.jit(run)
        return solver, args, engine
    if engine == "auto":
        # the autotuner's persisted, regression-gated winner for this
        # shape (runtime.autotune) overrides the static capacity ladder
        # — only when the checkout's tuned registry exists and
        # holds this key; otherwise the historical ladder is untouched
        from poisson_ellipse_tpu.runtime import autotune

        tuned = autotune.lookup(problem, dtype, storage_dtype=storage_dtype,
                                geometry=geometry)
        if tuned is not None and tuned.engine in ENGINE_CAPS:
            caps = ENGINE_CAPS[tuned.engine]
            if ((not history or caps["history"])
                    and (storage_dtype is None or caps["storage"])
                    and caps["family"] not in ("batched",)):
                engine = tuned.engine
                # the FULL knob dict rides along: the multigrid/sstep
                # builders below apply it, so the tuned configuration
                # is what actually runs, not just the engine name
                tuned_knobs = dict(tuned.knobs)
                if "sstep_s" in tuned_knobs:
                    sstep_s = int(tuned_knobs["sstep_s"])
    if engine == "auto" and history:
        # the mega-kernel engines auto would pick cannot record: take the
        # reference-trajectory engine instead of failing a telemetry ask
        engine = "xla"
    if history and engine in ENGINES and engine not in HISTORY_ENGINES:
        raise ValueError(
            f"engine {engine!r} keeps its scalar recurrence in VMEM kernel "
            "scratch and cannot record history; use one of "
            f"{', '.join(HISTORY_ENGINES[1:])} (or engine='auto', which "
            "resolves to xla under history=True)"
        )
    if engine == "auto":
        import jax

        from poisson_ellipse_tpu.resilience.errors import is_oom_error

        chain = CAPACITY_LADDER
        chain = chain[chain.index(select_engine(problem, dtype)):]
        for cand in chain:
            try:
                # the gate already ran above — don't re-validate per rung
                solver, args, _ = build_solver_body(
                    problem, cand, dtype, interpret, history=False, lanes=1,
                    geometry=geometry, theta=theta, validate_geometry=False,
                    storage_dtype=None, sstep_s=4, tuned_knobs=None,
                )
                if cand != "xla" and jax.default_backend() == "tpu":
                    # force Mosaic compilation now, where we can catch it.
                    # The jit dispatch cache is shared with this AOT
                    # lowering, so the probe costs nothing extra.
                    with obs_trace.span("engine.lower", engine=cand):
                        lowered = solver.lower(*args)
                    with obs_trace.span("engine.compile", engine=cand):
                        lowered.compile()
                return solver, args, cand
            except Exception as e:  # noqa: BLE001 — OOM degrades, rest re-raised
                # only the allocator's verdict degrades: a kernel Mosaic
                # refuses is a bug, and must not read as a slower engine
                if not is_oom_error(e) or cand == chain[-1]:
                    raise
                import warnings

                warnings.warn(
                    f"engine {cand!r} failed to build/compile for "
                    f"{problem.M}x{problem.N} out of memory "
                    f"({type(e).__name__}: {e}); falling back",
                    RuntimeWarning,
                    stacklevel=3,
                )
    if engine == "resident":
        from poisson_ellipse_tpu.ops.resident_pcg import build_resident_solver

        solver, args = build_resident_solver(
            problem, dtype, interpret=interpret, geometry=geometry,
            theta=theta,
        )
    elif engine == "streamed":
        from poisson_ellipse_tpu.ops.streamed_pcg import build_streamed_solver

        solver, args = build_streamed_solver(
            problem, dtype, interpret=interpret, geometry=geometry,
            theta=theta, storage_dtype=storage_dtype,
        )
    elif engine == "fused":
        from poisson_ellipse_tpu.ops.fused_pcg import build_fused_solver

        solver, args = build_fused_solver(
            problem, dtype, interpret=interpret, history=history,
            geometry=geometry, theta=theta,
        )
    elif engine == "xl":
        from poisson_ellipse_tpu.ops.xl_pcg import build_xl_solver

        solver, args = build_xl_solver(
            problem, dtype, interpret=interpret, geometry=geometry,
            theta=theta, storage_dtype=storage_dtype,
        )
    elif engine == "fmg":
        # full multigrid as the solver: one O(N) F-cycle (nested
        # iteration over the coarsened hierarchy) + the verified
        # warm-started mg-pcg handoff against δ (mg.fmg); tuned knobs
        # (levels/ν/coarse_degree/n_vcycles) become the F-cycle config
        from poisson_ellipse_tpu.mg.fmg import (
            build_fmg_solver,
            config_from_knobs,
        )

        solver, args, _ = build_fmg_solver(
            problem, dtype, history=history, geometry=geometry,
            theta=theta, config=config_from_knobs(problem, tuned_knobs),
        )
    elif engine in PRECOND_ENGINES:
        # the multigrid / Chebyshev preconditioned classical loop: the
        # hierarchy + Lanczos bounds are resolved at build time, the
        # V-cycle/polynomial runs inside the fused while_loop
        # (mg.engine); tuned knobs override the probed config's cycle
        # shape (the interval stays the probe's)
        from poisson_ellipse_tpu.mg.engine import build_precond_solver

        solver, args, _ = build_precond_solver(
            problem, engine, dtype, history=history, geometry=geometry,
            theta=theta, overrides=tuned_knobs,
        )
    elif engine in ("pipelined", "pipelined-pallas"):
        from poisson_ellipse_tpu.ops.pipelined_pcg import pcg_pipelined

        import jax

        a, b, rhs = assembly.assemble(problem, dtype, geometry=geometry,
                                      theta=theta)
        stencil = "pallas" if engine == "pipelined-pallas" else "xla"
        # no donation: same build-once-call-many contract as the xla path
        solver = jax.jit(  # tpulint: disable=TPU004
            lambda a, b, rhs: pcg_pipelined(
                problem, a, b, rhs, stencil=stencil, interpret=interpret,
                history=history, storage_dtype=storage_dtype,
            )
        )
        args = (a, b, rhs)
    elif engine in SSTEP_ENGINES:
        from poisson_ellipse_tpu.ops.sstep_pcg import pcg_sstep

        import jax

        if history:
            raise ValueError(
                "the s-step engines advance in coordinate blocks and do "
                "not record the per-iteration obs.convergence buffers; "
                "use a HISTORY_ENGINES engine for history=True"
            )
        a, b, rhs = assembly.assemble(problem, dtype, geometry=geometry,
                                      theta=theta)
        stencil = "pallas" if engine == "sstep-pallas" else "xla"
        solver = jax.jit(  # tpulint: disable=TPU004
            lambda a, b, rhs: pcg_sstep(
                problem, a, b, rhs, s=sstep_s, stencil=stencil,
                interpret=interpret, storage_dtype=storage_dtype,
            )
        )
        args = (a, b, rhs)
    elif engine in ("xla", "pallas"):
        # "pallas" = the XLA while_loop driving the per-op Pallas stencil
        # kernel (stage4's one-kernel-per-op structure on one chip)
        import jax

        a, b, rhs = assembly.assemble(problem, dtype, geometry=geometry,
                                      theta=theta)
        stencil = engine
        # no donation: the build-once-call-many contract re-feeds these
        # operands on every dispatch (bench --repeat, chained solves)
        solver = jax.jit(  # tpulint: disable=TPU004
            lambda a, b, rhs: pcg(
                problem, a, b, rhs, stencil=stencil, history=history,
                storage_dtype=storage_dtype, interpret=interpret,
            )
        )
        args = (a, b, rhs)
    else:
        raise ValueError(f"unknown engine: {engine!r} (choose from {ENGINES})")
    return solver, args, engine


def solve(
    problem: Problem, engine: str = "auto", dtype=jnp.float32, interpret=None,
    history: bool = False, lanes: int = 1, geometry=None, theta=None,
    validate_geometry: bool = True, storage_dtype=None, sstep_s: int = 4,
):
    """Assemble and solve single-chip with the selected engine.

    ``history=True`` returns ``(PCGResult, obs.ConvergenceTrace)`` — the
    on-device per-iteration convergence telemetry (see ``build_solver``).
    ``lanes`` selects the batch width of the batched engines, whose
    result is per-lane (see ``build_solver``). ``geometry``/``theta``
    select an arbitrary SDF domain through the admissibility gate (see
    ``build_solver``; exit-8 classified rejection before dispatch).
    """
    solver, args, _ = build_solver(
        problem, engine, dtype, interpret=interpret, history=history,
        lanes=lanes, geometry=geometry, theta=theta,
        validate_geometry=validate_geometry, storage_dtype=storage_dtype,
        sstep_s=sstep_s,
    )
    return solver(*args)
