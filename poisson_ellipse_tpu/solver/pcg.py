"""Single-chip preconditioned conjugate gradients, fully on-device.

The reference's PCG drivers (sequential ``solve`` at
``stage0/Withoutopenmp1.cpp:106-172``; distributed ``gradient_solver_mpi`` at
``stage4-mpi+cuda/poisson_mpi_cuda2.cu:687-982``) keep the scalar recurrence
(α, β, convergence decision) on the **host**, costing the CUDA stage ≥3
device↔host round-trips per iteration (dot partials + diff partials) plus a
device sync after every kernel. Here the entire loop — stencil, dots, axpy
updates, preconditioner, stopping rule — is one ``lax.while_loop`` traced
into a single XLA computation: zero host↔device transfers per iteration,
which is exactly the north-star design of BASELINE.json.

Semantics preserved from the reference loop, in order
(``stage0/Withoutopenmp1.cpp:124-169``):
  1. Ap = A·p;  denom = (Ap, p);  breakdown-exit if denom < 1e-15
  2. α = zr/denom;  w += αp;  r −= αAp
  3. z = D⁻¹r;  zr_new = (z, r)
  4. diff = ‖w^{k+1} − w^k‖ (norm convention per Problem.norm);
     converged-exit if diff < δ
  5. β = zr_new/zr;  p = z + βp
The returned iteration count matches the reference's (count of loop bodies
entered, including the one that triggers the exit).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs.convergence import (
    history_init,
    history_record,
    trace_of,
)
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.precision import (
    load as _load,
    resolve_storage_dtype,
    store as _store,
)
from poisson_ellipse_tpu.ops.reduction import grid_dot, grid_dots
from poisson_ellipse_tpu.ops.stencil import apply_a, apply_dinv, diag_d

# PCG breakdown guard on the (Ap, p) denominator (stage0/Withoutopenmp1.cpp:128).
DENOM_GUARD = 1e-15


class PCGResult(NamedTuple):
    """Solver output: solution grid, iterations, final step-norm, exit flags."""

    w: jax.Array
    iters: jax.Array
    diff: jax.Array
    converged: jax.Array
    breakdown: jax.Array


def init_state(problem: Problem, a, b, rhs, history: bool = False,
               precond=None, storage_dtype=None, x0=None,
               recycle: int | None = None):
    """The PCG carry at iteration 0 (the resumable solver state).

    Layout: (k, w, r, p, zr, diff, converged, breakdown) — everything the
    loop needs to continue, so a saved state resumes bit-identically
    (solver.checkpoint builds on this). With ``history=True`` the four
    ``obs.convergence`` buffers ((cap,) each) ride appended to the core
    carry; the core layout is untouched.

    ``precond`` is the optional ``z = M⁻¹ r`` applier (a linear SPD
    operator — the multigrid V-cycle / Chebyshev appliers of ``mg``);
    None keeps the reference's diagonal preconditioner exactly.

    ``storage_dtype`` (``ops.precision``) stores the carry's vector
    fields (w, r, p) at that width — bf16 halves their HBM footprint —
    while the scalar recurrence (zr, diff) stays at compute width; None
    is byte-identical to the pre-storage-axis carry.

    ``x0`` warm-starts the recurrence: w = x0 with the TRUE residual
    r = rhs − A·x0 — the full-multigrid handoff (``mg.fmg``) seeds the
    loop with the F-cycle solution and the loop *verifies* it against δ
    instead of trusting it. ``x0=None`` is byte-identical to the
    historical zero start (r = rhs, no stencil application).

    ``recycle`` appends a (cap, M+1, N+1) Lanczos-vector ring
    (``solver.recycle``) as the LAST carry element — after the history
    buffers when both ride — holding ``recycle`` basis vectors at
    compute width, slot 0 seeded with v₁ here. ``recycle=None`` leaves
    the carry untouched (jaxpr-pinned).
    """
    dtype = rhs.dtype
    st = resolve_storage_dtype(storage_dtype, dtype)
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    d = diag_d(a, b, h1, h2)
    if x0 is None:
        w0, r0 = jnp.zeros_like(rhs), rhs
    else:
        w0, r0 = x0, rhs - apply_a(x0, a, b, h1, h2)
    z0 = apply_dinv(r0, d) if precond is None else precond(r0)
    zr0 = grid_dot(z0, r0, h1, h2)
    state = (
        jnp.asarray(0, jnp.int32),
        _store(w0, st),
        _store(r0, st),
        _store(z0, st),  # p0 = z0
        zr0,
        jnp.asarray(jnp.inf, dtype),
        jnp.asarray(False),
        jnp.asarray(False),
    )
    if history:
        state = state + history_init(problem.max_iterations, dtype)
    if recycle:
        from poisson_ellipse_tpu.solver.recycle import ring_init

        # slot 0 = v₁ = z₀/√(z₀,r₀), the first Lanczos basis vector of
        # M⁻¹A in the M-inner product (solver.recycle's capture contract)
        ring = ring_init(problem, int(recycle), dtype)
        ok = zr0 > 0
        v1 = z0 * lax.rsqrt(jnp.where(ok, zr0, 1.0))
        ring = ring.at[0].set(jnp.where(ok, v1, ring[0]))
        state = state + (ring,)
    return state


def advance(problem: Problem, a, b, rhs, state, limit=None, stencil: str = "xla",
            history: bool = False, precond=None, storage_dtype=None,
            recycle: int | None = None, interpret=None):
    """Advance the PCG carry until convergence/breakdown or iteration
    ``limit`` (defaults to max_iterations). Returns the new carry.

    Running in chunks (limit=k, k+K, …) is bit-identical to one straight
    run: chunking only moves the while_loop boundary, not the arithmetic.

    ``history=True`` expects/returns the extended carry of
    ``init_state(..., history=True)`` and scatters each iteration's
    (zr, diff, α, β) into the appended ``obs.convergence`` buffers —
    pure extra on-device stores, so the iterate trajectory is
    bit-identical to ``history=False`` (and with it off, the traced
    computation is exactly the historyless one: jaxpr-pinned).

    ``precond`` swaps the diagonal preconditioner for an arbitrary
    linear SPD ``z = M⁻¹ r`` applier (``mg``'s V-cycle / Chebyshev);
    None traces exactly the historical diagonal loop.

    ``storage_dtype`` runs the storage-vs-compute split of
    ``ops.precision``: the carry's vectors AND the streamed operands
    (a, b, D) live at storage width in HBM, every read upcasts to the
    compute dtype in the consumer (XLA fuses the convert — the HBM read
    stays storage-width), every store rounds back down. None traces the
    byte-identical full-width loop.

    ``recycle`` expects/returns the ring-extended carry of
    ``init_state(..., recycle=cap)`` and scatters each iteration's
    Lanczos basis vector (the scaled preconditioned residual) into the
    appended ring (``solver.recycle``'s Krylov-recycling capture) —
    pure extra on-device stores, the same DUS discipline as the history
    buffers, so the iterate trajectory is bit-identical either way;
    with it off the traced computation is exactly the ringless one
    (jaxpr-pinned).
    """
    dtype = rhs.dtype
    st = resolve_storage_dtype(storage_dtype, dtype)
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    delta = jnp.asarray(problem.delta, dtype)
    # the bound may be a traced scalar (checkpointed runs pass k+chunk per
    # dispatch without recompiling)
    max_iter = (
        problem.max_iterations
        if limit is None
        else jnp.minimum(
            jnp.asarray(limit, jnp.int32), problem.max_iterations
        )
    )
    weighted = problem.norm == "weighted"

    if st is not None and precond is not None:
        raise ValueError(
            "storage_dtype covers the diagonal-preconditioned loops; the "
            "mg/cheb appliers carry their own full-width level hierarchy "
            "— run them at compute width"
        )
    d = diag_d(a, b, h1, h2)
    if st is not None:
        # operands stream at storage width too (the byte cut covers every
        # HBM pass, not just the carry); rounded ONCE here, upcast inside
        # the body so the loads stay narrow
        a_s, b_s, d_s = _store(a, st), _store(b, st), _store(d, st)
    else:
        a_s, b_s, d_s = a, b, d

    if stencil == "pallas":
        if st is not None:
            from poisson_ellipse_tpu.ops.pallas_kernels import (
                apply_a_mixed_pallas,
            )

            # the explicit mixed kernel: storage-width tiles DMA'd to
            # VMEM, upcast there, f32 stencil arithmetic, compute-width out
            apply_stencil = lambda p: apply_a_mixed_pallas(
                p, a_s, b_s, problem.h1, problem.h2, compute_dtype=dtype,
                interpret=interpret,
            )
        else:
            from poisson_ellipse_tpu.ops.pallas_kernels import apply_a_pallas

            apply_stencil = lambda p: apply_a_pallas(
                p, a, b, problem.h1, problem.h2, interpret=interpret
            )
    elif stencil == "xla":
        apply_stencil = lambda p: apply_a(
            _load(p, dtype, st), _load(a_s, dtype, st),
            _load(b_s, dtype, st), h1, h2,
        )
    else:
        raise ValueError(f"unknown stencil: {stencil!r}")

    apply_precond = (
        (lambda r: apply_dinv(r, _load(d_s, dtype, st)))
        if precond is None else precond
    )

    def cond(state):
        k, converged, breakdown = state[0], state[6], state[7]
        return (k < max_iter) & ~converged & ~breakdown

    def body(state):
        k, w_s, r_s, p_s, zr, _diff, _c, _bd = state[:8]
        # tile-local upcast to compute width (fused into the consumers —
        # the HBM reads stay storage-width); identity when st is None
        w = _load(w_s, dtype, st)
        r = _load(r_s, dtype, st)
        p = _load(p_s, dtype, st)
        ap = apply_stencil(p_s)
        denom = grid_dot(ap, p, h1, h2)
        breakdown = denom < DENOM_GUARD
        alpha = zr / jnp.where(breakdown, 1.0, denom)

        w_new = w + alpha * p
        r_new = r - alpha * ap
        z = apply_precond(r_new)

        # ‖w^{k+1} − w^k‖ computed from the realised update (w_new − w), not
        # α·p, for bitwise parity with the reference's w/w_prev difference
        # (stage0/Withoutopenmp1.cpp:149-154; stage4 update_w_r_kernel
        # poisson_mpi_cuda2.cu:626-660). Both post-update sums ride one
        # fused reduction — the same one-reduction idiom the sharded loop
        # stacks into a single psum (values bit-identical to the separate
        # grid_dot/grid_sumsq calls).
        dw = w_new - w
        sums = grid_dots((z, r_new), (dw, dw))
        zr_new = sums[0] * h1 * h2
        dw2 = sums[1]
        diff = jnp.sqrt(dw2 * h1 * h2) if weighted else jnp.sqrt(dw2)
        # a breakdown iteration discards its update, so it cannot also claim
        # convergence; report the diff of the state actually retained
        converged = ~breakdown & (diff < delta)
        diff = jnp.where(breakdown, _diff, diff)

        beta = zr_new / zr
        p_new = z + beta * p

        # On breakdown the reference exits *before* touching w/r (stage0:128);
        # keep the pre-update iterates in that (rare, terminal) case.
        # Stores round back to storage width (identity when st is None).
        w_out = jnp.where(breakdown, w_s, _store(w_new, st))
        r_out = jnp.where(breakdown, r_s, _store(r_new, st))
        p_out = jnp.where(breakdown | converged, p_s, _store(p_new, st))
        zr_out = jnp.where(breakdown | converged, zr, zr_new)
        out = (k + 1, w_out, r_out, p_out, zr_out, diff, converged, breakdown)
        if history:
            # raw zr/β, carry-held diff, applied α (0 on a breakdown
            # iteration, whose update is discarded — every engine's trace
            # reports the same thing for the same event) —
            # obs.convergence's recording contract; pure stores, no
            # effect on the iterates
            out = out + history_record(
                state[8:12] if recycle else state[8:], k, zr_new, diff,
                jnp.where(breakdown, 0.0, alpha), beta,
            )
        if recycle:
            from poisson_ellipse_tpu.solver.recycle import ring_record

            # slot k+1 = v_{k+2} = (−1)^{k+1} z_{k+1}/√(z,r)_{k+1}: the
            # next Lanczos basis vector, from arrays this body already
            # materialises — the host-side harvest pairs the ring with
            # the trace's tridiagonal to form approximate Ritz vectors;
            # pure stores, no effect on the iterates
            zr_ok = zr_new > 0
            sign = jnp.where(k % 2 == 0, -1.0, 1.0).astype(dtype)
            v_next = sign * z * lax.rsqrt(jnp.where(zr_ok, zr_new, 1.0))
            out = out + (
                ring_record(state[-1], k + 1, v_next, ~breakdown & zr_ok),
            )
        return out

    return lax.while_loop(cond, body, state)


def result_of(state) -> PCGResult:
    """View a PCG carry (core or history-extended) as a PCGResult."""
    k, w = state[0], state[1]
    diff, converged, breakdown = state[5], state[6], state[7]
    return PCGResult(
        w=w, iters=k, diff=diff, converged=converged, breakdown=breakdown
    )


def pcg(problem: Problem, a, b, rhs, stencil: str = "xla",
        history: bool = False, precond=None, storage_dtype=None,
        x0=None, recycle: int | None = None, interpret=None):
    """Run PCG for pre-assembled coefficients. All inputs (M+1, N+1).

    Jit-safe with ``problem`` static; the while_loop carries
    (k, w, r, p, zr, diff, converged, breakdown) entirely on device.

    stencil: "xla" (padded-slice arithmetic, XLA-fused) or "pallas" (the
    explicit VMEM-tiled kernel, ``ops.pallas_kernels.apply_a_pallas``;
    ``interpret`` picks its interpret mode, None = interpret off a TPU).
    The two agree to 1-2 ulps — not bitwise — so iteration counts may
    differ by a step on ill-conditioned grids.

    history=True returns ``(PCGResult, obs.ConvergenceTrace)`` — the
    per-iteration (zr, diff, α, β) series captured on device with zero
    extra host syncs; the iterates are bit-identical either way.

    precond: optional ``z = M⁻¹ r`` applier replacing the diagonal
    preconditioner (see ``advance``; ``mg`` builds the V-cycle and
    Chebyshev appliers this hook exists for).

    storage_dtype: the HBM storage width of the carry vectors and
    streamed operands (``ops.precision``; "bf16" halves the loop's HBM
    bytes, compute stays at ``rhs.dtype``). None = storage == compute,
    byte-identical to the historical loop. The product path for bf16 is
    the guard (``resilience.guard``), whose ladder recovers full-width
    accuracy; the raw engine converges to the storage dtype's floor.

    x0: optional warm start, verified by the TRUE residual at init (see
    ``init_state``) — a wrong x0 costs iterations, never correctness.
    None is byte-identical to the zero start.

    recycle: capacity of the on-device search-direction ring
    (``solver.recycle``). Requires ``history=True`` (the harvest pairs
    the stored directions with the trace's Lanczos coefficients);
    returns ``(PCGResult, ConvergenceTrace, ring)``. None traces
    exactly the ringless computation (jaxpr-pinned).
    """
    if recycle and not history:
        raise ValueError(
            "recycle requires history=True: the Ritz harvest pairs the "
            "direction ring with the trace's Lanczos coefficients"
        )
    state = advance(
        problem, a, b, rhs,
        init_state(problem, a, b, rhs, history=history, precond=precond,
                   storage_dtype=storage_dtype, x0=x0, recycle=recycle),
        stencil=stencil, history=history, precond=precond,
        storage_dtype=storage_dtype, recycle=recycle, interpret=interpret,
    )
    result = result_of(state)
    if recycle:
        return result, trace_of(state[8:12], result.iters), state[-1]
    if history:
        return result, trace_of(state[8:], result.iters)
    return result


def solve(problem: Problem, dtype=jnp.float32, stencil: str = "xla",
          history: bool = False, storage_dtype=None):
    """Assemble and solve on a single chip (the stage0-shaped entry point)."""
    a, b, rhs = assembly.assemble(problem, dtype)
    return pcg(problem, a, b, rhs, stencil=stencil, history=history,
               storage_dtype=storage_dtype)
