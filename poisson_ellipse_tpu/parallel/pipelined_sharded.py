"""Pipelined sharded PCG: ONE stacked psum collective per iteration.

The plain sharded loop (``parallel.pcg_sharded``) pays 2 ``lax.psum``
latencies per iteration, and both sit on the critical path: denom must
arrive before the axpy updates, whose results feed the second collective.
On the north-star configuration (large grids over many chips/hosts) that
reduce→broadcast latency IS the iteration floor — the stencil arithmetic
is local and fast, the collectives are not.

This module composes the pipelined recurrence (``ops.pipelined_pcg``)
with the mesh: every inner product an iteration needs is computed from
vectors already in hand, stacked into one (8,) partials vector, and
issued as a SINGLE ``lax.psum``. Crucially the iteration's halo exchange
(4 ``lax.ppermute``) and stencil application consume none of that psum's
results, so XLA's scheduler overlaps the collective with the
neighbour-exchange + stencil compute — the same collective-fusion/overlap
shape that hides all-reduce latency in distributed training stacks.

Per iteration, per shard:

  1 stacked psum             all 8 dot partials, one collective
  1 halo exchange            m = M⁻¹w in 4 ppermutes   } independent of
  1 stencil                  n = A m                   } the psum: overlap
  scalar tail                β, α, breakdown/convergence
  7 fused axpy updates       z s p x r u w

versus 2 psums + 1 halo exchange for the classical sharded loop — half
the collectives, and the remaining one hidden behind compute. Residual
replacement (``ops.pipelined_pcg.REPLACE_EVERY``) runs on the same fixed
cadence with two stacked halo exchanges; it is outside the steady-state
iteration and adds no collectives.

Accuracy contract is the pipelined engine's (reordering, not bitwise):
iteration counts within ±2 of the sharded ``xla`` path on the oracle
grids, asserted in ``tests/test_pipelined.py`` — which also pins "exactly
one psum in the loop body" structurally, from the jaxpr.

``build_pipelined_sharded_stepper`` is the chunked/resumable form of the
same iteration (the ``build_sharded_stepper`` contract), which is what
lets ``resilience.guard`` chunk, health-check and roll back pipelined
mesh solves. With ``abft=True`` it runs the in-loop SDC checks of
``resilience.abft`` adapted to this recurrence's collective schedule:
the single psum fires BEFORE the axpy updates, so the residual-sum
recurrence check is *lagged one iteration* — iteration k+1's directly
reduced Σr is compared against the prediction
``Σr − α·(Σw + β·Σs)`` carried from iteration k — plus the γ-positivity
invariant (γ = ⟨r, M⁻¹r⟩ > 0 until convergence, the check that catches a
sign-flipped all-reduce). All extra partials ride the SAME stacked psum:
still exactly one collective per iteration, jaxpr-pinned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.pipelined_pcg import REPLACE_EVERY, _bundle
from poisson_ellipse_tpu.ops.stencil import apply_a_block, apply_dinv, diag_d_block
from poisson_ellipse_tpu.parallel.halo import halo_extend, halo_extend_stacked
from poisson_ellipse_tpu.parallel.mesh import (
    AXIS_X,
    AXIS_Y,
    make_mesh,
    padded_dims,
    pcast_varying,
)
from poisson_ellipse_tpu.parallel.pcg_sharded import _host_sharded_args
from poisson_ellipse_tpu.solver.pcg import DENOM_GUARD, PCGResult

MESH_AXES = (AXIS_X, AXIS_Y)

# indices of the ABFT tail appended to the pipelined sharded carry:
# (…, pred_r, scale_r, pred_p, scale_p, sdc) — the lagged checks of the
# module docstring (r-chain skips replacement iterations; the p-chain
# holds across them, since replacement treats p as ground truth)
PIPE_PRED, PIPE_SCALE, PIPE_PRED_P, PIPE_SCALE_P, PIPE_SDC = (
    12, 13, 14, 15, 16
)


def _pipelined_parts(problem: Problem, px: int, py: int, bm: int, bn: int,
                     a_blk, b_blk, rhs_blk, dtype, abft: bool = False):
    """(state0, body, cond_of) for one shard of the pipelined iteration
    — the single source both the whole-solve form and the chunked
    stepper trace, so they cannot drift. ``cond_of(limit)`` builds the
    loop condition against a (traced or static) iteration bound."""
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    hw = h1 * h2
    delta_tol = jnp.asarray(problem.delta, dtype)
    weighted = problem.norm == "weighted"

    ix = lax.axis_index(AXIS_X)
    iy = lax.axis_index(AXIS_Y)
    gi = ix * bm + jnp.arange(bm, dtype=jnp.int32)
    gj = iy * bn + jnp.arange(bn, dtype=jnp.int32)
    interior = assembly.interior_mask(problem, gi, gj)

    # one-time coefficient halo exchange (loop invariant)
    a_ext = halo_extend(a_blk, px, py)
    b_ext = halo_extend(b_blk, px, py)
    d = jnp.where(interior, diag_d_block(a_ext, b_ext, h1, h2), 0.0)
    maskd = interior.astype(dtype)

    def stencil(v_ext):
        return apply_a_block(v_ext, a_ext, b_ext, h1, h2) * maskd

    def stencil_of(v):
        return stencil(halo_extend(v, px, py))

    def replace(k, x, r, u, w, z, s, p):
        """Residual replacement from ground-truth x and p: two
        stacked halo exchanges + four stencils, same cadence as the
        single-chip engine (no collectives — psum count per
        iteration stays at one)."""

        def rebuilt(_):
            xp_ext = halo_extend_stacked(jnp.stack([x, p]), px, py)
            r_t = rhs_blk - stencil(xp_ext[0])
            s_t = stencil(xp_ext[1])
            u_t = apply_dinv(r_t, d)
            q_t = apply_dinv(s_t, d)
            uq_ext = halo_extend_stacked(jnp.stack([u_t, q_t]), px, py)
            return (
                r_t, u_t, stencil(uq_ext[0]), stencil(uq_ext[1]), s_t
            )

        do = (k > 0) & (k % REPLACE_EVERY == 0)
        return lax.cond(do, rebuilt, lambda _: (r, u, w, z, s), None)

    r0 = rhs_blk
    u0 = apply_dinv(r0, d)
    w0 = stencil_of(u0)
    zeros = lambda: pcast_varying(jnp.zeros((bm, bn), dtype), MESH_AXES)
    state0 = (
        jnp.asarray(0, jnp.int32),
        zeros(),  # x
        r0, u0, w0,
        zeros(), zeros(), zeros(),  # z, s, p
        jnp.asarray(1.0, dtype),    # γ of the previous iteration
        jnp.asarray(jnp.inf, dtype),
        jnp.asarray(False),
        jnp.asarray(False),
    )
    if abft:
        state0 = state0 + (
            jnp.asarray(0.0, dtype),  # pred_r (checked from k=1 on)
            jnp.asarray(0.0, dtype),  # its drift scale
            jnp.asarray(0.0, dtype),  # pred_p
            jnp.asarray(0.0, dtype),  # its drift scale
            jnp.asarray(False),       # sdc
        )

    def cond_of(limit):
        def cond(state):
            k = state[0]
            converged, breakdown = state[10], state[11]
            go = (k < limit) & ~converged & ~breakdown
            if abft:
                # a flagged carry stops at once — the guard rolls the
                # chunk back; further iterations only amplify the flip
                go = go & ~state[PIPE_SDC]
            return go

        return cond

    def body(state):
        k, x, r, u, w, z, s, p, g_prev, diff_prev, _c, _bd = state[:12]
        r, u, w, z, s = replace(k, x, r, u, w, z, s, p)

        # THE one collective of the iteration: all partials in a
        # single stacked psum …
        partials = [jnp.sum(a_ * b_) for a_, b_ in _bundle(r, u, w, s, p)]
        if abft:
            # the ABFT partials ride the same psum — plain/abs sums of
            # vectors the bundle above already reads
            partials += [
                jnp.sum(r), jnp.sum(jnp.abs(r)),
                jnp.sum(w), jnp.sum(jnp.abs(w)),
                jnp.sum(s), jnp.sum(jnp.abs(s)),
                jnp.sum(p), jnp.sum(jnp.abs(p)),
                jnp.sum(u), jnp.sum(jnp.abs(u)),
            ]
        sums = lax.psum(jnp.stack(partials), MESH_AXES)
        # … which this halo exchange + stencil do NOT consume: XLA
        # overlaps the collective with the neighbour exchange and
        # the stencil compute
        m = apply_dinv(w, d)
        n = stencil_of(m)

        gamma = sums[0] * hw
        wu, wp, su, sp = sums[1], sums[2], sums[3], sums[4]
        uu, up, pp = sums[5], sums[6], sums[7]
        first = k == 0
        beta = jnp.where(
            first, 0.0, gamma / jnp.where(first, 1.0, g_prev)
        )
        denom = (wu + beta * (wp + su) + beta * beta * sp) * hw
        breakdown = denom < DENOM_GUARD
        alpha = gamma / jnp.where(breakdown, 1.0, denom)

        z_new = n + beta * z
        s_new = w + beta * s
        p_new = u + beta * p
        x_new = x + alpha * p_new
        r_new = r - alpha * s_new
        u_new = u - alpha * apply_dinv(s_new, d)
        w_new = w - alpha * z_new

        pp_new = uu + 2.0 * beta * up + beta * beta * pp
        dw2 = alpha * alpha * pp_new
        diff = jnp.sqrt(dw2 * hw) if weighted else jnp.sqrt(dw2)
        converged = ~breakdown & (diff < delta_tol)
        diff = jnp.where(breakdown, diff_prev, diff)

        keep = lambda old, new: jnp.where(breakdown, old, new)
        out = (
            k + 1,
            keep(x, x_new), keep(r, r_new), keep(u, u_new),
            keep(w, w_new), keep(z, z_new), keep(s, s_new),
            keep(p, p_new), keep(g_prev, gamma),
            diff, converged, breakdown,
        )
        if abft:
            from poisson_ellipse_tpu.resilience.abft import (
                ABFT_TINY,
                abft_rtol,
            )

            pred_r, scale_r, pred_p, scale_p, sdc = (
                state[PIPE_PRED:PIPE_SDC + 1]
            )
            s_r, s_absr = sums[8], sums[9]
            s_w, s_absw = sums[10], sums[11]
            s_s, s_abss = sums[12], sums[13]
            s_p, s_absp = sums[14], sums[15]
            s_u, s_absu = sums[16], sums[17]
            rtol = abft_rtol(dtype)
            # replacement legitimately rebuilds r away from the carried
            # prediction — skip the lagged r-check on those iterations
            # (the p-chain holds: replacement treats p as ground truth)
            replaced = (k > 0) & (k % REPLACE_EVERY == 0)
            ok_r = replaced | (
                jnp.abs(s_r - pred_r) <= rtol * (scale_r + ABFT_TINY)
            )
            ok_p = jnp.abs(s_p - pred_p) <= rtol * (scale_p + ABFT_TINY)
            ok_g = g_prev > 0  # γ is an energy product until convergence
            fault = (k > 0) & ~(ok_r & ok_p & ok_g)
            # next iteration's incoming r is r − α(w + βs) and incoming
            # p is u + βp: predict their sums (and the round-off scale
            # of each prediction) now
            pred_r_next = s_r - alpha * (s_w + beta * s_s)
            scale_r_next = s_absr + jnp.abs(alpha) * (
                s_absw + jnp.abs(beta) * s_abss
            )
            pred_p_next = s_u + beta * s_p
            scale_p_next = s_absu + jnp.abs(beta) * s_absp
            out = out + (
                keep(pred_r, pred_r_next),
                keep(scale_r, scale_r_next),
                keep(pred_p, pred_p_next),
                keep(scale_p, scale_p_next),
                sdc | fault,
            )
        return out

    return state0, body, cond_of


def build_pipelined_sharded_solver(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    geometry=None,
    theta=None,
):
    """(jitted solver, args) for the pipelined mesh-sharded solve.

    Operands are host-assembled in f64 and rounded once (the fidelity
    contract every engine shares); args = the three (g1p, g2p) arrays
    laid out P('x', 'y') over the mesh, so ``solver(*args)`` slots into
    the same harness/bench protocol as ``build_sharded_solver``.
    """
    if mesh is None:
        mesh = make_mesh()
    px = mesh.shape[AXIS_X]
    py = mesh.shape[AXIS_Y]
    g1p, g2p = padded_dims(problem.node_shape, mesh)
    bm, bn = g1p // px, g2p // py
    spec = P(AXIS_X, AXIS_Y)
    max_iter = problem.max_iterations

    def shard_fn(a_blk, b_blk, rhs_blk):
        state0, body, cond_of = _pipelined_parts(
            problem, px, py, bm, bn, a_blk, b_blk, rhs_blk, dtype
        )
        out = lax.while_loop(cond_of(max_iter), body, state0)
        k, x = out[0], out[1]
        diff, converged, breakdown = out[9], out[10], out[11]
        return x, k, diff, converged, breakdown

    mapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, P(), P(), P(), P()),
    )

    args = _host_sharded_args(problem, mesh, dtype, g1p, g2p, spec,
                              geometry=geometry, theta=theta)

    def solver(*arrays):
        x_pad, k, diff, converged, breakdown = mapped(*arrays)
        return PCGResult(
            w=x_pad[: problem.M + 1, : problem.N + 1],
            iters=k,
            diff=diff,
            converged=converged,
            breakdown=breakdown,
        )

    # no donation: the build-once-call-many contract re-feeds these
    # operands on every dispatch (bench --repeat, chained solves)
    return jax.jit(solver), args


def build_pipelined_sharded_stepper(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    abft: bool = False,
):
    """(init_fn, advance_fn) for chunked/resumable pipelined mesh solves
    — the ``build_sharded_stepper`` contract over the 12-field pipelined
    carry (x/r/u/w/z/s/p blocks sharded P('x','y'), γ/diff/flags
    replicated). Chunking only moves the while_loop boundary; the
    recurrence — including the fixed-cadence residual replacement, keyed
    on the carried absolute k — is untouched, so a chunked run converges
    in the same count as the straight solve. With ``abft`` the carry
    gains the three lagged-check scalars (module docstring) and the sdc
    flag rides out to the guard's chunk-boundary health read.
    """
    if mesh is None:
        mesh = make_mesh()
    px = mesh.shape[AXIS_X]
    py = mesh.shape[AXIS_Y]
    g1p, g2p = padded_dims(problem.node_shape, mesh)
    bm, bn = g1p // px, g2p // py
    spec = P(AXIS_X, AXIS_Y)
    scalar = P()
    state_specs = (
        (scalar,) + (spec,) * 7 + (scalar, scalar, scalar, scalar)
    )
    if abft:
        state_specs = state_specs + (scalar,) * 5
    max_iter = problem.max_iterations

    def init_shard(a_blk, b_blk, rhs_blk):
        state0, _body, _cond_of = _pipelined_parts(
            problem, px, py, bm, bn, a_blk, b_blk, rhs_blk, dtype,
            abft=abft,
        )
        return state0

    def advance_shard(a_blk, b_blk, rhs_blk, state, limit):
        _state0, body, cond_of = _pipelined_parts(
            problem, px, py, bm, bn, a_blk, b_blk, rhs_blk, dtype,
            abft=abft,
        )
        bound = jnp.minimum(jnp.asarray(limit, jnp.int32), max_iter)
        return lax.while_loop(cond_of(bound), body, state)

    # no donation on either half: operands are re-fed every chunk and
    # the carry doubles as the guard's rollback point
    init_mapped = jax.jit(shard_map(  # tpulint: disable=TPU004
        init_shard,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=state_specs,
    ))
    advance_mapped = jax.jit(shard_map(  # tpulint: disable=TPU004
        advance_shard,
        mesh=mesh,
        in_specs=(spec, spec, spec, state_specs, scalar),
        out_specs=state_specs,
    ))

    args = _host_sharded_args(problem, mesh, dtype, g1p, g2p, spec)

    def init_fn():
        return init_mapped(*args)

    def advance_fn(state, limit):
        return advance_mapped(
            args[0], args[1], args[2], state,
            jnp.asarray(limit, jnp.int32),
        )

    return init_fn, advance_fn


def pipelined_sharded_result_of(problem: Problem, state) -> PCGResult:
    """View a pipelined sharded carry as a PCGResult (crops padding; the
    ABFT tail, when present, is ignored)."""
    k, x = state[0], state[1]
    diff, converged, breakdown = state[9], state[10], state[11]
    return PCGResult(
        w=x[: problem.M + 1, : problem.N + 1],
        iters=k,
        diff=diff,
        converged=converged,
        breakdown=breakdown,
    )


def solve_pipelined_sharded(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
) -> PCGResult:
    """Assemble, shard and solve with the pipelined one-psum iteration."""
    solver, args = build_pipelined_sharded_solver(problem, mesh, dtype)
    return solver(*args)
