"""Distributed PCG: the whole solve as one shard_map-ped on-device program.

TPU-native redesign of the reference's distributed drivers (``solve_mpi``,
``stage2-mpi/poisson_mpi_decomp.cpp:356-460``; ``gradient_solver_mpi``,
``stage4-mpi+cuda/poisson_mpi_cuda2.cu:687-982``). Structural comparison,
per PCG iteration:

  reference stage4 (per iteration)          here (per iteration)
  ---------------------------------------   ---------------------------------
  4× (D2H memcpy → MPI_Sendrecv → H2D)      1 halo_extend = 4 lax.ppermute
  3× (dot kernel → D2H 256KiB partials      2 lax.psum collectives (denom;
      → host sum → MPI_Allreduce)              [zr, ‖Δw‖²] batched as one)
  α/β/convergence on host                   α/β/convergence on device in
  6 kernel launches + 6 device syncs          lax.while_loop — zero host
                                              round-trips, zero syncs

The decomposition itself (``choose_process_grid`` + ``decompose_2d``)
becomes a ``Mesh`` + zero-padding to even shards (see ``parallel.mesh``);
per-rank local assembly with a halo ring (``fictitious_regions_setup_local``,
``poisson_mpi_cuda2.cu:146-192``) is available as ``assembly_mode="device"``
— each device assembles its own halo-extended coefficient block from global
indices with no communication at all, exactly the reference's contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs.convergence import (
    history_init,
    history_record,
    trace_of,
)
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.stencil import apply_a_block, apply_dinv, diag_d_block
from poisson_ellipse_tpu.parallel.halo import halo_extend
from poisson_ellipse_tpu.parallel.mesh import (
    AXIS_X,
    AXIS_Y,
    make_mesh,
    padded_dims,
    pcast_varying,
)
from poisson_ellipse_tpu.solver.pcg import DENOM_GUARD, PCGResult


def _shard_ops(problem: Problem, px: int, py: int, bm: int, bn: int,
               a_ext, b_ext, dtype, stencil_impl: str = "xla",
               interpret: bool = False):
    """(stencil, pdot, d, maskd) closures for one shard — shared by the
    whole-solve and chunked-advance paths. ``maskd`` is the shard's
    interior mask in ``dtype`` (the ABFT checksum field is one stencil
    application over it).

    stencil_impl "pallas" runs the explicit VMEM-tiled stencil kernel
    (``ops.pallas_kernels.apply_a_block_pallas``) on each shard every
    iteration — the reference stage4's structure exactly: a device kernel
    per rank in the hot loop, ringed by halo exchange and scalar
    collectives (``apply_A_kernel`` inside ``gradient_solver_mpi``,
    ``poisson_mpi_cuda2.cu:507-536``, ``:846-939``). "xla" leaves the
    stencil to XLA's fusion (the default; same math, same FP form)."""
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)

    ix = lax.axis_index(AXIS_X)
    iy = lax.axis_index(AXIS_Y)
    gi = ix * bm + jnp.arange(bm, dtype=jnp.int32)
    gj = iy * bn + jnp.arange(bn, dtype=jnp.int32)
    interior = assembly.interior_mask(problem, gi, gj)

    # Diagonal, zeroed outside the global interior so apply_dinv's guard
    # keeps every iterate exactly zero there (boundary ring + shard padding).
    d = jnp.where(interior, diag_d_block(a_ext, b_ext, h1, h2), 0.0)
    maskd = interior.astype(dtype)

    if stencil_impl == "pallas":
        from poisson_ellipse_tpu.ops.pallas_kernels import apply_a_block_pallas

        def stencil(p):
            p_ext = halo_extend(p, px, py)
            # grid spacings as python floats: the kernel bakes them in as
            # compile-time constants (they never reach SMEM)
            return (
                apply_a_block_pallas(
                    p_ext, a_ext, b_ext, problem.h1, problem.h2,
                    interpret=interpret,
                    vma=frozenset((AXIS_X, AXIS_Y)),
                )
                * maskd
            )

    elif stencil_impl == "xla":

        def stencil(p):
            p_ext = halo_extend(p, px, py)
            return apply_a_block(p_ext, a_ext, b_ext, h1, h2) * maskd

    else:
        raise ValueError(f"unknown stencil_impl: {stencil_impl!r}")

    def pdot(u, v):
        return lax.psum(jnp.sum(u * v), (AXIS_X, AXIS_Y)) * h1 * h2

    return stencil, pdot, d, maskd


def _shard_init(problem: Problem, px: int, py: int, bm: int, bn: int,
                pdot, d, rhs_blk, dtype, history: bool = False,
                precond=None, abft: bool = False, x0_blk=None,
                stencil=None):
    """The full PCG carry at iteration 0 on one shard — layout matches
    ``solver.pcg.init_state`` (k, w, r, p, zr, diff, converged,
    breakdown), with w/r/p as per-shard blocks and replicated scalars.
    ``history=True`` appends the four ``obs.convergence`` buffers —
    scattered from psum-reduced scalars, so they stay replicated too.
    ``precond`` swaps the diagonal preconditioner for a per-shard
    ``z = M⁻¹ r`` applier (``parallel.mg_sharded``'s V-cycle/Chebyshev
    closures — halo ppermutes only, no scalar collectives).
    ``abft=True`` appends the four ABFT shadow scalars
    (S_r, S_w, S_p_pred, sdc — ``resilience.abft``), anchored by one
    stacked psum at iteration 0 (one-time, off the per-iteration path).
    ``x0_blk`` warm-starts the carry (w = x0 with the TRUE per-shard
    residual r = rhs − A·x0 via ``stencil`` — the full-multigrid
    handoff's verified seed, ``parallel.mg_sharded``'s F-cycle)."""
    if x0_blk is None:
        # the zeros literal is device-invariant; mark it varying over the
        # mesh so the while_loop carry type matches the per-device updates
        w0 = pcast_varying(jnp.zeros((bm, bn), dtype), (AXIS_X, AXIS_Y))
        r0 = rhs_blk
    else:
        if stencil is None:
            raise ValueError("x0_blk warm start needs the shard stencil")
        w0 = x0_blk
        r0 = rhs_blk - stencil(x0_blk)
    z0 = apply_dinv(r0, d) if precond is None else precond(r0)
    p0 = z0
    zr0 = pdot(z0, r0)
    state = (
        jnp.asarray(0, jnp.int32),
        w0,
        r0,
        p0,
        zr0,
        jnp.asarray(jnp.inf, dtype),
        jnp.asarray(False),
        jnp.asarray(False),
    )
    if history and abft:
        raise ValueError("history capture and ABFT extend the same carry "
                         "tail; request one or the other")
    if history:
        state = state + history_init(problem.max_iterations, dtype)
    if abft:
        sums = lax.psum(
            jnp.stack([jnp.sum(r0), jnp.sum(p0)]), (AXIS_X, AXIS_Y)
        )
        state = state + (
            sums[0], jnp.asarray(0.0, dtype), sums[1], jnp.asarray(False)
        )
    return state


def _shard_advance(problem: Problem, stencil, pdot, d, state, dtype,
                   limit=None, history: bool = False, precond=None,
                   abft: bool = False, abft_c=None):
    """Advance the sharded PCG carry until convergence/breakdown or
    iteration ``limit`` (defaults to max_iterations). Chunking only moves
    the while_loop boundary, not the arithmetic — same contract as
    ``solver.pcg.advance`` (including the history contract: recording is
    pure extra stores of already-psum-reduced scalars — no additional
    collectives, no host traffic).

    ``precond`` replaces the diagonal preconditioner with a per-shard
    ``z = M⁻¹ r`` applier; the scalar-collective cadence is untouched —
    the convergence word stays the ONE stacked psum below, the denom
    psum stays the other, and any preconditioner communication is halo
    ppermutes inside ``precond`` itself (jaxpr-pinned in
    ``tests/test_mg.py``).

    ``abft=True`` runs the in-loop SDC checks of ``resilience.abft``
    over the 4-scalar-extended carry, with ``abft_c`` the per-shard
    checksum field ``A·1`` (built OUTSIDE the loop —
    ``abft.checksum_field``). Every checksum partial is stacked into the
    SAME convergence psum, so the collective cadence is byte-identical
    to the plain loop: 1 denom psum + 1 stacked psum per iteration,
    pinned from the jaxpr in ``tests/test_elastic.py``."""
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    delta = jnp.asarray(problem.delta, dtype)
    weighted = problem.norm == "weighted"
    max_iter = (
        problem.max_iterations
        if limit is None
        else jnp.minimum(
            jnp.asarray(limit, jnp.int32), problem.max_iterations
        )
    )

    def cond(state):
        k, converged, breakdown = state[0], state[6], state[7]
        go = (k < max_iter) & ~converged & ~breakdown
        if abft:
            # a flagged carry stops the loop at once: every further
            # iteration would compute on (and amplify) the corruption,
            # and the guard is going to roll the whole chunk back anyway
            go = go & ~state[_SDC]
        return go

    if abft and (history or abft_c is None):
        raise ValueError(
            "abft needs the checksum field (abft_c) and excludes history "
            "capture — both extend the carry tail"
        )
    if abft:
        # the shadow-tail layout lives with resilience.abft; every
        # consumer (this loop, the guard's adapter, the meshguard)
        # addresses it through the same constants
        from poisson_ellipse_tpu.resilience.abft import (
            SDC as _SDC,
            SP_PRED as _SP,
            SR as _SR,
            SW as _SW,
        )

    def body(state):
        k, w, r, p, zr, _diff, _c, _bd = state[:8]
        ap = stencil(p)
        denom = pdot(ap, p)
        breakdown = denom < DENOM_GUARD
        alpha = zr / jnp.where(breakdown, 1.0, denom)

        w_new = w + alpha * p
        r_new = r - alpha * ap
        z = apply_dinv(r_new, d) if precond is None else precond(r_new)

        # one collective for both scalars (vs 2 of the reference's 3
        # Allreduces; the denominator one above is inherently sequential)
        dw = w_new - w
        if abft:
            # the ABFT partials ride the SAME stacked psum — every term
            # is a reduction over an array this body already produces or
            # reads (ap, r⁺, w⁺, p, z; c is the loop-invariant checksum
            # field), fused by XLA into the passes that materialize them
            partials = jnp.stack([
                jnp.sum(z * r_new), jnp.sum(dw * dw),
                jnp.sum(ap), jnp.sum(abft_c * p), jnp.sum(jnp.abs(ap)),
                jnp.sum(r_new), jnp.sum(jnp.abs(r_new)),
                jnp.sum(w_new), jnp.sum(jnp.abs(w_new)),
                jnp.sum(p), jnp.sum(jnp.abs(p)),
                jnp.sum(z),
            ])
            sums = lax.psum(partials, (AXIS_X, AXIS_Y))
            zr_sum, dw2 = sums[0], sums[1]
        else:
            partial_sums = jnp.stack([jnp.sum(z * r_new), jnp.sum(dw * dw)])
            zr_sum, dw2 = lax.psum(partial_sums, (AXIS_X, AXIS_Y))
        zr_new = zr_sum * h1 * h2
        diff = jnp.sqrt(dw2 * h1 * h2) if weighted else jnp.sqrt(dw2)
        converged = ~breakdown & (diff < delta)
        diff = jnp.where(breakdown, _diff, diff)

        beta = zr_new / zr
        p_new = z + beta * p

        w_out = jnp.where(breakdown, w, w_new)
        r_out = jnp.where(breakdown, r, r_new)
        p_out = jnp.where(breakdown | converged, p, p_new)
        zr_out = jnp.where(breakdown | converged, zr, zr_new)
        out = (k + 1, w_out, r_out, p_out, zr_out, diff, converged, breakdown)
        if history:
            # applied α is 0 on a breakdown iteration (update discarded)
            # — the same recording every engine's trace uses
            out = out + history_record(
                state[8:], k, zr_new, diff,
                jnp.where(breakdown, 0.0, alpha), beta,
            )
        if abft:
            from poisson_ellipse_tpu.resilience.abft import (
                ABFT_TINY,
                abft_rtol,
            )

            S_r, S_w, S_p_pred, sdc = (
                state[_SR], state[_SW], state[_SP], state[_SDC]
            )
            s_ap, s_cp, s_absap = sums[2], sums[3], sums[4]
            s_r, s_absr = sums[5], sums[6]
            s_w, s_absw = sums[7], sums[8]
            s_p, s_absp = sums[9], sums[10]
            s_z = sums[11]
            rtol = abft_rtol(dtype)
            aa = jnp.abs(alpha)
            # every check written as ~(drift <= tol): a NaN drift must
            # read as a violation, and NaN <= tol is False in IEEE
            ok_stencil = jnp.abs(s_ap - s_cp) <= rtol * (s_absap + ABFT_TINY)
            ok_r = jnp.abs(s_r - (S_r - alpha * s_ap)) <= rtol * (
                s_absr + aa * s_absap + ABFT_TINY
            )
            ok_w = jnp.abs(s_w - (S_w + alpha * s_p)) <= rtol * (
                s_absw + aa * s_absp + ABFT_TINY
            )
            ok_p = jnp.abs(s_p - S_p_pred) <= rtol * (s_absp + ABFT_TINY)
            ok_pos = zr > 0  # ⟨z, r⟩ is an energy product: > 0 until done
            fault = ~breakdown & ~(
                ok_stencil & ok_r & ok_w & ok_p & ok_pos
            )
            keep = lambda old, new: jnp.where(breakdown, old, new)
            out = out + (
                keep(S_r, s_r),
                keep(S_w, s_w),
                keep(S_p_pred, s_z + beta * s_p),
                sdc | fault,
            )
        return out

    return lax.while_loop(cond, body, state)


def _local_pcg(problem: Problem, px: int, py: int, bm: int, bn: int,
               a_ext, b_ext, rhs_blk, dtype, stencil_impl: str = "xla",
               interpret: bool = False, history: bool = False):
    """Per-device whole solve (init + advance to the iteration cap).
    Runs inside shard_map; a_ext/b_ext are the device's halo-extended
    (bm+2, bn+2) coefficient blocks, rhs_blk its owned (bm, bn) RHS
    block. With ``history`` the four replicated (cap,) trace buffers
    ride at the end of the returned tuple."""
    stencil, pdot, d, _maskd = _shard_ops(
        problem, px, py, bm, bn, a_ext, b_ext, dtype, stencil_impl, interpret
    )
    state0 = _shard_init(
        problem, px, py, bm, bn, pdot, d, rhs_blk, dtype, history=history
    )
    out = _shard_advance(
        problem, stencil, pdot, d, state0, dtype, history=history
    )
    k, w = out[0], out[1]
    diff, converged, breakdown = out[5], out[6], out[7]
    return (w, k, diff, converged, breakdown) + tuple(out[8:])


def build_sharded_solver(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    assembly_mode: str = "host",
    stencil_impl: str = "xla",
    history: bool = False,
    geometry=None,
    theta=None,
):
    """Return (jitted solver_fn, args) for the mesh-sharded solve.

    ``history=True`` (classical loops only — "xla"/"pallas") makes the
    solver return ``(PCGResult, obs.ConvergenceTrace)``: the
    per-iteration (zr, diff, α, β) series recorded on device from the
    already-psum-reduced scalars — zero extra collectives, zero host
    traffic inside the loop.

    assembly_mode:
      "host"   — coefficients assembled once on the host in f64, cast, and
                 laid out over the mesh (args = the three sharded arrays;
                 their one-time coefficient halos are exchanged on device).
      "device" — every device assembles its own halo-extended block from
                 global indices inside shard_map, zero communication
                 (args = ()); use with f64 traces — see
                 ``ops.assembly.assemble_numpy`` for the f32 hazard.
    stencil_impl:
      "xla"    — XLA-fused block stencil (default).
      "pallas" — explicit Pallas stencil kernel per shard per iteration
                 (decomposition × device kernels in one program — the
                 stage4 composition; see ``_local_pcg``).
      "fused"  — the whole iteration as two Pallas kernels per shard
                 (K1 p-update+stencil+denom, K2 updates+partials) with a
                 stacked (z, p) halo exchange: 2 kernels + 2 psum +
                 4 ppermute per iteration (``parallel.fused_sharded``;
                 f32/bf16, host assembly only).
      "pipelined" — the Ghysels–Vanroose recurrence with ONE stacked
                 psum per iteration, overlapped by XLA with the halo
                 exchange + stencil (``parallel.pipelined_sharded``;
                 iteration counts within ±2 of "xla", host assembly
                 only — the collective-latency engine for multi-chip/
                 multi-host scale).
    """
    if mesh is None:
        mesh = make_mesh()
    if geometry is not None and assembly_mode != "host":
        raise ValueError(
            "SDF geometry assembles on the HOST in f64 (the quadrature "
            "path of ops.assembly); assembly_mode='device' traces the "
            "closed-form ellipse only"
        )
    if history and stencil_impl not in ("xla", "pallas"):
        raise ValueError(
            "history capture covers the classical sharded loops "
            f"('xla'/'pallas'); got stencil_impl={stencil_impl!r} — the "
            "fused/pipelined sharded iterations keep their scalars inside "
            "kernels/recurrences with their own carry layouts"
        )
    if stencil_impl == "pipelined":
        # the one-collective iteration — its own recurrence and carry
        # layout live in parallel.pipelined_sharded
        if assembly_mode != "host":
            raise ValueError(
                "stencil_impl='pipelined' assembles on the host (the "
                f"rounded-once operand set); got assembly_mode={assembly_mode!r}"
            )
        from poisson_ellipse_tpu.parallel.pipelined_sharded import (
            build_pipelined_sharded_solver,
        )

        return build_pipelined_sharded_solver(
            problem, mesh, dtype, geometry=geometry, theta=theta
        )
    if stencil_impl == "fused":
        # the two-kernel fused iteration composed with the mesh — its own
        # carry layout (rotated loop) and tile-aligned shard padding live
        # in parallel.fused_sharded
        if assembly_mode != "host":
            raise ValueError(
                "stencil_impl='fused' assembles on the host (the rounded-"
                f"once operand set); got assembly_mode={assembly_mode!r}"
            )
        from poisson_ellipse_tpu.parallel.fused_sharded import (
            build_fused_sharded_solver,
        )

        return build_fused_sharded_solver(
            problem, mesh, dtype, geometry=geometry, theta=theta
        )
    px = mesh.shape[AXIS_X]
    py = mesh.shape[AXIS_Y]
    # interpret is a property of the MESH devices, not the process default
    # backend: a TPU-default process dry-running on a virtual CPU mesh
    # (the driver's multichip gate) must interpret, and vice versa
    interpret = mesh.devices.flat[0].platform != "tpu"
    g1p, g2p = padded_dims(problem.node_shape, mesh)
    bm, bn = g1p // px, g2p // py
    spec = P(AXIS_X, AXIS_Y)
    # the four replicated (cap,) trace buffers, when history rides along
    out_specs = (spec, P(), P(), P(), P()) + ((P(),) * 4 if history else ())

    if assembly_mode == "host":

        def shard_fn(a_blk, b_blk, rhs_blk):
            # one-time coefficient halo exchange (the reference avoids this
            # by assembling a halo ring locally; both modes are provided)
            a_ext = halo_extend(a_blk, px, py)
            b_ext = halo_extend(b_blk, px, py)
            return _local_pcg(
                problem, px, py, bm, bn, a_ext, b_ext, rhs_blk, dtype,
                stencil_impl=stencil_impl, interpret=interpret,
                history=history,
            )

        # check_vma off only for the interpret-mode pallas stencil: its
        # internals mix varying refs with unvarying index values, which
        # the vma checker rejects (the kernel itself is per-shard pure);
        # compiled TPU runs keep full vma checking
        mapped = shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=out_specs,
            check_vma=not (stencil_impl == "pallas" and interpret),
        )

        args = _host_sharded_args(problem, mesh, dtype, g1p, g2p, spec,
                                  geometry=geometry, theta=theta)
    elif assembly_mode == "device":

        def shard_fn():
            ix = lax.axis_index(AXIS_X)
            iy = lax.axis_index(AXIS_Y)
            gi_ext = ix * bm - 1 + jnp.arange(bm + 2, dtype=jnp.int32)
            gj_ext = iy * bn - 1 + jnp.arange(bn + 2, dtype=jnp.int32)
            a_ext, b_ext = assembly.coefficients_at(problem, gi_ext, gj_ext, dtype)
            rhs_blk = assembly.rhs_at(
                problem, gi_ext[1:-1], gj_ext[1:-1], dtype
            )
            return _local_pcg(
                problem, px, py, bm, bn, a_ext, b_ext, rhs_blk, dtype,
                stencil_impl=stencil_impl, interpret=interpret,
                history=history,
            )

        mapped = shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(),
            out_specs=out_specs,
            check_vma=not (stencil_impl == "pallas" and interpret),
        )
        args = ()
    else:
        raise ValueError(f"unknown assembly_mode: {assembly_mode!r}")

    def solver(*arrays):
        out = mapped(*arrays)
        w_pad, k, diff, converged, breakdown = out[:5]
        result = PCGResult(
            w=w_pad[: problem.M + 1, : problem.N + 1],
            iters=k,
            diff=diff,
            converged=converged,
            breakdown=breakdown,
        )
        if history:
            return result, trace_of(out[5:], k)
        return result

    return jax.jit(solver), args


def build_sharded_stepper(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    stencil_impl: str = "xla",
    abft: bool = False,
):
    """(init_fn, advance_fn) for chunked/resumable sharded solves.

    ``init_fn() -> state`` builds the iteration-0 carry; ``advance_fn(state,
    limit) -> state`` advances it until convergence/breakdown or iteration
    ``limit`` (a traced scalar: chunked runs pass k+chunk per dispatch
    without recompiling). The carry layout matches ``solver.pcg.init_state``
    — (k, w, r, p, zr, diff, converged, breakdown) — with w/r/p as global
    padded ``(g1p, g2p)`` arrays sharded ``P('x','y')`` over the mesh and
    scalars replicated, which is exactly what ``solver.checkpoint``
    persists through orbax (sharded carries save/restore with their
    shardings intact). Chunking only moves the while_loop boundary, not
    the arithmetic, so a chunked run converges in the same iteration count
    as ``build_sharded_solver``'s straight solve.

    The reference has no distributed checkpointing at all (SURVEY §5) —
    its MPI runs are start-to-finish; this is the subsystem the long
    sharded runs (the only ones long enough to need it) get natively.

    ``abft=True`` extends the carry with the four ABFT shadow scalars
    (``resilience.abft``) and runs the in-loop SDC checks; the checksum
    field ``A·1`` is built per dispatch, outside the loop, and the
    per-iteration collective cadence is byte-identical to abft=False.
    """
    if mesh is None:
        mesh = make_mesh()
    px = mesh.shape[AXIS_X]
    py = mesh.shape[AXIS_Y]
    interpret = mesh.devices.flat[0].platform != "tpu"
    g1p, g2p = padded_dims(problem.node_shape, mesh)
    bm, bn = g1p // px, g2p // py
    spec = P(AXIS_X, AXIS_Y)
    scalar = P()
    state_specs = (scalar, spec, spec, spec, scalar, scalar, scalar, scalar)
    if abft:
        state_specs = state_specs + (scalar,) * 4
    check_vma = not (stencil_impl == "pallas" and interpret)

    def init_shard(a_blk, b_blk, rhs_blk):
        a_ext = halo_extend(a_blk, px, py)
        b_ext = halo_extend(b_blk, px, py)
        _stencil, pdot, d, _maskd = _shard_ops(
            problem, px, py, bm, bn, a_ext, b_ext, dtype,
            stencil_impl, interpret,
        )
        return _shard_init(
            problem, px, py, bm, bn, pdot, d, rhs_blk, dtype, abft=abft
        )

    def advance_shard(a_blk, b_blk, state, limit):
        from poisson_ellipse_tpu.resilience.abft import checksum_field

        a_ext = halo_extend(a_blk, px, py)
        b_ext = halo_extend(b_blk, px, py)
        stencil, pdot, d, maskd = _shard_ops(
            problem, px, py, bm, bn, a_ext, b_ext, dtype,
            stencil_impl, interpret,
        )
        c = checksum_field(stencil, maskd) if abft else None
        return _shard_advance(
            problem, stencil, pdot, d, state, dtype, limit=limit,
            abft=abft, abft_c=c,
        )

    # no donation on either stepper half: a/b are re-fed every chunk, and
    # the carry cannot be donated because solver.checkpoint hands it to
    # orbax's *async* save — the serializer may still be reading the old
    # buffers while the next advance runs
    init_mapped = jax.jit(shard_map(  # tpulint: disable=TPU004
        init_shard,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=state_specs,
        check_vma=check_vma,
    ))
    advance_mapped = jax.jit(shard_map(  # tpulint: disable=TPU004
        advance_shard,
        mesh=mesh,
        in_specs=(spec, spec, state_specs, scalar),
        out_specs=state_specs,
        check_vma=check_vma,
    ))

    args = _host_sharded_args(problem, mesh, dtype, g1p, g2p, spec)

    def init_fn():
        return init_mapped(*args)

    def advance_fn(state, limit):
        # args[2] is the RHS — consumed by init only; the carry holds r
        return advance_mapped(
            args[0], args[1], state, jnp.asarray(limit, jnp.int32)
        )

    return init_fn, advance_fn


def build_sharded_recover(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    stencil_impl: str = "xla",
    abft: bool = False,
):
    """Jitted true-residual restart over the sharded carry — the
    recovery primitive ``resilience.guard`` applies to mesh solves.

    ``recover_fn(state) -> state`` rebuilds r = rhs − A·w on every shard
    (one halo exchange + block stencil), the preconditioned residual and
    zr from ground truth, KEEPING the search direction p — the
    residual-replacement form that preserves oracle iteration parity
    (see ``resilience.guard``) — and clears the converged/breakdown
    flags. Same carry layout in and out as ``build_sharded_stepper``, so
    a recovered carry feeds straight back into ``advance_fn``. With
    ``abft`` the four shadow scalars are re-anchored to the rebuilt
    carry (one stacked psum — recovery is off the hot path).
    """
    if mesh is None:
        mesh = make_mesh()
    px = mesh.shape[AXIS_X]
    py = mesh.shape[AXIS_Y]
    interpret = mesh.devices.flat[0].platform != "tpu"
    g1p, g2p = padded_dims(problem.node_shape, mesh)
    bm, bn = g1p // px, g2p // py
    spec = P(AXIS_X, AXIS_Y)
    scalar = P()
    state_specs = (scalar, spec, spec, spec, scalar, scalar, scalar, scalar)
    if abft:
        state_specs = state_specs + (scalar,) * 4

    def recover_shard(a_blk, b_blk, rhs_blk, state):
        a_ext = halo_extend(a_blk, px, py)
        b_ext = halo_extend(b_blk, px, py)
        stencil, pdot, d, _maskd = _shard_ops(
            problem, px, py, bm, bn, a_ext, b_ext, dtype,
            stencil_impl, interpret,
        )
        k, w, _r, p, _zr, diff, _c, _bd = state[:8]
        r2 = rhs_blk - stencil(w)
        z2 = apply_dinv(r2, d)
        zr2 = pdot(z2, r2)
        out = (
            k, w, r2, p, zr2, diff,
            jnp.asarray(False), jnp.asarray(False),
        )
        if abft:
            sums = lax.psum(
                jnp.stack([jnp.sum(r2), jnp.sum(w), jnp.sum(p)]),
                (AXIS_X, AXIS_Y),
            )
            out = out + (sums[0], sums[1], sums[2], jnp.asarray(False))
        return out

    mapped = jax.jit(shard_map(  # tpulint: disable=TPU004
        recover_shard,
        mesh=mesh,
        in_specs=(spec, spec, spec, state_specs),
        out_specs=state_specs,
        check_vma=not (stencil_impl == "pallas" and interpret),
    ))
    args = _host_sharded_args(problem, mesh, dtype, g1p, g2p, spec)

    def recover_fn(state):
        return mapped(args[0], args[1], args[2], state)

    return recover_fn


def sharded_result_of(problem: Problem, state) -> PCGResult:
    """View a sharded PCG carry as a PCGResult (crops the shard padding;
    any ABFT shadow-scalar tail is ignored)."""
    k, w, _r, _p, _zr, diff, converged, breakdown = state[:8]
    return PCGResult(
        w=w[: problem.M + 1, : problem.N + 1],
        iters=k,
        diff=diff,
        converged=converged,
        breakdown=breakdown,
    )


def solve_sharded(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    assembly_mode: str = "host",
    stencil_impl: str = "xla",
    history: bool = False,
):
    """Assemble, shard and solve over the mesh (all devices by default).
    ``history=True`` returns (PCGResult, obs.ConvergenceTrace)."""
    solver, args = build_sharded_solver(
        problem, mesh, dtype, assembly_mode, stencil_impl=stencil_impl,
        history=history,
    )
    return solver(*args)


def _pad_to(arr, g1p: int, g2p: int):
    return np.pad(
        arr, ((0, g1p - arr.shape[0]), (0, g2p - arr.shape[1]))
    )


def _host_sharded_args(problem: Problem, mesh: Mesh, dtype,
                       g1p: int, g2p: int, spec, geometry=None, theta=None):
    """Host-f64-assembled a/b/rhs, zero-padded to even shards and laid out
    over the mesh (the "host" assembly mode's operand set). ``geometry``/
    ``theta`` select the SDF quadrature assembly (``ops.assembly``)."""
    a, b, rhs = assembly.assemble_numpy(problem, geometry=geometry,
                                        theta=theta)
    np_dtype = assembly.numpy_dtype(dtype)
    sharding = NamedSharding(mesh, spec)
    return tuple(
        jax.device_put(_pad_to(arr, g1p, g2p).astype(np_dtype), sharding)
        for arr in (a, b, rhs)
    )
