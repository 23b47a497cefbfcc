"""Fused-sharded PCG: the two-kernel iteration composed with the mesh.

The true heir of the reference stage4's composition — a 2D rank
decomposition whose hot loop runs device *kernels* per rank, ringed by
halo exchange and scalar reductions (``gradient_solver_mpi``,
``poisson_mpi_cuda2.cu:846-939``: six CUDA kernel launches + MPI_Sendrecv
halos + three MPI_Allreduce per iteration). Here one PCG iteration on
every shard is:

  1 stacked halo exchange   (z, p) pair in 4 ``lax.ppermute``s
  K1  pn = z + beta*p; ap = A(pn); denominator partial   (one kernel)
  1 ``lax.psum``            denominator
  K2  alpha; w += alpha*pn; r -= alpha*ap; ||dw||^2;
      z = r * 1/D; (z, r) partials                       (one kernel)
  1 ``lax.psum``            [zr, ||dw||^2] batched as one collective

i.e. 2 kernels + 2 psum + 4 ppermute per iteration, versus the ~8 XLA
fusions the plain sharded loop emits per iteration — the same
launch-count fusion the single-chip fused engine performs
(``ops.fused_pcg``), now per shard inside ``jax.shard_map``.

Kernel structure: K2 is *reused verbatim* from the single-chip fused
engine (``ops.fused_pcg._k2_kernel`` — pure elementwise + reduction on
the owned block). K1 differs from the single-chip K1 only in how halos
arrive: on one chip the neighbour rows come from extra BlockSpecs of the
same array and the Dirichlet columns are zero by padding; on a mesh the
halos are real neighbour data delivered by ``halo_extend_stacked``, so
K1 runs on (bm+2, bn+2) halo-extended inputs DMA'd in aligned row
windows — the proven pattern of ``ops.pallas_kernels._stencil_kernel``
— and mirrors ``ops.stencil.apply_a_block``'s expression tree term for
term (each difference divided by h before combining), which is what
keeps iteration-count parity with the sharded XLA path.

Sharding layout: the global node grid is zero-padded so every shard is
(8, 128)-tile aligned — (bm, bn) = (g1p/px, g2p/py) with bm % 8 == 0,
bn % 128 == 0. Padding carries zero coefficients and RHS, so padded
nodes behave exactly like the exterior Dirichlet ring (the
``parallel.mesh.padded_dims`` invariant, tightened to Mosaic tiling).

f32/bf16 only (Pallas TPU has no f64 path); f64 sharded runs use the
XLA stencil path (``parallel.pcg_sharded``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.fused_pcg import (
    _k2_kernel,
    interior_normalized,
    rotated_cond,
    rotated_next_state,
    rotated_state0,
)
from poisson_ellipse_tpu.ops.pallas_kernels import _row_tile, round_up
from poisson_ellipse_tpu.parallel.halo import halo_extend, halo_extend_stacked
from poisson_ellipse_tpu.parallel.mesh import (
    AXIS_X,
    AXIS_Y,
    make_mesh,
    pcast_varying,
)
from poisson_ellipse_tpu.solver.pcg import DENOM_GUARD, PCGResult

MESH_AXES = (AXIS_X, AXIS_Y)

# _row_tile / round_up are the shared VMEM-tile heuristic of
# ops.pallas_kernels — one copy, so a future budget fix cannot diverge
# between the single-chip and sharded engines (bm is 8-aligned by the
# fused-sharded padding, which is what _row_tile's divisor scan needs).


def padded_dims_fused(node_shape, mesh: Mesh) -> tuple[int, int]:
    """Global node dims padded so every shard is Mosaic-tile aligned."""
    g1, g2 = node_shape
    px = mesh.shape[AXIS_X]
    py = mesh.shape[AXIS_Y]
    return round_up(g1, 8 * px), round_up(g2, 128 * py)


def _k1_kernel(h1, h2, tm, bn, n_tiles,
               beta_ref, d_ref, z_hbm, p_hbm, a_hbm, b_hbm,
               pn_out, ap_out, denom_out, z_s, p_s, a_s, b_s, sems, acc):
    """pn = z + beta*p; ap = A(pn) masked; denom partial — one row tile.

    Inputs are halo-extended blocks padded to (bm+8, bn+128): tile i's
    owned rows sit at extended rows i*tm+1 .. i*tm+tm, so the aligned
    (tm+8)-row window starting at i*tm covers the stencil's row halo.
    The stencil mirrors ``ops.stencil.apply_a_block`` term for term; the
    mask is d != 0 (d is the interior-masked operator diagonal), which
    keeps every iterate exactly zero on the ring/padding as the sharded
    XLA path's maskd multiply does.
    """
    i = pl.program_id(0)
    r0 = i * tm
    copies = [
        pltpu.make_async_copy(src.at[pl.ds(r0, tm + 8), :], dst, sems.at[k])
        for k, (src, dst) in enumerate(
            [(z_hbm, z_s), (p_hbm, p_s), (a_hbm, a_s), (b_hbm, b_s)]
        )
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()

    beta = beta_ref[0]
    # the updated direction on the (tm+2)-row stencil window, halo included
    pn_w = z_s[0 : tm + 2, :] + beta * p_s[0 : tm + 2, :]
    wc = pn_w[1 : tm + 1, 1 : bn + 1]
    ax = -(
        a_s[2 : tm + 2, 1 : bn + 1] * (pn_w[2 : tm + 2, 1 : bn + 1] - wc) / h1
        - a_s[1 : tm + 1, 1 : bn + 1] * (wc - pn_w[0:tm, 1 : bn + 1]) / h1
    ) / h1
    ay = -(
        b_s[1 : tm + 1, 2 : bn + 2] * (pn_w[1 : tm + 1, 2 : bn + 2] - wc) / h2
        - b_s[1 : tm + 1, 1 : bn + 1] * (wc - pn_w[1 : tm + 1, 0:bn]) / h2
    ) / h2
    d = d_ref[:]
    ap = jnp.where(d != 0.0, ax + ay, 0.0)

    pn_out[:] = wc
    ap_out[:] = ap

    @pl.when(i == 0)
    def _():
        acc[0] = jnp.zeros((), wc.dtype)

    acc[0] += jnp.sum(ap * wc)

    @pl.when(i == n_tiles - 1)
    def _():
        denom_out[0] = acc[0]


class _ShardKernels(NamedTuple):
    k1: callable
    k2: callable
    bm: int
    bn: int
    cols: int  # padded column count of the halo-extended operands


def build_shard_kernels(bm: int, bn: int, h1: float, h2: float, dtype,
                        interpret: bool) -> _ShardKernels:
    """K1/K2 pallas_call closures for one (bm, bn) shard.

    Outputs carry vma annotations over both mesh axes so the kernels
    type-check under shard_map's varying-mesh-axes analysis (same
    contract as ``ops.pallas_kernels.apply_a_block_pallas``'s ``vma``).
    """
    if bm % 8 or bn % 128:
        raise ValueError(
            f"fused-sharded shards must be (8, 128)-aligned, got ({bm}, {bn})"
        )
    itemsize = jnp.dtype(dtype).itemsize
    cols = bn + 128  # bn + 2 halo columns, rounded up to the lane tile
    vma = frozenset(MESH_AXES)

    # K1: 4 DMA windows of (tm+8, cols) + d/pn/ap blocks of (tm, bn)
    tm1 = _row_tile(bm, cols, itemsize, 7)
    n1 = bm // tm1
    blk1 = lambda: pl.BlockSpec(
        (tm1, bn), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    any_ = lambda: pl.BlockSpec(memory_space=pl.ANY)
    k1 = pl.pallas_call(
        functools.partial(_k1_kernel, float(h1), float(h2), tm1, bn, n1),
        grid=(n1,),
        in_specs=[smem(), blk1(), any_(), any_(), any_(), any_()],
        out_specs=(blk1(), blk1(), smem()),
        out_shape=(
            jax.ShapeDtypeStruct((bm, bn), dtype, vma=vma),
            jax.ShapeDtypeStruct((bm, bn), dtype, vma=vma),
            jax.ShapeDtypeStruct((1,), dtype, vma=vma),
        ),
        scratch_shapes=[
            pltpu.VMEM((tm1 + 8, cols), dtype),
            pltpu.VMEM((tm1 + 8, cols), dtype),
            pltpu.VMEM((tm1 + 8, cols), dtype),
            pltpu.VMEM((tm1 + 8, cols), dtype),
            pltpu.SemaphoreType.DMA((4,)),
            pltpu.SMEM((1,), dtype),
        ],
        interpret=interpret,
    )

    # K2: the single-chip fused engine's kernel, verbatim, on the owned
    # block — 9 live (tm, bn) buffers (5 in, 3 out, + pipeline slack)
    tm2 = _row_tile(bm, bn, itemsize, 9)
    n2 = bm // tm2
    blk2 = lambda: pl.BlockSpec(
        (tm2, bn), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    k2 = pl.pallas_call(
        functools.partial(_k2_kernel, n2),
        grid=(n2,),
        in_specs=[smem(), smem(), blk2(), blk2(), blk2(), blk2(), blk2()],
        out_specs=(blk2(), blk2(), blk2(), smem()),
        out_shape=(
            jax.ShapeDtypeStruct((bm, bn), dtype, vma=vma),
            jax.ShapeDtypeStruct((bm, bn), dtype, vma=vma),
            jax.ShapeDtypeStruct((bm, bn), dtype, vma=vma),
            jax.ShapeDtypeStruct((2,), dtype, vma=vma),
        ),
        scratch_shapes=[pltpu.SMEM((2,), dtype)],
        interpret=interpret,
    )

    def call_k1(beta, d_blk, z_ext, p_ext, a_ext, b_ext):
        return k1(jnp.reshape(beta, (1,)), d_blk, z_ext, p_ext, a_ext, b_ext)

    def call_k2(zr, denom, w, r, pn, ap, dinv_blk):
        return k2(
            jnp.reshape(zr, (1,)), jnp.reshape(denom, (1,)),
            w, r, pn, ap, dinv_blk,
        )

    return _ShardKernels(k1=call_k1, k2=call_k2, bm=bm, bn=bn, cols=cols)


def _pad_ext(x_ext, cols: int):
    """Pad a (bm+2, bn+2) halo-extended block to the (bm+8, cols) layout
    K1's aligned DMA windows require (zeros: Dirichlet exterior)."""
    return jnp.pad(x_ext, ((0, 6), (0, cols - x_ext.shape[1])))


def _vary(x):
    """Broadcast a replicated scalar to mesh-varying, so kernel operand
    vma sets are uniform under shard_map's checker."""
    return pcast_varying(x, MESH_AXES)


def build_fused_sharded_solver(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    interpret: bool | None = None,
    geometry=None,
    theta=None,
):
    """(jitted solver, args) for the fused two-kernel mesh-sharded solve.

    Operands are assembled on the host in f64 (the reference's assembly,
    ``fictitious_regions_setup_local``, ``poisson_mpi_cuda2.cu:146-192``)
    and rounded once to the run dtype — the same fidelity contract as
    every other engine, which is what preserves the published
    iteration-count oracles. args = (a, b, d, dinv, rhs), each a global
    (g1p, g2p) array laid out P('x', 'y') over the mesh.
    """
    if jnp.dtype(dtype).itemsize >= 8:
        raise ValueError(
            "fused-sharded supports f32/bf16; use stencil_impl='xla' for f64"
        )
    if mesh is None:
        mesh = make_mesh()
    px = mesh.shape[AXIS_X]
    py = mesh.shape[AXIS_Y]
    if interpret is None:
        interpret = mesh.devices.flat[0].platform != "tpu"
    g1p, g2p = padded_dims_fused(problem.node_shape, mesh)
    bm, bn = g1p // px, g2p // py
    kern = build_shard_kernels(
        bm, bn, problem.h1, problem.h2, dtype, interpret
    )

    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    delta = jnp.asarray(problem.delta, dtype)
    weighted = problem.norm == "weighted"
    max_iter = problem.max_iterations

    def pdot(u, v):
        return lax.psum(jnp.sum(u * v), MESH_AXES) * h1 * h2

    def shard_fn(a_blk, b_blk, d_blk, dinv_blk, rhs_blk):
        # one-time coefficient halo exchange + DMA-layout padding (loop
        # invariant: sits outside the while_loop)
        a_ext = _pad_ext(halo_extend(a_blk, px, py), kern.cols)
        b_ext = _pad_ext(halo_extend(b_blk, px, py), kern.cols)

        r0 = rhs_blk
        z0 = r0 * dinv_blk  # multiply by 1/D, as K2 does every iteration
        zr0 = pdot(z0, r0)
        varying_zeros = lambda: pcast_varying(
            jnp.zeros((bm, bn), dtype), MESH_AXES
        )
        state0 = rotated_state0(
            varying_zeros(), r0, z0, varying_zeros(), zr0, dtype
        )

        def body(s):
            _k, w, r, z, p, zr, beta, _diff, _c, _bd = s
            zp_ext = halo_extend_stacked(jnp.stack([z, p]), px, py)
            z_ext = _pad_ext(zp_ext[0], kern.cols)
            p_ext = _pad_ext(zp_ext[1], kern.cols)
            pn, ap, dpart = kern.k1(
                _vary(beta), d_blk, z_ext, p_ext, a_ext, b_ext
            )
            denom = lax.psum(dpart[0], MESH_AXES) * h1 * h2
            breakdown = denom < DENOM_GUARD
            w_new, r_new, z_new, sums = kern.k2(
                _vary(zr), _vary(denom), w, r, pn, ap, dinv_blk
            )
            psums = lax.psum(sums, MESH_AXES)
            return rotated_next_state(
                s, pn, w_new, r_new, z_new, psums[0] * h1 * h2, psums[1],
                breakdown, h1, h2, delta, weighted,
            )

        out = lax.while_loop(rotated_cond(max_iter), body, state0)
        k, w = out[0], out[1]
        diff, converged, breakdown = out[7], out[8], out[9]
        return w, k, diff, converged, breakdown

    spec = P(AXIS_X, AXIS_Y)
    mapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec,) * 5,
        out_specs=(spec, P(), P(), P(), P()),
        # interpret-mode pallas internals mix varying refs with unvarying
        # index values, which the vma checker rejects (same waiver as the
        # per-op pallas stencil path, parallel.pcg_sharded); compiled TPU
        # runs keep full vma checking
        check_vma=not interpret,
    )

    args = _fused_sharded_args(problem, mesh, dtype, g1p, g2p, spec,
                               geometry=geometry, theta=theta)

    def solver(a, b, d, dinv, rhs):
        w_pad, k, diff, converged, breakdown = mapped(a, b, d, dinv, rhs)
        return PCGResult(
            w=w_pad[: problem.M + 1, : problem.N + 1],
            iters=k,
            diff=diff,
            converged=converged,
            breakdown=breakdown,
        )

    # no donation: build-once-call-many — callers re-feed these operands
    # every dispatch (bench --repeat protocol)
    # tpulint: disable=TPU004
    return jax.jit(solver), args


def _fused_sharded_args(problem: Problem, mesh: Mesh, dtype,
                        g1p: int, g2p: int, spec, geometry=None,
                        theta=None):
    """Host-f64-assembled (a, b, d, dinv, rhs), rounded once, zero-padded
    to tile-aligned shards and laid out over the mesh.

    d/dinv come from ``ops.fused_pcg.interior_normalized`` — the shared
    normalised/guarded diagonal algebra — so K2's preconditioner multiply
    uses the identical rounded-once reciprocal as the single-chip fused
    engine (the two paths share the code, not a copy)."""
    a64, b64, rhs64 = assembly.assemble_numpy(problem, geometry=geometry,
                                              theta=theta)
    _an, _as, _bw, _be, d64, dinv64 = interior_normalized(problem, a64, b64)
    np_dtype = assembly.numpy_dtype(dtype)
    sharding = NamedSharding(mesh, spec)

    def put(arr):
        padded = np.pad(
            arr, ((0, g1p - arr.shape[0]), (0, g2p - arr.shape[1]))
        )
        return jax.device_put(padded.astype(np_dtype), sharding)

    return tuple(put(x) for x in (a64, b64, d64, dinv64, rhs64))


def solve_fused_sharded(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    interpret: bool | None = None,
) -> PCGResult:
    """Assemble, shard and solve with the fused two-kernel iteration."""
    solver, args = build_fused_sharded_solver(
        problem, mesh, dtype, interpret=interpret
    )
    return solver(*args)
