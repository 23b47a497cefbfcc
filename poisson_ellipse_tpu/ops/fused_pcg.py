"""Fused-iteration PCG: the whole loop body as two Pallas kernels.

The reference's stage4 runs six CUDA kernels + six device syncs + three
host round-trips per PCG iteration (``poisson_mpi_cuda2.cu:846-939``).
Measurements on the bench chip show the XLA while_loop path is
*overhead/compute*-bound, not HBM-bound (achieved streaming bandwidth is
~7 TB/s while one XLA iteration costs 40-480 us across the reference
grids — far above the pure-traffic bound), so the fusion targets are
kernel-count and per-element VPU work. One iteration is:

  K1  p = z + beta*p;  ap = A(p);  denom-partial      (one kernel)
  K2  alpha; w += alpha*p; r -= alpha*ap; ||dw||^2;
      z = r/D; (z,r)-partial                          (one kernel)
  +   one scalar fusion (beta, diff, convergence)

i.e. 3 launches/iteration vs the ~8 fusions XLA emits for the unfused
body — exactly the ``apply_A+dot`` / ``update_w_r+norm`` fusion SURVEY
section 7 step 6 calls for, plus the p-update folded into the stencil
(legal because the loop is rotated: beta is applied at the *start* of
the next body, which computes the same value sequence as the reference
order, ``stage0/Withoutopenmp1.cpp:124-169``).

Two loop-invariant rewrites keep the kernels off the VPU's slow paths —
both verified to preserve the published iteration-count oracles
(546/989/1858/2449) in f32 on hardware:

- the stencil runs on coefficients pre-divided by h^2 and pre-masked to
  the interior,  ap = an*(p-p_up) + as*(p-p_dn) + bw*(p-p_lf) +
  be*(p-p_rt),  so the kernel has zero divisions and zero mask logic.
  The differences come first, as in the reference
  (``stage0/Withoutopenmp1.cpp:83-85``): the expanded form
  D*p - (an*p_up + ...) cancels ~log2(D|p|/|Ap|) bits per application,
  which at 4096² doubled the f32 l2 error (PERF.md, PR 21);
- the preconditioner is a multiply by a precomputed 1/D (guarded where
  D = 0), not an in-loop divide.

Layout: all state rides padded to (g1p, g2p) = (row-tile multiple, lane
multiple). Padding and ring carry zero coefficients, so every iterate
stays exactly zero there (same invariant as ``parallel.mesh.padded_dims``).

Row halos for the stencil come from extra ``BlockSpec``s of the same
operand: a (tm, lanes) mid block plus (8, lanes) neighbour blocks whose
index maps point one 8-row block before/after — overlapping windows are
inexpressible in a single BlockSpec, but two narrow extra specs give the
halo rows through the normal double-buffered pipeline (no manual DMA, no
alignment pads; this replaces round 1's serial make_async_copy windows,
which is why this stencil pipelines and that one did not).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs.convergence import (
    history_init,
    history_record,
    trace_of,
)
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.stencil import diag_d
from poisson_ellipse_tpu.solver.pcg import DENOM_GUARD, PCGResult

# VMEM working-set budget for one kernel's live blocks (x2 for the
# pipeline's double buffering). The chip exposes ~15 MB usable.
_VMEM_BUDGET = 11 * 1024 * 1024


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pick_tile(g1: int, g2p: int, itemsize: int, n_buffers: int) -> int:
    """Row tile: multiple of 8, sized so n_buffers double-buffered blocks
    fit the VMEM budget (the 8-row halo specs are counted separately)."""
    per_row = g2p * itemsize * n_buffers * 2
    tm = max((_VMEM_BUDGET // max(per_row, 1)) // 8 * 8, 8)
    return min(tm, max(_round_up(g1, 8), 8), 512)


def _shift_down(mid, up_row):
    """Rows r0-1 .. r0+tm-2: predecessor of each row."""
    return jnp.concatenate([up_row, mid[:-1]], axis=0)


def _shift_up(mid, down_row):
    """Rows r0+1 .. r0+tm: successor of each row."""
    return jnp.concatenate([mid[1:], down_row], axis=0)


def _shift_left(x):
    """Column j-1 with a zero at j=0 (the Dirichlet ring is zero)."""
    zero = jnp.zeros((x.shape[0], 1), x.dtype)
    return jnp.concatenate([zero, x[:, :-1]], axis=1)


def _shift_right(x):
    """Column j+1 with a zero at the last (padded) column."""
    zero = jnp.zeros((x.shape[0], 1), x.dtype)
    return jnp.concatenate([x[:, 1:], zero], axis=1)


def _k1_kernel(n_tiles,
               beta_ref,
               z_up, z_mid, z_dn, p_up, p_mid, p_dn,
               an_mid, as_mid, bw_mid, be_mid,
               pn_out, ap_out, denom_out, acc):
    """p = z + beta*p, ap = A(p), denominator partial — one row tile.

    The neighbour coefficients are pre-masked to the interior, so the
    clamped-garbage halo rows at the first/last tile are multiplied by
    exact zeros and the ring/padding output is exactly zero with no
    in-kernel masking.
    """
    i = pl.program_id(0)
    beta = beta_ref[0]
    pn = z_mid[:] + beta * p_mid[:]
    # halo rows of the *updated* p, built from the neighbour specs
    pn_row_up = z_up[7:8, :] + beta * p_up[7:8, :]
    pn_row_dn = z_dn[0:1, :] + beta * p_dn[0:1, :]

    ap = (
        an_mid[:] * (pn - _shift_down(pn, pn_row_up))
        + as_mid[:] * (pn - _shift_up(pn, pn_row_dn))
        + bw_mid[:] * (pn - _shift_left(pn))
        + be_mid[:] * (pn - _shift_right(pn))
    )

    pn_out[:] = pn
    ap_out[:] = ap

    @pl.when(i == 0)
    def _():
        acc[0] = jnp.zeros((), pn.dtype)

    acc[0] += jnp.sum(ap * pn)

    @pl.when(i == n_tiles - 1)
    def _():
        denom_out[0] = acc[0]


def _k2_kernel(n_tiles,
               zr_ref, denom_ref,
               w_mid, r_mid, p_mid, ap_mid, dinv_mid,
               w_out, r_out, z_out, sums_out, acc):
    """alpha; w/r update; ||dw||^2 and (z,r) partials — one row tile.

    alpha is derived in-kernel from the (zr, denom) scalars so no extra
    scalar kernel sits between K1 and K2; on breakdown (denom under the
    reference's 1e-15 guard, ``stage0/Withoutopenmp1.cpp:128``) alpha is
    forced to 0, which holds w/r exactly (the reference exits before
    touching them).
    """
    i = pl.program_id(0)
    denom = denom_ref[0]
    breakdown = denom < DENOM_GUARD
    alpha = zr_ref[0] / jnp.where(breakdown, jnp.ones_like(denom), denom)
    alpha = jnp.where(breakdown, jnp.zeros_like(alpha), alpha)

    w = w_mid[:]
    w_new = w + alpha * p_mid[:]
    r_new = r_mid[:] - alpha * ap_mid[:]
    z = r_new * dinv_mid[:]
    # realised increment (w_new - w), not alpha*p: the convergence oracle
    # counts depend on the FP difference (poisson_mpi_cuda2.cu:626-660)
    dw = w_new - w

    w_out[:] = w_new
    r_out[:] = r_new
    z_out[:] = z

    @pl.when(i == 0)
    def _():
        acc[0] = jnp.zeros((), w.dtype)
        acc[1] = jnp.zeros((), w.dtype)

    acc[0] += jnp.sum(z * r_new)
    acc[1] += jnp.sum(dw * dw)

    @pl.when(i == n_tiles - 1)
    def _():
        sums_out[0] = acc[0]
        sums_out[1] = acc[1]


class _FusedKernels(NamedTuple):
    k1: callable
    k2: callable
    g1p: int
    g2p: int


def build_kernels(problem: Problem, g1: int, g2: int, dtype,
                  interpret=None) -> _FusedKernels:
    """Compile-ready K1/K2 closures for one grid size."""
    if interpret is None:
        interpret = _interpret_default()
    itemsize = jnp.dtype(dtype).itemsize
    g2p = _round_up(g2, 128)
    # K1 holds ~12 live (tm, g2p) blocks, K2 ~9; size for the larger set
    tm = _pick_tile(g1, g2p, itemsize, 13)
    g1p = _round_up(g1, tm)
    n_tiles = g1p // tm
    nb = max(g1p // 8 - 1, 0)  # last valid 8-row block index

    mid = lambda: pl.BlockSpec((tm, g2p), lambda i: (i, 0))
    c = tm // 8  # 8-row blocks per tile

    def up_map(i):
        return (jnp.maximum(i * c - 1, 0), 0)

    def dn_map(i):
        return (jnp.minimum((i + 1) * c, nb), 0)

    up = lambda: pl.BlockSpec((8, g2p), up_map)
    dn = lambda: pl.BlockSpec((8, g2p), dn_map)
    smem_in = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)

    k1 = pl.pallas_call(
        functools.partial(_k1_kernel, n_tiles),
        grid=(n_tiles,),
        in_specs=[smem_in(), up(), mid(), dn(), up(), mid(), dn(),
                  mid(), mid(), mid(), mid()],
        out_specs=(mid(), mid(), pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=(
            jax.ShapeDtypeStruct((g1p, g2p), dtype),
            jax.ShapeDtypeStruct((g1p, g2p), dtype),
            jax.ShapeDtypeStruct((1,), dtype),
        ),
        scratch_shapes=[pltpu.SMEM((1,), dtype)],
        interpret=interpret,
    )

    k2 = pl.pallas_call(
        functools.partial(_k2_kernel, n_tiles),
        grid=(n_tiles,),
        in_specs=[smem_in(), smem_in(),
                  mid(), mid(), mid(), mid(), mid()],
        out_specs=(mid(), mid(), mid(),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=(
            jax.ShapeDtypeStruct((g1p, g2p), dtype),
            jax.ShapeDtypeStruct((g1p, g2p), dtype),
            jax.ShapeDtypeStruct((g1p, g2p), dtype),
            jax.ShapeDtypeStruct((2,), dtype),
        ),
        scratch_shapes=[pltpu.SMEM((2,), dtype)],
        interpret=interpret,
    )

    def call_k1(beta, z, p, an, as_, bw, be):
        return k1(jnp.reshape(beta, (1,)), z, z, z, p, p, p,
                  an, as_, bw, be)

    def call_k2(zr, denom, w, r, p, ap, dinv):
        return k2(
            jnp.reshape(zr, (1,)), jnp.reshape(denom, (1,)),
            w, r, p, ap, dinv,
        )

    return _FusedKernels(k1=call_k1, k2=call_k2, g1p=g1p, g2p=g2p)


def _pad(x, g1p, g2p):
    return jnp.pad(x, ((0, g1p - x.shape[0]), (0, g2p - x.shape[1])))


def normalized_coefficients(problem: Problem, a, b, g1p: int, g2p: int,
                            dtype=None):
    """The loop-invariant operand set of the fused iteration.

    Returns (an, as_, bw, be, dinv), each (g1p, g2p):
      an_ij = a_ij / h1^2        ("north", multiplies p_{i-1,j})
      as_ij = a_{i+1,j} / h1^2   ("south", multiplies p_{i+1,j})
      bw_ij = b_ij / h2^2        ("west",  multiplies p_{i,j-1})
      be_ij = b_{i,j+1} / h2^2   ("east",  multiplies p_{i,j+1})
      dinv  = 1/d where d != 0 else 0, d = an + as_ + bw + be (= diag_d)
    all masked to the interior 1..M-1 x 1..N-1, so the stencil
      ap = an*(p-p_up) + as*(p-p_dn) + bw*(p-p_lf) + be*(p-p_rt)
    is exactly zero on the ring/padding with no runtime masking.

    The divisions/sums happen in the *input* precision: pass f64 numpy
    a/b (``assembly.assemble_numpy``) with ``dtype=f32`` to get
    coefficients rounded once from the reference's double-precision
    values — the closest f32 can sit to the reference operator, and what
    keeps the iteration-count oracles exact. Jax-array (traced) inputs
    are supported too and computed in their own dtype.
    """
    if dtype is None:
        dtype = a.dtype
    an, as_, bw, be, _d, dinv = interior_normalized(problem, a, b)
    import numpy as np

    xp = np if isinstance(a, np.ndarray) else jnp
    g1, g2 = a.shape
    pad = ((0, g1p - g1), (0, g2p - g2))
    return tuple(
        jnp.asarray(xp.pad(x, pad).astype(dtype))
        for x in (an, as_, bw, be, dinv)
    )


def normalized_unmasked(problem: Problem, a, b):
    """(an, bw) = (a/h1², b/h2²) over the full grid, unmasked, in the
    input precision — the one place the 1/h² hoisting algebra lives.
    ``interior_normalized`` builds the masked operand set from these; the
    streamed engine uses them directly (its south/east coefficients are
    offset slices, which only works unmasked)."""
    ih1 = 1.0 / (float(problem.h1) * float(problem.h1))
    ih2 = 1.0 / (float(problem.h2) * float(problem.h2))
    return a * ih1, b * ih2


def interior_normalized(problem: Problem, a, b):
    """(an, as_, bw, be, d, dinv) in the *input* precision, unpadded.

    The single source of the normalised/guarded operand algebra — the
    streamed engine reuses the ``dinv`` element so the two "value
    identical" paths cannot drift (they share the code, not a copy).
    """
    import numpy as np

    xp = np if isinstance(a, np.ndarray) else jnp
    g1, g2 = a.shape
    an, bw = normalized_unmasked(problem, a, b)
    as_ = xp.roll(an, -1, axis=0)
    be = xp.roll(bw, -1, axis=1)
    gi = xp.arange(g1)[:, None]
    gj = xp.arange(g2)[None, :]
    interior = (
        (gi >= 1) & (gi <= problem.M - 1) & (gj >= 1) & (gj <= problem.N - 1)
    )
    z = xp.zeros((), an.dtype)
    an, as_, bw, be = (
        xp.where(interior, x, z) for x in (an, as_, bw, be)
    )
    d = an + as_ + bw + be
    dinv = xp.where(d != 0.0, 1.0 / xp.where(d != 0.0, d, 1.0), z)
    return an, as_, bw, be, d, dinv


def fused_operands(problem: Problem, g1p: int, g2p: int, dtype,
                   geometry=None, theta=None):
    """Device-ready loop-invariant operands, rounded once from the f64
    host assembly (the oracle-exact path; see normalized_coefficients).
    ``geometry``/``theta`` select the SDF quadrature assembly."""
    import numpy as np

    a64, b64, _ = assembly.assemble_numpy(problem, geometry=geometry,
                                          theta=theta)
    np_dtype = np.dtype(jnp.dtype(dtype).name)
    return normalized_coefficients(problem, a64, b64, g1p, g2p, np_dtype)


def rotated_state0(w0, r0, z0, p0, zr0, dtype):
    """Iteration-0 carry of the rotated fused loop — the one layout
    (k, w, r, z, p, zr, beta, diff, converged, breakdown) shared by the
    single-chip engine and ``parallel.fused_sharded`` (beta0 = 0 makes
    the first K1 produce p1 = z0, the reference's initial direction)."""
    return (
        jnp.asarray(0, jnp.int32),
        w0,
        r0,
        z0,
        p0,
        zr0,
        jnp.asarray(0.0, dtype),        # beta
        jnp.asarray(jnp.inf, dtype),    # diff
        jnp.asarray(False),
        jnp.asarray(False),
    )


def rotated_cond(max_iter):
    """while_loop predicate over the ``rotated_state0`` carry layout."""

    def cond(s):
        k = s[0]
        converged, breakdown = s[8], s[9]
        return (k < max_iter) & ~converged & ~breakdown

    return cond


def rotated_next_state(s, pn, w_new, r_new, z_new, zr_new, dw2,
                       breakdown, h1, h2, delta, weighted):
    """Scalar tail of one rotated iteration: the convergence test, the
    breakdown holds (zr/beta frozen so the exit state matches the
    reference's early return) and the next beta — one copy of the carry
    algebra shared by the single-chip and sharded fused engines."""
    k = s[0]
    zr, beta, diff = s[5], s[6], s[7]
    ndiff = jnp.sqrt(dw2 * h1 * h2) if weighted else jnp.sqrt(dw2)
    converged = ~breakdown & (ndiff < delta)
    ndiff = jnp.where(breakdown, diff, ndiff)
    beta_new = zr_new / jnp.where(breakdown, jnp.ones_like(zr), zr)
    return (
        k + 1, w_new, r_new, z_new, pn,
        jnp.where(breakdown, zr, zr_new),
        jnp.where(breakdown, beta, beta_new),
        ndiff, converged, breakdown,
    )


def _run_fused(problem: Problem, kern: _FusedKernels, coeffs, r0,
               g1: int, g2: int, history: bool = False):
    """The rotated while_loop given prebuilt kernels + operand set.

    ``history=True`` appends the four ``obs.convergence`` buffers to the
    rotated carry and records each iteration's (zr, diff, α, β) at the
    XLA level, outside the Pallas kernels — α re-derives K2's in-kernel
    value from the same (zr, denom) scalars and expression, so the trace
    matches what the kernel applied; returns (PCGResult, trace).
    """
    dtype = r0.dtype
    g1p, g2p = kern.g1p, kern.g2p
    an, as_, bw, be, dinv_p = coeffs

    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    delta = jnp.asarray(problem.delta, dtype)
    weighted = problem.norm == "weighted"

    z0 = r0 * dinv_p
    zr0 = jnp.sum(z0 * r0) * h1 * h2
    state0 = rotated_state0(
        jnp.zeros((g1p, g2p), dtype), r0, z0,
        jnp.zeros((g1p, g2p), dtype), zr0, dtype,
    )
    if history:
        state0 = state0 + history_init(problem.max_iterations, dtype)

    def body(s):
        k, w, r, z, p, zr, beta, _diff, _c, _bd = s[:10]
        pn, ap, denom_raw = kern.k1(beta, z, p, an, as_, bw, be)
        denom = denom_raw[0] * h1 * h2
        breakdown = denom < DENOM_GUARD
        w_new, r_new, z_new, sums = kern.k2(zr, denom, w, r, pn, ap, dinv_p)
        zr_new = sums[0] * h1 * h2
        out = rotated_next_state(
            s[:10], pn, w_new, r_new, z_new, zr_new, sums[1],
            breakdown, h1, h2, delta, weighted,
        )
        if history:
            # K2's guarded α, re-derived from the same scalars it read
            alpha = zr / jnp.where(breakdown, jnp.ones_like(denom), denom)
            alpha = jnp.where(breakdown, jnp.zeros_like(alpha), alpha)
            beta_new = zr_new / jnp.where(breakdown, jnp.ones_like(zr), zr)
            out = out + history_record(s[10:], k, zr_new, out[7], alpha, beta_new)
        return out

    out = lax.while_loop(
        rotated_cond(problem.max_iterations), body, state0
    )
    k, w = out[0], out[1]
    diff, converged, breakdown = out[7], out[8], out[9]
    result = PCGResult(
        w=w[:g1, :g2], iters=k, diff=diff,
        converged=converged, breakdown=breakdown,
    )
    if history:
        return result, trace_of(out[10:], k)
    return result


def pcg_fused(problem: Problem, a, b, rhs, interpret=None,
              history: bool = False):
    """PCG with the fused two-kernel iteration. Same value *sequence* as
    ``solver.pcg.pcg`` (reference order, rotated) up to the documented
    pre-divided-coefficient rewrite. Jit-safe with traced a/b/rhs; the
    coefficient normalisation then runs in the input dtype — for the
    oracle-exact f64-rounded operand set use ``build_fused_solver``.

    f32/bf16 only (Pallas TPU has no f64 path); callers with f64 inputs
    should use the XLA path.
    """
    dtype = rhs.dtype
    if jnp.dtype(dtype).itemsize >= 8:
        raise ValueError("pcg_fused supports f32/bf16; use stencil='xla' for f64")
    g1, g2 = rhs.shape
    kern = build_kernels(problem, g1, g2, dtype, interpret=interpret)
    coeffs = normalized_coefficients(problem, a, b, kern.g1p, kern.g2p)
    r0 = _pad(rhs, kern.g1p, kern.g2p)
    return _run_fused(problem, kern, coeffs, r0, g1, g2, history=history)


def build_fused_solver(problem: Problem, dtype=jnp.float32, interpret=None,
                       history: bool = False, geometry=None, theta=None):
    """(jitted solver, args) with the f64-rounded operand set.

    The operands (normalised coefficients + RHS) are assembled on the
    host in double precision — exactly the reference's assembly
    (``fictitious_regions_setup_local``, ``poisson_mpi_cuda2.cu:146-192``)
    — and rounded once to the run dtype. This is the bench/CLI fused
    path; it reproduces the published iteration counts in f32.
    """
    import numpy as np

    if jnp.dtype(dtype).itemsize >= 8:
        raise ValueError("fused solver supports f32/bf16; use stencil='xla'")
    g1, g2 = problem.node_shape
    kern = build_kernels(problem, g1, g2, dtype, interpret=interpret)
    coeffs = fused_operands(problem, kern.g1p, kern.g2p, dtype,
                            geometry=geometry, theta=theta)
    _, _, rhs64 = assembly.assemble_numpy(problem, geometry=geometry,
                                          theta=theta)
    np_dtype = np.dtype(jnp.dtype(dtype).name)
    r0 = jnp.asarray(
        np.pad(
            rhs64, ((0, kern.g1p - g1), (0, kern.g2p - g2))
        ).astype(np_dtype)
    )
    args = (*coeffs, r0)

    def solver(an, as_, bw, be, dinv_p, r0):
        return _run_fused(
            problem, kern, (an, as_, bw, be, dinv_p), r0, g1, g2,
            history=history,
        )

    # no donation: build-once-call-many — callers re-feed these operands
    # every dispatch (bench --repeat protocol)
    # tpulint: disable=TPU004
    return jax.jit(solver), args


def solve_fused(problem: Problem, dtype=jnp.float32,
                interpret=None) -> PCGResult:
    """Assemble and solve with the fused iteration (single chip)."""
    solver, args = build_fused_solver(problem, dtype, interpret=interpret)
    return solver(*args)
