"""Whole-solve Pallas kernel: the entire PCG loop VMEM-resident.

The reference's stage4 pays, per iteration, 6 kernel launches + 6 device
syncs + >=3 device->host copies + 4 MPI_Sendrecv + 3 MPI_Allreduce
(``poisson_mpi_cuda2.cu:846-939``). The XLA while_loop path already
collapses that to ~8 fused kernels with zero host traffic; this module
collapses it to **zero per-iteration kernel boundaries**: one
``pallas_call`` holds the whole ``lax.while_loop``, with every operand
and iterate living in VMEM for the entire solve. HBM is touched exactly
twice — operands in at entry, solution out at exit.

This is the design point the chip's memory system rewards: the bench
part has ~128 MB of VMEM (measured; ``vmem_limit_bytes`` raised
accordingly), so grids whose ~17-array working set fits the 125 MB
residency budget — everything up to roughly 1100x1650, which covers the
reference's 400x600 and 800x1200 headline grids (``fits_resident`` is
the exact gate) — run the whole solve on-chip, where iteration cost is
pure VPU arithmetic (measured 3.5 us/iter @ 400x600, 7.9 @ 800x1200,
14.5 @ 1100x1650) instead of the ~40-75 us/iter the kernel-per-op
structure costs. Grids that don't fit fall back to the streamed
whole-solve kernel (``ops.streamed_pcg``) — ``solver.engine`` picks.

Arithmetic is the stencil form shared with ``fused_pcg`` (coefficients
pre-divided by h^2 and pre-masked to the interior, differences first;
the preconditioner a multiply by a precomputed guarded 1/D), with the same
rotated loop whose value sequence matches the reference order
(``stage0/Withoutopenmp1.cpp:124-169``). The z iterate is eliminated
algebraically (p = r*Dinv + beta*p), which drops one resident array and
one store per iteration; verified to preserve the published
iteration-count oracles in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.fused_pcg import fused_operands
from poisson_ellipse_tpu.solver.pcg import DENOM_GUARD, PCGResult
from poisson_ellipse_tpu.utils.device import scaled_vmem_budget

# Measured usable VMEM on the 128 MiB bench part (minus compiler
# reserves); scaled to the actual device's capacity at the use sites
# via ``utils.device.scaled_vmem_budget`` (device_kind-keyed table).
_VMEM_LIMIT = 127 * 1024 * 1024
_RESIDENT_BUDGET = 125 * 1024 * 1024
# Empirical working-set envelope: operands (5 coeffs + rhs) + scratch
# state (w, r, p) + w_out + ~6 Mosaic temporaries during the whole-array
# stencil/update expressions. Chip-measured with the scratch-state
# kernel, then still holding a 6th coefficient (D): 1100x1650 (17
# arrays = 124.9 MB) compiles and converges; 1200x1800 (157.7 MB) fails
# Mosaic allocation — hence BUDGET=125 MB.
_ARRAYS_RESIDENT = 17


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def padded_shape(problem: Problem) -> tuple[int, int]:
    g1, g2 = problem.node_shape
    return _round_up(g1, 8), _round_up(g2, 128)


def fits_resident(problem: Problem, dtype=jnp.float32, device=None) -> bool:
    """True if the whole solve's working set fits on-chip (on ``device``'s
    VMEM capacity; default: the default-backend device)."""
    g1p, g2p = padded_shape(problem)
    need = _ARRAYS_RESIDENT * g1p * g2p * jnp.dtype(dtype).itemsize
    return need <= scaled_vmem_budget(_RESIDENT_BUDGET, device)


def _shift_rows_down(x):
    """Row i-1 (zero row at the top: the ring is zero)."""
    zero = jnp.zeros((1, x.shape[1]), x.dtype)
    return jnp.concatenate([zero, x[:-1]], axis=0)


def _shift_rows_up(x):
    zero = jnp.zeros((1, x.shape[1]), x.dtype)
    return jnp.concatenate([x[1:], zero], axis=0)


def _shift_cols_right(x):
    zero = jnp.zeros((x.shape[0], 1), x.dtype)
    return jnp.concatenate([zero, x[:, :-1]], axis=1)


def _shift_cols_left(x):
    zero = jnp.zeros((x.shape[0], 1), x.dtype)
    return jnp.concatenate([x[:, 1:], zero], axis=1)


def _mega_kernel(h1, h2, delta, weighted, max_iter,
                 an, as_, bw, be, dinv, r0,
                 w_out, iters_out, diff_out, flags_out,
                 w_s, r_s, p_s):
    """The full PCG solve. Runs as a single grid-less invocation.

    State (w, r, p) lives in mutable VMEM scratch and the while_loop
    carries only scalars: carrying arrays would make Mosaic double-buffer
    them (an extra full-array copy each per iteration and ~3 more
    resident arrays of budget). In-place updates are value-safe on the
    breakdown path because alpha is forced to 0 there — w + 0·p and
    r − 0·ap are bitwise w and r, the reference's exit-before-touching
    semantics (``stage0/Withoutopenmp1.cpp:128``); p is rotated-loop
    state and is never read after exit.
    """
    dtype = r0.dtype
    an_v = an[...]
    as_v = as_[...]
    bw_v = bw[...]
    be_v = be[...]
    dinv_v = dinv[...]
    r_init = r0[...]

    h1h2 = jnp.asarray(h1 * h2, dtype)
    z0 = r_init * dinv_v
    zr0 = jnp.sum(z0 * r_init) * h1h2

    w_s[...] = jnp.zeros_like(r_init)
    r_s[...] = r_init
    p_s[...] = jnp.zeros_like(r_init)   # beta0 = 0 -> p1 = z0

    carry0 = (
        jnp.asarray(0, jnp.int32),
        zr0,
        jnp.asarray(0.0, dtype),       # beta
        jnp.asarray(jnp.inf, dtype),   # diff
        jnp.asarray(False),
        jnp.asarray(False),
    )

    def cond(c):
        k, _zr, _b, _d, conv, bd = c
        return (k < max_iter) & ~conv & ~bd

    def body(c):
        k, zr, beta, diff, _cv, _bd = c
        pn = r_s[...] * dinv_v + beta * p_s[...]
        p_s[...] = pn
        ap = (
            an_v * (pn - _shift_rows_down(pn))
            + as_v * (pn - _shift_rows_up(pn))
            + bw_v * (pn - _shift_cols_right(pn))
            + be_v * (pn - _shift_cols_left(pn))
        )
        denom = jnp.sum(ap * pn) * h1h2
        breakdown = denom < DENOM_GUARD
        alpha = zr / jnp.where(breakdown, jnp.ones_like(denom), denom)
        alpha = jnp.where(breakdown, jnp.zeros_like(alpha), alpha)

        w = w_s[...]
        w_new = w + alpha * pn
        r_new = r_s[...] - alpha * ap
        w_s[...] = w_new
        r_s[...] = r_new
        # realised increment (w_new - w), not alpha*p: the convergence
        # oracle counts depend on the FP difference (cu:626-660)
        dw = w_new - w
        dw2 = jnp.sum(dw * dw)
        # two VPU reductions over VMEM-resident values inside ONE Mosaic
        # kernel: no collective and no HBM pass exists to fuse away
        # tpulint: disable=TPU007
        zr_new = jnp.sum((r_new * dinv_v) * r_new) * h1h2

        ndiff = jnp.sqrt(dw2 * h1h2) if weighted else jnp.sqrt(dw2)
        conv = ~breakdown & (ndiff < delta)
        ndiff = jnp.where(breakdown, diff, ndiff)
        beta_new = jnp.where(breakdown, beta, zr_new / zr)
        zr_out = jnp.where(breakdown, zr, zr_new)
        return (k + 1, zr_out, beta_new, ndiff, conv, breakdown)

    out = lax.while_loop(cond, body, carry0)
    w_out[...] = w_s[...]
    iters_out[0] = out[0]
    diff_out[0] = out[3]
    flags_out[0] = out[4].astype(jnp.int32)
    flags_out[1] = out[5].astype(jnp.int32)


def build_resident_solver(problem: Problem, dtype=jnp.float32,
                          interpret=None, geometry=None, theta=None):
    """(jitted whole-solve kernel, args) for a grid that fits VMEM.

    args are the f64-rounded normalised operands + RHS (the same operand
    set as ``fused_pcg.build_fused_solver``), so the two paths are
    value-identical where both apply.
    """
    import numpy as np

    if jnp.dtype(dtype).itemsize >= 8:
        raise ValueError("resident solver supports f32/bf16")
    if not fits_resident(problem, dtype):
        raise ValueError(
            f"grid {problem.M}x{problem.N} exceeds the VMEM-resident "
            "budget; use the streamed engine (ops.streamed_pcg) or let "
            "solver.engine pick"
        )
    if interpret is None:
        interpret = _interpret_default()
    g1, g2 = problem.node_shape
    g1p, g2p = padded_shape(problem)

    coeffs = fused_operands(problem, g1p, g2p, dtype, geometry=geometry,
                            theta=theta)
    _, _, rhs64 = assembly.assemble_numpy(problem, geometry=geometry,
                                          theta=theta)
    np_dtype = np.dtype(jnp.dtype(dtype).name)
    r0 = jnp.asarray(
        np.pad(rhs64, ((0, g1p - g1), (0, g2p - g2))).astype(np_dtype)
    )
    args = (*coeffs, r0)

    kernel = functools.partial(
        _mega_kernel,
        float(problem.h1), float(problem.h2), float(problem.delta),
        problem.norm == "weighted", problem.max_iterations,
    )
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    call = pl.pallas_call(
        kernel,
        in_specs=[vmem()] * 6,
        out_specs=(vmem(), smem(), smem(), smem()),
        out_shape=(
            jax.ShapeDtypeStruct((g1p, g2p), dtype),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), dtype),
            jax.ShapeDtypeStruct((2,), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((g1p, g2p), dtype),  # w
            pltpu.VMEM((g1p, g2p), dtype),  # r
            pltpu.VMEM((g1p, g2p), dtype),  # p
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=scaled_vmem_budget(_VMEM_LIMIT)
        ),
        interpret=interpret,
    )

    def solver(*operands):
        w_pad, iters, diff, flags = call(*operands)
        return PCGResult(
            w=w_pad[:g1, :g2],
            iters=iters[0],
            diff=diff[0],
            converged=flags[0].astype(bool),
            breakdown=flags[1].astype(bool),
        )

    return jax.jit(solver), args


def solve_resident(problem: Problem, dtype=jnp.float32,
                   interpret=None) -> PCGResult:
    """Assemble and solve entirely on-chip (single kernel)."""
    solver, args = build_resident_solver(problem, dtype, interpret=interpret)
    return solver(*args)
