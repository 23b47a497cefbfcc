"""s-step (communication-avoiding) PCG: s iterations per HBM/collective round.

The pipelined recurrence (``ops.pipelined_pcg``) got the iteration down
to ONE fused reduction; s-step CG (Chronopoulos & Gear 1989; the basis/
Gram formulation of Carson & Demmel 2013) goes below one: it advances
**s iterations per matrix-powers round**. One round

1. builds the monomial Krylov basis of the preconditioned operator
   Â = D⁻¹A from the current direction and residual,
       V = [p, Âp, …, Â^s p,  z, Âz, …, Â^{s-1} z]    (K = 2s+1 vectors)
   — for the 5-point stencil this is a cheap s-deep-halo kernel: the
   sharded form exchanges ONE s-deep halo and applies the stencil chain
   locally (``parallel.sstep_sharded``);
2. computes two small Gram matrices in ONE stacked reduction —
   Gm = h₁h₂·VᵀDV (the M-inner products: zr and the α-denominator are
   its quadratic forms) and Ge = VᵀV (the ‖Δx‖ step norm) — so the
   sharded form issues exactly ONE ``lax.psum`` per s iterations
   (vs 1/iter pipelined, 2/iter classical; jaxpr-pinned);
3. runs s CG iterations **in coordinates**: every iterate the inner
   steps touch stays in span(V), Â becomes the K×K shift matrix
   :func:`shift_matrix`, and α/β/convergence are O(K²) scalar work —
   no array passes, no reductions, no collectives;
4. reconstructs (x, r, p) from the coordinate vectors (one contraction
   against V) and rounds to storage width if a ``storage_dtype`` is set
   (``ops.precision`` — both bandwidth levers compose).

Monomial-basis round-off (the classical s-step hazard: powers of Â
align and the Gram system loses digits) is answered by the SAME
residual-replacement discipline the pipelined engine uses: every
:func:`~poisson_ellipse_tpu.ops.precision.replace_every` iterations the
block start rebuilds r = rhs − A·x from ground truth (both cadences
divide both block sizes, so a replacement always lands on a block
boundary), and s is capped at 4 — the measured-stable regime for this
operator family. Iteration counts land within the pipelined engine's
±2-style envelope of the classical oracle (asserted in
``tests/test_sstep.py``); bitwise parity remains the classical engines'
contract.

Convergence/breakdown semantics inside a block mirror the classical
loop per iteration: the (Ap⁺, p⁺) breakdown guard applies to the
coordinate-form denominator, a breakdown iteration discards its update
and exits, a converged iteration freezes p/zr, and the iteration count
includes the body that fired the exit. A chunk limit (``advance``'s
``limit``) is honoured exactly — the block's remaining inner steps are
masked off and the next dispatch re-anchors the basis at the boundary —
so guard chunking and fault injection stop at exact iterations; the
re-anchor makes chunked runs iteration-equivalent, not bitwise, to
straight runs (documented trade; the classical engines keep the bitwise
contract).

The carry layout IS the classical one — (k, x, r, p, zr, diff,
converged, breakdown) — so ``solver.checkpoint``, the guard's recovery
(``resilience.guard``), and the sharded reshard machinery apply
unchanged.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.precision import (
    load as _load,
    replace_every,
    resolve_storage_dtype,
    store as _store,
)
from poisson_ellipse_tpu.ops.stencil import apply_a, apply_dinv, diag_d
from poisson_ellipse_tpu.solver.pcg import (
    DENOM_GUARD,
    PCGResult,
    init_state as _classical_init,
    result_of,
)

# block sizes the engine supports: s=2 (conservative) and s=4 (the
# bandwidth headline). Both divide both residual-replacement cadences
# (32 f32 / 8 bf16), so replacements land on block boundaries.
SSTEP_CHOICES = (2, 4)
DEFAULT_S = 4


def basis_size(s: int) -> int:
    """K = 2s+1: s+1 powers of the direction, s of the residual."""
    return 2 * s + 1


# Per-power basis scaling: each stored basis vector is Â^j v / ρ^j with
# ρ = BASIS_SCALE. Gershgorin bounds λmax(D⁻¹A) ≤ 2 for this operator
# family (the same cap ``mg.cheby`` leans on), so ρ = 2 keeps the
# monomial columns' norms from growing with the power — a communication-
# free stabiliser (a norm-scaled basis would cost a reduction per power,
# which is exactly what this engine exists to avoid).
BASIS_SCALE = 2.0


def gram_dtype(compute_dtype):
    """The Gram accumulation dtype: f64 when x64 is available, else the
    compute dtype.

    Measured at 400×600 f32 (the stiff κ≈8e4 operator): an f32-
    accumulated Gram loses the digits the s=4 coordinate recurrence
    needs near convergence — 773 iterations vs the 546 oracle — while
    an f64 Gram restores EXACT classical parity. The f64 work is K²
    output scalars plus a widened accumulator over arrays that still
    stream at storage width (the convert fuses into the reduction), so
    the byte model is untouched; on x64-disabled processes the engine
    degrades to the f32 Gram (s=2 stays at exact parity there — its
    5-vector Gram holds the digits; s=4 trades iterations, documented).
    A Chebyshev–Leja Newton basis was measured and does NOT recover
    this (748 iters): the loss is accumulation round-off, not basis
    conditioning.
    """
    import jax

    if jax.config.jax_enable_x64 and jnp.dtype(compute_dtype).itemsize < 8:
        # gated on x64 the line above: never a silent downcast
        return jnp.float64  # tpulint: disable=TPU001
    return jnp.dtype(compute_dtype)


def shift_matrix(s: int, dtype=jnp.float32):
    """The K×K matrix B with coords(Â·v) = B·coords(v) for every vector
    the inner iterations can produce — the ρ-scaled monomial basis
    shifts each power to the next with weight ρ (p-part indices 0…s,
    z-part indices s+1…2s). Iteration j ≤ s−1 touches p-degree ≤ j and
    z-degree ≤ j−1, so the shift never falls off the basis (the
    Carson–Demmel degree bound)."""
    K = basis_size(s)
    B = np.zeros((K, K))
    for i in range(s):
        B[i + 1, i] = BASIS_SCALE
    for i in range(s - 1):
        B[s + 2 + i, s + 1 + i] = BASIS_SCALE
    return jnp.asarray(B, dtype)


def init_state(problem: Problem, a, b, rhs, storage_dtype=None):
    """The s-step carry at iteration 0 — exactly the classical carry
    (``solver.pcg.init_state``, no history tail)."""
    return _classical_init(problem, a, b, rhs, storage_dtype=storage_dtype)


def sstep_inner(Gm, Ge, Bm, s, k, limit, delta, hw, weighted,
                diff0, conv0, bd0, dtype):
    """The s masked CG iterations in K-dimensional coordinates.

    Pure scalar/K-vector work on the replicated Gram matrices — shared
    verbatim by the single-chip and sharded engines, which is what makes
    the sharded collective cadence 1 psum per s iterations: nothing in
    here reduces over the grid.

    Returns (k, x_c, z_c, p_c, zr, diff, converged, breakdown) with the
    classical per-iteration semantics (masked, so a mid-block exit or a
    chunk ``limit`` freezes the remaining steps).
    """
    K = Gm.shape[0]
    iz = s + 1
    x_c = jnp.zeros((K,), dtype)
    z_c = jnp.zeros((K,), dtype).at[iz].set(1.0)
    p_c = jnp.zeros((K,), dtype).at[0].set(1.0)
    # zr re-derived from the Gram diagonal: (z, r) = zᵀDz = Gm[z₀,z₀]
    zr = Gm[iz, iz]
    conv, bd, diff = conv0, bd0, diff0
    for _ in range(s):
        active = ~conv & ~bd & (k < limit)
        ap_c = Bm @ p_c
        denom = p_c @ (Gm @ ap_c)
        bd_fire = active & (denom < DENOM_GUARD)
        alpha = zr / jnp.where(denom < DENOM_GUARD, 1.0, denom)
        x_n = x_c + alpha * p_c
        z_n = z_c - alpha * ap_c
        zr_n = z_n @ (Gm @ z_n)
        # Ge is PSD up to round-off; clamp so a −ε quadratic form at the
        # storage floor cannot surface as a NaN step norm
        dw2 = alpha * alpha * jnp.maximum(p_c @ (Ge @ p_c), 0.0)
        diff_n = jnp.sqrt(dw2 * hw) if weighted else jnp.sqrt(dw2)
        conv_n = diff_n < delta
        beta = zr_n / jnp.where(zr == 0.0, 1.0, zr)
        p_n = z_n + beta * p_c
        upd = active & ~bd_fire
        k = k + active.astype(jnp.int32)
        x_c = jnp.where(upd, x_n, x_c)
        z_c = jnp.where(upd, z_n, z_c)
        diff = jnp.where(upd, diff_n, diff)
        adv = upd & ~conv_n
        p_c = jnp.where(adv, p_n, p_c)
        zr = jnp.where(adv, zr_n, zr)
        conv = conv | (upd & conv_n)
        bd = bd | bd_fire
    return k, x_c, z_c, p_c, zr, diff, conv, bd


def advance(problem: Problem, a, b, rhs, state, s: int = DEFAULT_S,
            limit=None, stencil: str = "xla", interpret=None,
            storage_dtype=None):
    """Advance the s-step carry until convergence/breakdown or iteration
    ``limit`` (honoured exactly — see module docstring on the mid-block
    re-anchor)."""
    if s not in SSTEP_CHOICES:
        raise ValueError(f"s must be one of {SSTEP_CHOICES}, got {s}")
    dtype = rhs.dtype
    st = resolve_storage_dtype(storage_dtype, dtype)
    cadence = replace_every(st, dtype)
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    hw = h1 * h2
    delta = jnp.asarray(problem.delta, dtype)
    weighted = problem.norm == "weighted"
    max_iter = (
        problem.max_iterations
        if limit is None
        else jnp.minimum(jnp.asarray(limit, jnp.int32),
                         problem.max_iterations)
    )
    d = diag_d(a, b, h1, h2)
    a_s, b_s = (_store(a, st), _store(b, st)) if st is not None else (a, b)
    d_s = _store(d, st) if st is not None else d

    if stencil == "pallas":
        if st is not None:
            from poisson_ellipse_tpu.ops.pallas_kernels import (
                apply_a_mixed_pallas,
            )

            def apply_stencil(v):
                return apply_a_mixed_pallas(
                    v, a_s, b_s, problem.h1, problem.h2,
                    compute_dtype=dtype, interpret=interpret,
                )

        else:
            from poisson_ellipse_tpu.ops.pallas_kernels import apply_a_pallas

            def apply_stencil(v):
                return apply_a_pallas(v, a, b, problem.h1, problem.h2,
                                      interpret=interpret)

    elif stencil == "xla":

        def apply_stencil(v):
            return apply_a(v, _load(a_s, dtype, st), _load(b_s, dtype, st),
                           h1, h2)

    else:
        raise ValueError(f"unknown stencil: {stencil!r}")

    def dinv(v):
        return apply_dinv(v, _load(d_s, dtype, st))

    def ahat(v):
        return dinv(apply_stencil(v))

    Bm = shift_matrix(s, dtype)

    def cond(state):
        k, converged, breakdown = state[0], state[6], state[7]
        return (k < max_iter) & ~converged & ~breakdown

    def body(state):
        k, x_sv, r_sv, p_sv, _zr, diff0, conv0, bd0 = state[:8]
        x = _load(x_sv, dtype, st)
        r = _load(r_sv, dtype, st)
        p = _load(p_sv, dtype, st)

        # residual replacement on the recurrence cadence: a block whose
        # s iterations CONTAIN a cadence multiple rebuilds r from
        # ground truth — the monomial basis's drift bound AND the
        # storage axis's (tightened cadence under bf16). Phrased as
        # containment, not block-start alignment: a chunk limit or
        # fault stop mid-block re-anchors block starts off the s-grid,
        # and an equality test would then never fire again for the
        # rest of the solve
        km = k % cadence
        do = (k > 0) & ((km == 0) | (km > cadence - s))
        r = lax.cond(do, lambda _: rhs - apply_stencil(x), lambda _: r, None)

        # matrix-powers basis: one stencil chain, no reductions
        z = dinv(r)
        if st is not None:
            # sub-compute storage: the direction reconstructed through a
            # storage-rounded basis accumulates drift the p-preserving
            # replacement cannot clear (measured: bf16+monomial climbs);
            # the tightened cadence pairs with a full p = z restart —
            # the ~25% iteration tax applies only to the replaced blocks
            # of the low-precision phase, which the guard's promotion
            # rung bounds anyway
            p = jnp.where(do, z, p)
        scale = jnp.asarray(1.0 / BASIS_SCALE, dtype)
        vs = [p]
        for _ in range(s):
            vs.append(ahat(vs[-1]) * scale)
        zs = [z]
        for _ in range(s - 1):
            zs.append(ahat(zs[-1]) * scale)
        V = jnp.stack(vs + zs)  # (K, M+1, N+1)

        # the block's ONE stacked reduction: both Gram matrices from a
        # single pass over V (D is diagonal, zero outside the interior,
        # so full-grid sums equal interior sums — the reduction-layout
        # invariant). Accumulation at gram_dtype (f64 under x64): the
        # measured parity requirement — the convert fuses into the
        # reduction, so V still streams at storage width
        d_c = _load(d_s, dtype, st)
        gd = gram_dtype(dtype)
        Vg = V.astype(gd)
        Gm = jnp.einsum("kij,lij->kl", Vg, Vg * d_c.astype(gd)) * hw.astype(gd)
        Ge = jnp.einsum("kij,lij->kl", Vg, Vg)

        k_n, x_c, z_c, p_c, zr_n, diff_n, conv_n, bd_n = sstep_inner(
            Gm, Ge, Bm.astype(gd), s, k, max_iter, delta.astype(gd),
            hw.astype(gd), weighted, diff0.astype(gd), conv0, bd0, gd,
        )
        x_c, z_c, p_c = (
            x_c.astype(dtype), z_c.astype(dtype), p_c.astype(dtype)
        )
        zr_n, diff_n = zr_n.astype(dtype), diff_n.astype(dtype)

        # reconstruct in full space (one contraction against the basis);
        # r = D·z exactly — the diagonal preconditioner's inverse pair
        x_new = x + jnp.tensordot(x_c, V, axes=1)
        z_new = jnp.tensordot(z_c, V, axes=1)
        r_new = d_c * z_new
        p_new = jnp.tensordot(p_c, V, axes=1)
        return (
            k_n,
            _store(x_new, st), _store(r_new, st), _store(p_new, st),
            zr_n, diff_n, conv_n, bd_n,
        )

    # the Gram matrices, the K-space recurrence and the reconstruction
    # are contractions: on a TPU their default precision is one bf16
    # pass, which costs the recurrence its f32 parity (measured on the
    # chip: 61 iterations for the 50-iteration 40x40 oracle)
    with jax.default_matmul_precision("highest"):
        return lax.while_loop(cond, body, state)


def pcg_sstep(problem: Problem, a, b, rhs, s: int = DEFAULT_S,
              stencil: str = "xla", interpret=None, storage_dtype=None):
    """Run s-step PCG for pre-assembled coefficients ((M+1, N+1) grids).

    Jit-safe with ``problem``/``s`` static; the while_loop advances s
    iterations per body over the classical carry layout. ``stencil``
    "xla" or "pallas" (the basis chain through the per-op kernel; with a
    ``storage_dtype`` the mixed kernels — storage-width HBM tiles,
    compute-width VMEM math). Returns a :class:`PCGResult`.
    """
    state = advance(
        problem, a, b, rhs,
        init_state(problem, a, b, rhs, storage_dtype=storage_dtype),
        s=s, stencil=stencil, interpret=interpret,
        storage_dtype=storage_dtype,
    )
    return result_of(state)


def solve(problem: Problem, dtype=jnp.float32, s: int = DEFAULT_S,
          stencil: str = "xla", interpret=None, storage_dtype=None):
    """Assemble and solve on a single chip with the s-step recurrence."""
    a, b, rhs = assembly.assemble(problem, dtype)
    return pcg_sstep(problem, a, b, rhs, s=s, stencil=stencil,
                     interpret=interpret, storage_dtype=storage_dtype)
