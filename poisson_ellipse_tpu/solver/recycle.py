"""Krylov recycling: deflated warm starts for correlated solve streams.

The fleet's request mix is not i.i.d. — the same geometry family, grid
bucket and ε recur — yet only *executables* were amortized (warm pool,
autotuner); the math restarted cold every solve. Deflated/recycled CG
(Saad et al. 2000; Parks et al., GCRODR, 2006) fixes that: project out
the extremal modes that survive the diag/mg preconditioners — exactly
the cut-cell outliers the fictitious-domain blend creates and the
degenerate-cut clamp leaves behind — and the next related solve starts
past the part of the spectrum that was costing the iterations.

Pipeline, host-orchestrated around unchanged device loops:

1. **Capture** — the solve carries a bounded on-device ring of its
   Lanczos basis vectors (:func:`ring_init` / :func:`ring_record`, the
   same ``dynamic_update_slice`` discipline as ``obs.convergence``'s
   history buffers; ``recycle=None`` traces the byte-identical ringless
   loop). CG's preconditioned residuals ARE the Lanczos basis of M⁻¹A
   in the M-inner product up to sign and scale —
   v_{j+1} = (−1)^j z_j/√(z_j,r_j) — both already computed by the loop,
   so each slot is one scaled store of an array the body materialises
   anyway, in step-for-step alignment with the tridiagonal the trace's
   α/β coefficients reconstruct.
2. **Harvest** (:func:`harvest`, host-side) — ``obs.spectrum``'s
   ``ritz_decomposition`` (truncated to the ring's steps) gives the
   T_m eigenpairs; the ``extremal_indices`` rule picks the k outliers;
   W = P·Y turns the stored directions into approximate extremal Ritz
   vectors of M⁻¹A. Approximate is fine: the deflation below is an
   exact Galerkin projection onto span(W) *whatever* W is — basis
   quality buys iteration cut, never correctness.
3. **Deflate** (:func:`deflated_x0`) — the next related solve starts at
   ``x0 += W (WᵀAW)⁻¹ Wᵀ r₀``, fed through the existing
   ``init_state(x0=...)`` path, whose TRUE-residual initialisation
   (r = rhs − A·x0) verifies the seed instead of trusting it. A stale
   or poisoned basis therefore costs iterations, never a wrong answer
   (:func:`check_warm_start` flags those hits as ``recycle:bad-hit``).

The sharded form keeps the 1-stacked-psum/iteration discipline: the k
deflation dots Wᵀr₀ ride ONE stacked psum at init, outside the loop
(:func:`build_deflated_sharded_init`), contract-checked as the
``recycle`` capability row of ``analysis.contracts`` — the hot loop's
collective cadence is byte-identical to the undeflated solve.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs import spectrum
from poisson_ellipse_tpu.obs import trace as obs_trace
from poisson_ellipse_tpu.ops.stencil import apply_a

# Default on-device ring capacity (Lanczos vectors stored) and deflation
# rank harvested from it. Deflating a mode with tiny θ amplifies any
# basis inaccuracy by the spectral spread, so the extremal pairs must be
# CONVERGED Ritz pairs before they pay: measured at 128², a 16-slot ring
# (11% of the 150-iteration solve) leaves λ_min at ~6e-3 relative
# eigen-residual and the warm start *loses* iterations, while 64 slots
# turn an ε=1% correlated follow-up from 80 iterations (plain warm
# start) into 1. Rule of thumb the default encodes: cap ≥ ~40% of the
# expected iteration count, k well under cap. Memory is cap full grids
# at compute width (ring_model_bytes) — opt-in per solve, so the big
# grids simply pass a smaller cap.
RECYCLE_CAP = 64
RECYCLE_K = 8

# A warm start whose true relative residual exceeds this is WORSE than
# starting cold (‖r₀‖/‖rhs‖ = 1 exactly at x0 = 0): a semantic-cache
# miss dressed as a hit. It still converges — init_state verifies by
# true residual — but the event lets the fleet see the cache misbehaving.
BAD_HIT_RATIO = 1.0

# Gram matrices (WᵀAW) more ill-conditioned than this mean the harvested
# directions were numerically dependent; the projection would amplify
# noise, so the harvest declines and the next solve runs cold.
GRAM_COND_LIMIT = 1e12


# -- on-device ring (the capture half) ---------------------------------------


def ring_init(problem: Problem, cap: int, dtype) -> jax.Array:
    """The zeroed (cap, M+1, N+1) Lanczos-vector ring carried through
    the solve loop — one full-grid slot per stored basis vector, at
    compute width (the harvest's Gram algebra needs the accuracy).
    ``init_state`` seeds slot 0 with v₁ = z₀/√(z₀,r₀)."""
    return jnp.zeros((int(cap),) + tuple(problem.node_shape), dtype)


def ring_record(ring: jax.Array, slot, v, valid) -> jax.Array:
    """Scatter Lanczos vector ``v`` into ``slot``, first ``cap`` slots
    only, skipped (slot kept) when ``valid`` is False.

    Same ``dynamic_update_slice`` discipline as ``obs.convergence``'s
    history buffers — pure on-device stores, nothing the loop waits on.
    Past the capacity the write degenerates to rewriting slot cap−1
    with its own value: slots stay step-aligned with the Lanczos
    reconstruction (slot j ↔ basis vector v_{j+1}) instead of wrapping
    into a misaligned window.
    """
    cap = ring.shape[0]
    s = jnp.minimum(slot, cap - 1)
    zero = jnp.zeros((), s.dtype)
    keep = lax.dynamic_slice(ring, (s, zero, zero), (1,) + ring.shape[1:])
    rec = jnp.where(
        valid & (slot < cap), v[None].astype(ring.dtype), keep
    )
    return lax.dynamic_update_slice(ring, rec, (s, zero, zero))


def ring_model_bytes(
    problem: Problem, cap: int = RECYCLE_CAP, dtype=jnp.float32
) -> int:
    """Modeled HBM footprint of the direction ring — the `harness
    inspect` line (cap full grids at compute width)."""
    m, n = problem.node_shape
    return int(cap) * int(m) * int(n) * int(jnp.dtype(dtype).itemsize)


# -- harvest + deflation (the host-side half) --------------------------------


class DeflationBasis(NamedTuple):
    """One harvested recycling basis: k approximate extremal Ritz
    vectors W (grid-normalised), their images AW = A·W, the Gram matrix
    G = WᵀAW in the grid inner product, and the Ritz values they carry
    (diagnostics — the deflated-interval predictor's k).

    Tied to the (a, b) operator it was harvested from; a basis applied
    to a *different* operator is exactly the bad-hit case the
    true-residual init absorbs.
    """

    w: jax.Array  # (k, M+1, N+1)
    aw: jax.Array  # (k, M+1, N+1)
    gram: np.ndarray  # (k, k), symmetric
    thetas: np.ndarray  # (k,) harvested Ritz values, ascending
    h1: float
    h2: float

    @property
    def rank(self) -> int:
        return int(self.w.shape[0])

    @property
    def nbytes(self) -> int:
        return int(self.w.nbytes) + int(self.aw.nbytes)


def harvest(
    problem: Problem, a, b, trace, ring, k: int = RECYCLE_K
) -> DeflationBasis | None:
    """Build the k-mode deflation basis from one solve's trace + ring.

    The Lanczos reconstruction is truncated to the ring's capacity
    (T_j is itself the j-step Lanczos matrix, so the eigenpairs match
    the basis vectors actually stored); ``extremal_indices`` picks the
    same modes the deflated predictor removes. Returns None when the
    trace is too short to leave a deflated remainder (k ≥ m) or the
    Gram matrix says the stored basis was numerically dependent — the
    caller runs cold, which is always safe.
    """
    cap = int(ring.shape[0])
    thetas, y = spectrum.ritz_decomposition(trace, max_steps=cap)
    m = int(thetas.size)
    k = int(k)
    if k <= 0 or m == 0 or k >= m:
        return None
    dtype = ring.dtype
    idx = spectrum.extremal_indices(m, k)
    yk = jnp.asarray(np.ascontiguousarray(y[:, idx]), dtype)  # (m, k)
    w = jnp.einsum("mk,mij->kij", yk, ring[:m])
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    # grid-normalise each column: V·Y is M-orthonormal only up to the
    # ring's f32 rounding and truncation, and the Gram conditioning
    # check below must be scale-free (span unchanged)
    norms = jnp.sqrt(jnp.einsum("kij,kij->k", w, w) * h1 * h2)
    w = w / jnp.where(norms > 0, norms, 1.0)[:, None, None]
    aw = jax.vmap(lambda wi: apply_a(wi, a, b, h1, h2))(w)
    gram = np.asarray(
        jnp.einsum("kij,lij->kl", w, aw), dtype=np.float64
    ) * float(problem.h1) * float(problem.h2)
    gram = 0.5 * (gram + gram.T)
    if not np.all(np.isfinite(gram)):
        return None
    try:
        cond = np.linalg.cond(gram)
    except np.linalg.LinAlgError:
        return None
    if not np.isfinite(cond) or cond > GRAM_COND_LIMIT:
        return None
    return DeflationBasis(
        w=w,
        aw=aw,
        gram=gram,
        thetas=np.asarray(thetas[idx], dtype=np.float64),
        h1=float(problem.h1),
        h2=float(problem.h2),
    )


def deflated_x0(basis: DeflationBasis, rhs, x0=None, residual=None):
    """The deflated warm start ``x0 + W (WᵀAW)⁻¹ Wᵀ r₀``.

    ``r₀`` is ``rhs`` for the zero base (the common path), or the
    caller-supplied true ``residual`` when stacking on a nonzero ``x0``
    (a semantic-cache hit being deflated on top). The Galerkin solve is
    k×k host-side f64; a singular system returns None and the caller
    falls back to the undeflated start.
    """
    if residual is not None:
        r0 = residual
    elif x0 is None:
        r0 = rhs
    else:
        raise ValueError(
            "deflating on top of a nonzero x0 needs its TRUE residual "
            "(rhs - A@x0) — pass residual="
        )
    t = np.asarray(
        jnp.einsum("kij,ij->k", basis.w, r0), dtype=np.float64
    ) * basis.h1 * basis.h2
    try:
        c = np.linalg.solve(basis.gram, t)
    except np.linalg.LinAlgError:
        return None
    if not np.all(np.isfinite(c)):
        return None
    lift = jnp.einsum("k,kij->ij", jnp.asarray(c, rhs.dtype), basis.w)
    return lift if x0 is None else x0 + lift


def reproject_x0(problem: Problem, a, b, rhs, basis: DeflationBasis, w):
    """Restart-boundary re-projection: re-deflate a partially converged
    iterate against its TRUE residual (the guard's optional
    chunk-boundary hook — extremal components that CG reintroduced
    through rounding get projected back out). Returns ``w`` unchanged
    when the Galerkin solve declines."""
    dtype = rhs.dtype
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    r = rhs - apply_a(w, a, b, h1, h2)
    out = deflated_x0(basis, rhs, x0=w, residual=r)
    return w if out is None else out


# -- warm-start admission (the bad-hit contract) -----------------------------


def warm_start_ratio(problem: Problem, a, b, rhs, x0) -> float:
    """‖rhs − A·x0‖ / ‖rhs‖ — the true relative residual of a proposed
    warm start, computed eagerly at admission time (never inside a
    loop). 0 = already solved, 1 = no better than cold."""
    dtype = rhs.dtype
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    r = rhs - apply_a(x0, a, b, h1, h2)
    num = float(jnp.sqrt(jnp.sum(r * r)))
    den = float(jnp.sqrt(jnp.sum(rhs * rhs)))
    if den == 0.0:
        return math.inf if num > 0 else 0.0
    return num / den


def check_warm_start(
    problem: Problem, a, b, rhs, x0, source: str = "recycle",
    request_id: str | None = None,
):
    """Admit a proposed warm start, flagging bad hits.

    Returns ``(x0_to_use, ratio)``. A finite ratio keeps the seed even
    when it is worse than cold — the true-residual init makes a bad hit
    cost iterations, never correctness — but ratios over
    :data:`BAD_HIT_RATIO` emit a ``recycle:bad-hit`` trace event so the
    fleet can see a misbehaving cache without any solve going wrong. A
    non-finite seed (NaN/Inf contamination would poison the recurrence
    itself, not just the start) is dropped to a cold start, also
    flagged.
    """
    if x0 is None:
        return None, None
    ratio = warm_start_ratio(problem, a, b, rhs, x0)
    if not math.isfinite(ratio):
        obs_trace.event(
            "recycle:bad-hit", request_id=request_id, source=source,
            ratio=None, dropped=True,
        )
        return None, ratio
    if ratio > BAD_HIT_RATIO:
        obs_trace.event(
            "recycle:bad-hit", request_id=request_id, source=source,
            ratio=ratio, dropped=False,
        )
    return x0, ratio


# -- sharded deflated init (the 1-psum/iter discipline) ----------------------


def build_deflated_sharded_init(
    problem: Problem,
    mesh=None,
    dtype=jnp.float32,
    stencil_impl: str = "xla",
):
    """Jitted ``init_fn(a, b, rhs, w_basis, ginv) -> carry``: the
    sharded iteration-0 carry warm-started by a k-mode deflation basis.

    ``w_basis`` is the (k, g1p, g2p) basis sharded ``P(None, 'x', 'y')``
    (every device holds its block of every mode); ``ginv`` the
    replicated k×k inverse Gram (:func:`sharded_basis_args` builds
    both). The k deflation dots Wᵀ·rhs fold into ONE stacked psum — the
    same idiom as the loop's stacked convergence psum — so the whole
    deflated init costs exactly 2 psums (the stack + zr₀) for ANY k,
    and the loop it hands off to is byte-identical to the undeflated
    one: 1 denom psum + 1 stacked psum per iteration. Both facts are
    the ``recycle`` capability row of ``analysis.contracts``, pinned
    from the jaxpr.
    """
    from jax.sharding import PartitionSpec as P

    from poisson_ellipse_tpu.parallel.halo import halo_extend
    from poisson_ellipse_tpu.parallel.mesh import (
        AXIS_X,
        AXIS_Y,
        make_mesh,
        padded_dims,
    )
    from poisson_ellipse_tpu.parallel.pcg_sharded import (
        _shard_init,
        _shard_ops,
    )

    if mesh is None:
        mesh = make_mesh()
    px = mesh.shape[AXIS_X]
    py = mesh.shape[AXIS_Y]
    interpret = mesh.devices.flat[0].platform != "tpu"
    g1p, g2p = padded_dims(problem.node_shape, mesh)
    bm, bn = g1p // px, g2p // py
    spec = P(AXIS_X, AXIS_Y)
    scalar = P()
    basis_spec = P(None, AXIS_X, AXIS_Y)
    state_specs = (scalar, spec, spec, spec, scalar, scalar, scalar, scalar)

    def init_shard(a_blk, b_blk, rhs_blk, wb_blk, ginv):
        a_ext = halo_extend(a_blk, px, py)
        b_ext = halo_extend(b_blk, px, py)
        stencil, pdot, d, _maskd = _shard_ops(
            problem, px, py, bm, bn, a_ext, b_ext, dtype,
            stencil_impl, interpret,
        )
        h1 = jnp.asarray(problem.h1, dtype)
        h2 = jnp.asarray(problem.h2, dtype)
        # the k deflation dots Wᵀ·rhs as ONE stacked psum (the
        # convergence-word idiom — k partials, one collective); issued
        # here rather than parallel/ because the recycle contract cell
        # pins THIS init's psum count from the jaxpr — the budget the
        # collective-modules fence exists to protect is checked at the
        # source
        partials = jnp.einsum("kij,ij->k", wb_blk, rhs_blk)
        t = lax.psum(  # tpulint: disable=TPU020
            partials, (AXIS_X, AXIS_Y)
        ) * h1 * h2
        c = ginv @ t
        x0_blk = jnp.einsum("k,kij->ij", c, wb_blk)
        return _shard_init(
            problem, px, py, bm, bn, pdot, d, rhs_blk, dtype,
            x0_blk=x0_blk, stencil=stencil,
        )

    # no donation: the basis is the whole point of recycling — reused
    # across every solve of the correlated stream — and a/b/rhs are the
    # caller's long-lived sharded operands
    return jax.jit(shard_map(  # tpulint: disable=TPU004
        init_shard,
        mesh=mesh,
        in_specs=(spec, spec, spec, basis_spec, scalar),
        out_specs=state_specs,
        check_vma=not (stencil_impl == "pallas" and interpret),
    ))


def sharded_basis_args(basis: DeflationBasis, problem: Problem, mesh=None,
                       dtype=jnp.float32):
    """(w_basis, ginv) device arrays for
    :func:`build_deflated_sharded_init` — the basis zero-padded to the
    mesh's (g1p, g2p) shard grid and laid out ``P(None, 'x', 'y')``, and
    the k×k inverse Gram replicated. Zero padding is exact: padded nodes
    are outside every mode's support, so the folded dots see only real
    grid."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from poisson_ellipse_tpu.parallel.mesh import (
        AXIS_X,
        AXIS_Y,
        make_mesh,
        padded_dims,
    )

    if mesh is None:
        mesh = make_mesh()
    g1p, g2p = padded_dims(problem.node_shape, mesh)
    k, m, n = basis.w.shape
    w_pad = jnp.zeros((k, g1p, g2p), dtype)
    w_pad = w_pad.at[:, :m, :n].set(basis.w.astype(dtype))
    w_basis = jax.device_put(
        w_pad, NamedSharding(mesh, P(None, AXIS_X, AXIS_Y))
    )
    ginv = jax.device_put(
        jnp.asarray(np.linalg.inv(basis.gram), dtype),
        NamedSharding(mesh, P()),
    )
    return w_basis, ginv
