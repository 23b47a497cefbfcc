"""Mesh-sharded s-step PCG: one s-deep halo + ONE psum per s iterations.

The communication ledger, per PCG iteration, engine by engine:

  classical (``pcg_sharded``)        4 ppermute + 2 psum
  pipelined (``pipelined_sharded``)  4 ppermute + 1 psum (stacked)
  s-step (here, s ∈ {2, 4})          4/s ppermute + **1/s psum**

One outer body advances s iterations (``ops.sstep_pcg``): it exchanges
ONE s-deep halo round — the (p, r, x) triple stacked into a single
4-ppermute slab exchange (``parallel.halo.halo_extend_stacked``; x rides
along so the residual-replacement rebuild ``r = rhs − A·x`` is local,
keeping the loop body's collective count independent of the replacement
cond) — builds the matrix-powers basis by applying the masked stencil
chain against per-depth interior masks and diagonals (all loop-invariant,
computed from the deep coefficient halos exchanged once per dispatch,
OUTSIDE the loop), reduces both Gram matrices plus the ABFT partials in
one stacked ``lax.psum``, and runs the s coordinate-space iterations
replicated (``ops.sstep_pcg.sstep_inner`` — zero further collectives).
The "exactly 1 psum + 4 ppermute per while body (= per s iterations)"
claim is jaxpr-pinned via ``obs.static_cost`` in ``tests/test_sstep.py``.
(With a sub-compute ``storage_dtype`` the exchange is one cell deeper —
(s+1) — so the p = z direction restart of ``ops.sstep_pcg`` stays local;
the collective *count* is unchanged.)

The carry layout is the classical sharded one — (k, w, r, p, zr, diff,
converged, breakdown) with (bm, bn) blocks and replicated scalars — so
``_shard_init``, ``build_sharded_recover`` and the guard's sharded
adapter machinery apply unchanged, and the ABFT shadow tail reuses
``resilience.abft``'s (S_r, S_w, S_p_pred, sdc) slots at block
granularity: shadow recurrences predict next-block column sums through
the basis coordinates (Σp⁺ = Σₘ p_c[m]·σₘ with σₘ = Σ basisₘ — the σ/τ
column-sum vectors ride the SAME Gram psum), and psum corruption is
caught by Gram-diagonal positivity (the diagonals are sums of squares:
a sign-flipped reduction is structurally negative). Both detectors ride
the existing collective — the zero-extra-collective ABFT stance of
``resilience.abft``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.precision import (
    load as _pload,
    replace_every,
    resolve_storage_dtype,
    store as _pstore,
)
from poisson_ellipse_tpu.ops.sstep_pcg import (
    BASIS_SCALE,
    DEFAULT_S,
    SSTEP_CHOICES,
    basis_size,
    gram_dtype,
    shift_matrix,
    sstep_inner,
)
from poisson_ellipse_tpu.ops.stencil import apply_a_block, apply_dinv, diag_d_block
from poisson_ellipse_tpu.parallel.halo import halo_extend, halo_extend_stacked
from poisson_ellipse_tpu.parallel.mesh import AXIS_X, AXIS_Y, make_mesh, padded_dims
from poisson_ellipse_tpu.parallel.pcg_sharded import (
    _host_sharded_args,
    _shard_init,
    _shard_ops,
    sharded_result_of,
)
from poisson_ellipse_tpu.resilience.abft import ABFT_TINY, abft_rtol


def _crop(arr, m: int):
    """Remove ``m`` halo cells from each side of a 2-D block."""
    return arr[m:-m, m:-m] if m else arr


def make_sstep_parts(problem, mesh, dtype, s, storage_dtype=None,
                       abft: bool = False, geometry=None, theta=None):
    """Shared plumbing for the solver and stepper forms: per-shard init
    and block-advance closures over one mesh decomposition."""
    if s not in SSTEP_CHOICES:
        raise ValueError(f"s must be one of {SSTEP_CHOICES}, got {s}")
    if mesh is None:
        mesh = make_mesh()
    st = resolve_storage_dtype(storage_dtype, dtype)
    cadence = replace_every(st, dtype)
    # exchange depth: s for the basis; one deeper under sub-compute
    # storage so the p = z restart's z is available at depth s locally
    w_ex = s + (1 if st is not None else 0)
    zd = w_ex - 1  # the residual/z₀ depth
    px = mesh.shape[AXIS_X]
    py = mesh.shape[AXIS_Y]
    g1p, g2p = padded_dims(problem.node_shape, mesh)
    bm, bn = g1p // px, g2p // py
    if w_ex >= min(bm, bn):
        raise ValueError(
            f"{w_ex}-deep halos need blocks deeper than that; got "
            f"{bm}x{bn} blocks on a {px}x{py} mesh"
        )
    spec = P(AXIS_X, AXIS_Y)
    scalar = P()
    state_specs = (scalar, spec, spec, spec, scalar, scalar, scalar, scalar)
    if abft:
        state_specs = state_specs + (scalar,) * 4
    K = basis_size(s)
    iz = s + 1
    Bm = shift_matrix(s, dtype)
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    hw = h1 * h2
    delta = jnp.asarray(problem.delta, dtype)
    weighted = problem.norm == "weighted"
    rtol = jnp.asarray(abft_rtol(st if st is not None else dtype), dtype)

    def depth_fields(a_deep, b_deep):
        """Per-depth loop-invariant (interior mask, masked diagonal) for
        q ∈ [0, w_ex−1] — global indices, locally computable (out-of-
        range indices fall outside the interior, the device-assembly
        convention)."""
        ix = lax.axis_index(AXIS_X)
        iy = lax.axis_index(AXIS_Y)
        masks, diags = [], []
        for q in range(w_ex):
            gi = ix * bm - q + jnp.arange(bm + 2 * q, dtype=jnp.int32)
            gj = iy * bn - q + jnp.arange(bn + 2 * q, dtype=jnp.int32)
            interior = assembly.interior_mask(problem, gi, gj)
            a_q1 = _crop(a_deep, w_ex - q - 1)  # depth q+1: diag's extent
            b_q1 = _crop(b_deep, w_ex - q - 1)
            d_q = jnp.where(interior, diag_d_block(a_q1, b_q1, h1, h2), 0.0)
            masks.append(interior.astype(dtype))
            diags.append(d_q)
        return masks, diags

    def init_shard(a_blk, b_blk, rhs_blk):
        a_ext = halo_extend(a_blk, px, py)
        b_ext = halo_extend(b_blk, px, py)
        _stencil, pdot, d, _maskd = _shard_ops(
            problem, px, py, bm, bn, a_ext, b_ext, dtype, "xla", False
        )
        state = _shard_init(
            problem, px, py, bm, bn, pdot, d, rhs_blk, dtype, abft=abft
        )
        if st is not None:
            state = (state[0],) + tuple(
                _pstore(v, st) for v in state[1:4]
            ) + state[4:]
        return state

    def advance_shard(a_blk, b_blk, rhs_blk, state, limit):
        # deep coefficient halos: exchanged once per DISPATCH, outside
        # the while body — per-depth masks/diags derive locally
        a_deep = halo_extend(a_blk, px, py, width=w_ex)
        b_deep = halo_extend(b_blk, px, py, width=w_ex)
        masks, diags = depth_fields(a_deep, b_deep)
        # rhs at the replacement rebuild's depth, also outside the loop
        rhs_ext = (
            halo_extend(rhs_blk, px, py, width=zd) if zd else rhs_blk
        )
        max_iter = jnp.minimum(
            jnp.asarray(limit, jnp.int32), problem.max_iterations
        )
        scale = jnp.asarray(1.0 / BASIS_SCALE, dtype)

        def chain(v_ext, q_in):
            """One Â = D⁻¹A application down the halo chain: depth q_in
            in, masked preconditioned depth q_in−1 out."""
            q = q_in - 1
            a_q = _crop(a_deep, w_ex - q_in)
            b_q = _crop(b_deep, w_ex - q_in)
            out = apply_a_block(v_ext, a_q, b_q, h1, h2) * masks[q]
            return apply_dinv(out, diags[q])

        def cond(state):
            k, converged, breakdown = state[0], state[6], state[7]
            go = (k < max_iter) & ~converged & ~breakdown
            if abft:
                # a flagged carry stops at once (the classical stance)
                go = go & ~state[11]
            return go

        def body(state):
            k, x_sv, r_sv, p_sv, _zr, diff0, conv0, bd0 = state[:8]
            x_own = _pload(x_sv, dtype, st)
            r_own = _pload(r_sv, dtype, st)
            p_own = _pload(p_sv, dtype, st)

            # THE block's halo round: (p, r, x) as one stacked deep slab
            # exchange — 4 ppermutes per s iterations
            ext = halo_extend_stacked(
                jnp.stack([p_own, r_own, x_own]), px, py, width=w_ex
            )
            p_ext = _crop(ext[0], w_ex - s)  # depth s: the basis root
            r_ext, x_ext = ext[1], ext[2]

            # residual replacement, entirely local: x travelled at depth
            # w_ex, so A·x is computable at depth zd without another
            # round. Containment form (a block whose s iterations span
            # a cadence multiple fires), not block-start equality —
            # chunk limits re-anchor block starts off the s-grid, and
            # an equality test would then never fire again
            km = k % cadence
            do = (k > 0) & ((km == 0) | (km > cadence - s))

            def replaced(_):
                ax = apply_a_block(
                    x_ext, a_deep, b_deep, h1, h2
                ) * masks[zd]
                return rhs_ext - ax

            r_base = lax.cond(
                do, replaced, lambda _: _crop(r_ext, 1), None
            )  # depth zd

            z0 = apply_dinv(r_base, diags[zd])
            p0 = p_ext
            if st is not None:
                # sub-compute storage: pair the tightened cadence with a
                # full p = z restart (ops.sstep_pcg's measured stance);
                # z0 is at depth s here (zd = s), so the restart is local
                p0 = jnp.where(do, z0, p0)

            # matrix-powers chains (masked, preconditioned, ρ-scaled)
            vs = [p0]
            for q in range(s, 0, -1):
                vs.append(chain(vs[-1], q) * scale)
            zs = [z0]
            for q in range(zd, zd - (s - 1), -1):
                zs.append(chain(zs[-1], q) * scale)
            # owned crops, stacked: (K, bm, bn)
            V = jnp.stack([_crop(v, (v.shape[0] - bm) // 2) for v in vs + zs])
            d0 = diags[0]
            # Gram partials accumulate at gram_dtype (f64 under x64) —
            # the measured s=4 parity requirement (ops.sstep_pcg
            # .gram_dtype); the widened entries ride the SAME psum (K²
            # scalars — collective count unchanged, bytes negligible)
            gd = gram_dtype(dtype)
            Vg = V.astype(gd)
            Vd = Vg * d0.astype(gd)

            # the block's ONE stacked psum: both Gram partials (+ ABFT)
            gm_loc = jnp.einsum("kij,lij->kl", Vg, Vd)
            ge_loc = jnp.einsum("kij,lij->kl", Vg, Vg)
            parts = [gm_loc.ravel(), ge_loc.ravel()]
            if abft:
                sigma_loc = jnp.sum(Vg, axis=(1, 2))      # σ: Σ basisₘ
                tau_loc = jnp.sum(Vd, axis=(1, 2))        # τ: Σ D·basisₘ
                extras = jnp.stack([
                    jnp.sum(x_own), jnp.sum(jnp.abs(x_own)),
                    jnp.sum(jnp.abs(p_own)), jnp.sum(jnp.abs(r_own)),
                ]).astype(gd)
                parts += [sigma_loc, tau_loc, extras]
            sums = lax.psum(jnp.concatenate(parts), (AXIS_X, AXIS_Y))
            Gm = sums[: K * K].reshape(K, K) * hw.astype(gd)
            Ge = sums[K * K : 2 * K * K].reshape(K, K)

            k_n, x_c, z_c, p_c, zr_n, diff_n, conv_n, bd_n = sstep_inner(
                Gm, Ge, Bm.astype(gd), s, k, max_iter, delta.astype(gd),
                hw.astype(gd), weighted, diff0.astype(gd), conv0, bd0, gd,
            )
            zr_n, diff_n = zr_n.astype(dtype), diff_n.astype(dtype)

            x_new = x_own + jnp.tensordot(x_c.astype(dtype), V, axes=1)
            z_new = jnp.tensordot(z_c.astype(dtype), V, axes=1)
            r_new = d0 * z_new
            p_new = jnp.tensordot(p_c.astype(dtype), V, axes=1)
            out = (
                k_n,
                _pstore(x_new, st), _pstore(r_new, st), _pstore(p_new, st),
                zr_n, diff_n, conv_n, bd_n,
            )
            if abft:
                S_r, S_x, S_p, sdc = state[8], state[9], state[10], state[11]
                off = 2 * K * K
                sigma = sums[off : off + K]
                tau = sums[off + K : off + 2 * K]
                s_x, s_absx = sums[off + 2 * K], sums[off + 2 * K + 1]
                s_absp, s_absr = sums[off + 2 * K + 2], sums[off + 2 * K + 3]
                # block-start checks against last block's predictions:
                # Σp = σ₀, Σr = τ_z₀ (r = D·z₀; skipped on replacement —
                # the rebuild legitimately changes r), Σx directly.
                # Written as ~(drift ≤ tol): NaN must read as violation.
                # Under sub-compute storage the replacement block ALSO
                # restarts p = z (the measured bf16 stance), so its Σp
                # legitimately breaks the prediction — skipped there.
                p_restarted = do if st is not None else jnp.asarray(False)
                ok_p = p_restarted | (
                    jnp.abs(sigma[0] - S_p) <= rtol * (s_absp + ABFT_TINY)
                )
                ok_r = do | (
                    jnp.abs(tau[iz] - S_r) <= rtol * (s_absr + ABFT_TINY)
                )
                ok_x = jnp.abs(s_x - S_x) <= rtol * (s_absx + ABFT_TINY)
                # Gram diagonals are sums of squares: a sign-flipped psum
                # (psum_corrupt) is structurally negative
                ok_gram = jnp.all(jnp.diagonal(Gm) >= 0.0) & jnp.all(
                    jnp.diagonal(Ge) >= 0.0
                )
                fault = ~bd_n & ~(ok_p & ok_r & ok_x & ok_gram)
                # next-block predictions through the coordinates
                keep = lambda old, new: jnp.where(bd_n, old, new)
                out = out + (
                    keep(S_r, (z_c @ tau).astype(dtype)),
                    keep(S_x, (s_x + x_c @ sigma).astype(dtype)),
                    keep(S_p, (p_c @ sigma).astype(dtype)),
                    sdc | fault,
                )
            return out

        # full-f32 contractions on a TPU (see ops.sstep_pcg.advance)
        with jax.default_matmul_precision("highest"):
            return lax.while_loop(cond, body, state)

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
    args = _host_sharded_args(problem, mesh, dtype, g1p, g2p, spec,
                              geometry=geometry, theta=theta)

    def init_fn(*arrays):
        use = arrays if arrays else args
        return init_mapped(*use[:3])

    def advance_fn(state, limit, arrays=None):
        use = arrays if arrays is not None else args
        lim = problem.max_iterations if limit is None else limit
        return advance_mapped(
            use[0], use[1], use[2], state, jnp.asarray(lim, jnp.int32)
        )

    return init_fn, advance_fn, args


def build_sstep_sharded_solver(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    s: int = DEFAULT_S,
    storage_dtype=None,
    geometry=None,
    theta=None,
):
    """(jitted solver, args) for the whole mesh-sharded s-step solve.

    Args are the host-assembled (a, b, rhs) laid out over the mesh (the
    ``pcg_sharded`` "host" assembly mode); the result is a
    ``PCGResult`` with the shard padding cropped.
    """
    init_fn, advance_fn, args = make_sstep_parts(
        problem, mesh, dtype, s=s, storage_dtype=storage_dtype,
        geometry=geometry, theta=theta,
    )

    def solver(*arrays):
        state = advance_fn(init_fn(*arrays), None, arrays)
        return sharded_result_of(problem, state)

    return jax.jit(solver), args


def build_sstep_sharded_stepper(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    s: int = DEFAULT_S,
    abft: bool = False,
    storage_dtype=None,
):
    """(init_fn, advance_fn) for chunked/guarded sharded s-step solves.

    Same contract as ``pcg_sharded.build_sharded_stepper`` — classical
    carry layout, traced ``limit`` honoured exactly (a mid-block limit
    masks the remaining inner steps and the next dispatch re-anchors the
    basis) — so the guard's sharded adapter, ``build_sharded_recover``
    and the checkpoint machinery compose unchanged. ``abft=True``
    appends the (S_r, S_w, S_p_pred, sdc) shadow tail (module
    docstring), anchored by ``_shard_init`` and re-anchored by
    ``build_sharded_recover`` exactly like the classical stepper's.
    """
    init_fn, advance_fn, _args = make_sstep_parts(
        problem, mesh, dtype, s=s, abft=abft, storage_dtype=storage_dtype
    )

    def init():
        return init_fn()

    def advance(state, limit):
        return advance_fn(state, limit)

    return init, advance


def solve_sstep_sharded(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    s: int = DEFAULT_S,
    storage_dtype=None,
):
    """Assemble, shard and solve over the mesh with the s-step engine."""
    solver, args = build_sstep_sharded_solver(
        problem, mesh, dtype, s=s, storage_dtype=storage_dtype
    )
    return solver(*args)
