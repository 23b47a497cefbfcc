"""Mesh-sharded mg-pcg / cheb-pcg: the V-cycle under shard_map.

The same classical sharded PCG loop as ``parallel.pcg_sharded`` — the
scalar-collective cadence is UNTOUCHED: one denom psum plus ONE stacked
convergence-word psum per iteration, exactly the classical discipline —
with the preconditioner swapped for the layout-generic V-cycle /
Chebyshev cores of ``mg`` running on per-shard blocks. Every piece of
preconditioner communication is a nearest-neighbour halo exchange
(``parallel.halo.halo_extend`` — 4 ``lax.ppermute``): Chebyshev steps
pay one halo per stencil application, transfers one halo each (the
9-point full-weighting gather and the odd-node bilinear straddle both
reach exactly one cell across the shard edge). ``halos_per_precond``
is the static budget; ``tests/test_mg.py`` pins the jaxpr's psum AND
ppermute counts against it via ``obs.static_cost``.

Level geometry: the fine node grid pads to a multiple of
``(px·2^{L−1}, py·2^{L−1})`` so every level's shard block stays even
and node-nested (coarse local (ic, jc) at fine local (2ic, 2jc) on the
same device — coarsening never moves data between shards). Level
coefficients are coarsened on the HOST in f64 from the same hierarchy
the single-chip engine uses (``mg.coarsen.coefficient_hierarchy`` — one
coarsening, two layouts), padded per level and laid out over the mesh.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from poisson_ellipse_tpu.mg import cheby, coarsen as mg_coarsen, vcycle
from poisson_ellipse_tpu.mg.transfer import prolong_block, restrict_block
from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.stencil import (
    apply_a_block,
    apply_dinv,
    diag_d_block,
)
from poisson_ellipse_tpu.parallel.halo import halo_extend
from poisson_ellipse_tpu.parallel.mesh import AXIS_X, AXIS_Y, make_mesh
from poisson_ellipse_tpu.parallel.pcg_sharded import (
    _shard_advance,
    _shard_init,
    _shard_ops,
)
from poisson_ellipse_tpu.solver.pcg import PCGResult


def halos_per_precond(levels: int, nu: int = vcycle.DEFAULT_NU,
                      coarse_degree: int = vcycle.DEFAULT_COARSE_DEGREE,
                      ) -> int:
    """Halo exchanges one preconditioner application costs (each is 4
    ppermutes). Per non-coarsest level: ν−1 pre-smooth applies + 1
    residual + 1 restrict + 1 prolong + ν post-smooth applies = 2ν+2;
    coarsest: degree−1 applies. The static budget the jaxpr pin checks."""
    if levels == 1:
        return coarse_degree - 1
    return (levels - 1) * (2 * nu + 2) + coarse_degree - 1


def mg_padded_dims(problem: Problem, mesh: Mesh, levels: int,
                   ) -> tuple[int, int]:
    """Fine padded dims divisible by (px·2^{L−1}, py·2^{L−1}).

    M divisible by 2^{L−1} (the level-count rule) makes the rounded-up
    size automatically ≥ M + 2^{L−1}, so every level's padded grid
    covers its node grid: g1p/2ˡ ≥ M/2ˡ + 1."""
    px = mesh.shape[AXIS_X]
    py = mesh.shape[AXIS_Y]
    ux = px << (levels - 1)
    uy = py << (levels - 1)
    g1, g2 = problem.node_shape
    return (-(-g1 // ux)) * ux, (-(-g2 // uy)) * uy


def _interior_mask(Ml: int, Nl: int, gi, gj):
    """Interior mask of a level's GLOBAL node grid at block indices
    (zeros the Dirichlet ring and all shard padding)."""
    return (
        ((gi >= 1) & (gi <= Ml - 1))[:, None]
        & ((gj >= 1) & (gj <= Nl - 1))[None, :]
    )


class _MgShardSetup:
    """Everything the mesh-preconditioned loop needs, factored once so
    the whole-solve form and the chunked stepper (the guard's resumable
    surface) cannot drift: level operands laid out over the mesh, the
    per-shard precond factory, and the geometry."""

    def __init__(self, problem: Problem, mesh: Mesh, dtype, kind: str,
                 config, geometry=None, theta=None):
        from poisson_ellipse_tpu.mg.engine import resolve_config

        if kind not in ("mg", "cheb"):
            raise ValueError(f"unknown preconditioner kind: {kind!r}")
        a0, b0, rhs0 = assembly.assemble(problem, dtype, geometry=geometry,
                                         theta=theta)
        cfg = config if config is not None else resolve_config(
            problem, a0, b0, rhs0, kind
        )
        # a supplied config with the dataclass-default degenerate interval
        # (lo=0.0) falls back to the Gershgorin interval instead of
        # crashing the Chebyshev setup at trace time — same stance as
        # mg.engine
        lo, hi = cheby.clip_interval((cfg.lo, cfg.hi))
        if (lo, hi) != (cfg.lo, cfg.hi):
            cfg = dataclasses.replace(cfg, lo=lo, hi=hi)
        self.problem = problem
        self.mesh = mesh
        self.dtype = dtype
        self.kind = kind
        self.cfg = cfg
        self.levels = cfg.levels if kind == "mg" else 1
        self.hier = mg_coarsen.coefficient_hierarchy(
            problem, geometry=geometry, theta=theta
        )[:self.levels]
        self.px = mesh.shape[AXIS_X]
        self.py = mesh.shape[AXIS_Y]
        self.interpret = mesh.devices.flat[0].platform != "tpu"
        self.g1p, self.g2p = mg_padded_dims(problem, mesh, self.levels)
        self.bm, self.bn = self.g1p // self.px, self.g2p // self.py
        self.spec = P(AXIS_X, AXIS_Y)
        sharding = NamedSharding(mesh, self.spec)
        np_dtype = assembly.numpy_dtype(dtype)

        def _pad_to(arr, r, c):
            return np.pad(
                arr, ((0, r - arr.shape[0]), (0, c - arr.shape[1]))
            )

        # fine operands + one (a, b) pair per level, each padded to its
        # own level dims (divisible by the mesh by construction), sharded
        args = [
            jax.device_put(
                _pad_to(arr, self.g1p, self.g2p).astype(np_dtype), sharding
            )
            for arr in (self.hier[0]["a"], self.hier[0]["b"],
                        assembly.assemble_numpy(problem, geometry=geometry,
                                                theta=theta)[2])
        ]
        for l in range(1, self.levels):
            for key in ("a", "b"):
                args.append(jax.device_put(
                    _pad_to(
                        self.hier[l][key], self.g1p >> l, self.g2p >> l
                    ).astype(np_dtype),
                    sharding,
                ))
        self.args = tuple(args)
        self.smooth_lo, self.smooth_hi = cheby.smoother_interval(cfg.hi)

    def extend_levels(self, a_blk, b_blk, level_blks):
        """One halo exchange per level's coefficients, once per dispatch
        (the loop and the V-cycle reuse the extended blocks)."""
        px, py = self.px, self.py
        level_exts = [(halo_extend(a_blk, px, py),
                       halo_extend(b_blk, px, py))]
        for l in range(1, self.levels):
            al, bl = level_blks[2 * (l - 1)], level_blks[2 * (l - 1) + 1]
            level_exts.append((halo_extend(al, px, py),
                               halo_extend(bl, px, py)))
        return level_exts

    def level_ops(self, level_exts) -> list[vcycle.LevelOps]:
        """Block-layout LevelOps from the halo-extended per-level
        coefficient blocks — the raw per-level closures both cycle
        shapes compose: ``make_precond`` into the V-cycle preconditioner
        and ``build_fmg_sharded_solver`` into the F-cycle."""
        px, py, bm, bn = self.px, self.py, self.bm, self.bn
        hier, cfg, dtype = self.hier, self.cfg, self.dtype
        smooth_lo, smooth_hi = self.smooth_lo, self.smooth_hi
        ops = []
        for l, (a_ext, b_ext) in enumerate(level_exts):
            Ml, Nl = hier[l]["M"], hier[l]["N"]
            h1 = jnp.asarray(hier[l]["h1"], dtype)
            h2 = jnp.asarray(hier[l]["h2"], dtype)
            bml, bnl = bm >> l, bn >> l
            ix = lax.axis_index(AXIS_X)
            iy = lax.axis_index(AXIS_Y)
            gi = ix * bml + jnp.arange(bml, dtype=jnp.int32)
            gj = iy * bnl + jnp.arange(bnl, dtype=jnp.int32)
            mask = _interior_mask(Ml, Nl, gi, gj).astype(dtype)
            d = jnp.where(
                mask.astype(bool), diag_d_block(a_ext, b_ext, h1, h2), 0.0
            )
            last = l == len(level_exts) - 1

            def make_apply(a_ext=a_ext, b_ext=b_ext, h1=h1, h2=h2,
                           mask=mask):
                return lambda x: (
                    apply_a_block(halo_extend(x, px, py), a_ext, b_ext,
                                  h1, h2) * mask
                )

            def make_dinv(d=d):
                return lambda x: apply_dinv(x, d)

            if last:
                restrict = prolong = None
            else:
                Mc, Nc = hier[l + 1]["M"], hier[l + 1]["N"]
                bmc, bnc = bml // 2, bnl // 2
                gic = ix * bmc + jnp.arange(bmc, dtype=jnp.int32)
                gjc = iy * bnc + jnp.arange(bnc, dtype=jnp.int32)
                cmask = _interior_mask(Mc, Nc, gic, gjc).astype(dtype)

                def restrict(r, cmask=cmask):
                    return restrict_block(halo_extend(r, px, py)) * cmask

                def prolong(ec, mask=mask, shape=(bml, bnl)):
                    return prolong_block(
                        halo_extend(ec, px, py), shape
                    ) * mask

            ops.append(vcycle.LevelOps(
                apply_a=make_apply(),
                dinv=make_dinv(),
                smooth_lo=smooth_lo,
                smooth_hi=cfg.hi,
                solve_lo=min(cfg.lo * (4.0 ** l), smooth_hi / 4.0),
                restrict=restrict,
                prolong=prolong,
            ))
        return ops

    def make_precond(self, level_exts):
        """The per-shard ``z = M⁻¹ r`` applier: the block LevelOps
        composed into the generic V-cycle core (or the standalone
        Chebyshev polynomial for kind="cheb")."""
        cfg = self.cfg
        ops = self.level_ops(level_exts)
        if self.kind == "cheb":
            fine = ops[0]
            return lambda r: cheby.chebyshev_apply(
                fine.apply_a, fine.dinv, r, cfg.lo, cfg.hi, cfg.cheb_degree
            )
        return vcycle.make_vcycle(
            ops, nu=cfg.nu, coarse_degree=cfg.coarse_degree
        )


def build_mg_sharded_solver(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    kind: str = "mg",
    config=None,
    history: bool = False,
    geometry=None,
    theta=None,
):
    """(jitted solver_fn, args) for the mesh-sharded preconditioned solve.

    ``kind`` "mg" (V-cycle) or "cheb" (degree-k polynomial). The
    spectral interval comes from the same single-chip Lanczos probe the
    single-chip engines use (the operator — and so its spectrum — is
    mesh-independent), the hierarchy from the same host-f64 coarsening.
    Args are the per-level (a, b) arrays plus the fine RHS, all padded
    and laid out over the mesh.
    """
    if mesh is None:
        mesh = make_mesh()
    setup = _MgShardSetup(problem, mesh, dtype, kind, config,
                          geometry=geometry, theta=theta)
    px, py, bm, bn = setup.px, setup.py, setup.bm, setup.bn
    interpret = setup.interpret
    spec = setup.spec
    args = setup.args

    out_specs = (spec, P(), P(), P(), P()) + ((P(),) * 4 if history else ())

    def shard_fn(a_blk, b_blk, rhs_blk, *level_blks):
        level_exts = setup.extend_levels(a_blk, b_blk, level_blks)
        precond = setup.make_precond(level_exts)
        stencil, pdot, d, _maskd = _shard_ops(
            problem, px, py, bm, bn, level_exts[0][0], level_exts[0][1],
            dtype, "xla", interpret,
        )
        state0 = _shard_init(
            problem, px, py, bm, bn, pdot, d, rhs_blk, dtype,
            history=history, precond=precond,
        )
        out = _shard_advance(
            problem, stencil, pdot, d, state0, dtype, history=history,
            precond=precond,
        )
        k, w = out[0], out[1]
        diff, converged, breakdown = out[5], out[6], out[7]
        return (w, k, diff, converged, breakdown) + tuple(out[8:])

    mapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec,) * len(args),
        out_specs=out_specs,
    )

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
            from poisson_ellipse_tpu.obs.convergence import trace_of

            return result, trace_of(out[5:], k)
        return result

    return jax.jit(solver), args


def build_mg_sharded_stepper(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    kind: str = "mg",
    config=None,
    abft: bool = False,
):
    """(init_fn, advance_fn, recover_fn) for chunked/resumable
    mesh-preconditioned solves — the ``parallel.pcg_sharded.
    build_sharded_stepper`` contract with the V-cycle/Chebyshev in the
    ``z = M⁻¹r`` slot, which is what lets ``resilience.guard`` chunk,
    health-check and recover mg-pcg/cheb-pcg mesh solves exactly like
    the classical stepper (carry layout is shared; only the preconditioner
    and the per-level operands differ). ``abft=True`` appends the four
    ABFT shadow scalars and runs the in-loop SDC checks at the same
    collective cadence (``resilience.abft``).

    ``recover_fn`` is the true-residual restart under the SAME M —
    z and zr are rebuilt through the preconditioner, so the restarted
    recurrence still describes M⁻¹A (the guard's parity contract).
    """
    if mesh is None:
        mesh = make_mesh()
    setup = _MgShardSetup(problem, mesh, dtype, kind, config)
    px, py, bm, bn = setup.px, setup.py, setup.bm, setup.bn
    interpret = setup.interpret
    spec = setup.spec
    args = setup.args
    scalar = P()
    state_specs = (scalar, spec, spec, spec, scalar, scalar, scalar, scalar)
    if abft:
        state_specs = state_specs + (scalar,) * 4
    n_level_args = len(args) - 3

    def init_shard(a_blk, b_blk, rhs_blk, *level_blks):
        level_exts = setup.extend_levels(a_blk, b_blk, level_blks)
        precond = setup.make_precond(level_exts)
        _stencil, pdot, d, _maskd = _shard_ops(
            problem, px, py, bm, bn, level_exts[0][0], level_exts[0][1],
            dtype, "xla", interpret,
        )
        return _shard_init(
            problem, px, py, bm, bn, pdot, d, rhs_blk, dtype,
            precond=precond, abft=abft,
        )

    def advance_shard(a_blk, b_blk, state, limit, *level_blks):
        from poisson_ellipse_tpu.resilience.abft import checksum_field

        level_exts = setup.extend_levels(a_blk, b_blk, level_blks)
        precond = setup.make_precond(level_exts)
        stencil, pdot, d, maskd = _shard_ops(
            problem, px, py, bm, bn, level_exts[0][0], level_exts[0][1],
            dtype, "xla", interpret,
        )
        c = checksum_field(stencil, maskd) if abft else None
        return _shard_advance(
            problem, stencil, pdot, d, state, dtype, limit=limit,
            precond=precond, abft=abft, abft_c=c,
        )

    def recover_shard(a_blk, b_blk, rhs_blk, state, *level_blks):
        level_exts = setup.extend_levels(a_blk, b_blk, level_blks)
        precond = setup.make_precond(level_exts)
        stencil, pdot, _d, _maskd = _shard_ops(
            problem, px, py, bm, bn, level_exts[0][0], level_exts[0][1],
            dtype, "xla", interpret,
        )
        k, w, _r, p, _zr, diff, _c, _bd = state[:8]
        r2 = rhs_blk - stencil(w)
        z2 = precond(r2)
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

    level_specs = (spec,) * n_level_args
    # no donation on any half: operands are re-fed every chunk and the
    # carry doubles as the guard's rollback point
    init_mapped = jax.jit(shard_map(
        init_shard,
        mesh=mesh,
        in_specs=(spec, spec, spec) + level_specs,
        out_specs=state_specs,
    ))
    advance_mapped = jax.jit(shard_map(
        advance_shard,
        mesh=mesh,
        in_specs=(spec, spec, state_specs, scalar) + level_specs,
        out_specs=state_specs,
    ))
    recover_mapped = jax.jit(shard_map(
        recover_shard,
        mesh=mesh,
        in_specs=(spec, spec, spec, state_specs) + level_specs,
        out_specs=state_specs,
    ))

    def init_fn():
        return init_mapped(*args[:3], *args[3:])

    def advance_fn(state, limit):
        return advance_mapped(
            args[0], args[1], state, jnp.asarray(limit, jnp.int32),
            *args[3:],
        )

    def recover_fn(state):
        return recover_mapped(args[0], args[1], args[2], state, *args[3:])

    return init_fn, advance_fn, recover_fn


def solve_mg_sharded(problem: Problem, mesh: Mesh | None = None,
                     dtype=jnp.float32, kind: str = "mg",
                     history: bool = False):
    """Assemble, shard and solve with the mesh V-cycle/Chebyshev."""
    solver, args = build_mg_sharded_solver(
        problem, mesh, dtype, kind=kind, history=history
    )
    return solver(*args)


# -- full multigrid (the F-cycle solver), sharded ----------------------------


def halos_per_fcycle(levels: int, nu: int = vcycle.DEFAULT_NU,
                     coarse_degree: int = vcycle.DEFAULT_COARSE_DEGREE,
                     n_vcycles: int = 2) -> int:
    """Halo exchanges one sharded F-cycle costs (each 4 ppermutes) —
    the static collective budget the jaxpr pin in ``tests/test_fmg.py``
    checks via ``obs.static_cost``. Per level l < L−1: one RHS restrict
    + one prolong + n_vcycles × (1 residual apply + the V-cycle over
    levels[l:]); coarsest: the degree−1 direct sweep. The F-cycle adds
    ZERO scalar collectives — psums stay the handoff loop's classical
    cadence, exactly the mg-pcg discipline."""
    if levels == 1:
        return coarse_degree - 1
    total = coarse_degree - 1  # the coarsest direct sweep
    for l in range(levels - 1):
        total += 2  # restrict f_l down + prolong x_{l+1} up
        total += n_vcycles * (1 + halos_per_precond(
            levels - l, nu, coarse_degree
        ))
    return total


def build_fmg_sharded_solver(
    problem: Problem,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    config=None,
    geometry=None,
    theta=None,
):
    """(jitted solver_fn, args) for the mesh-sharded full-multigrid solve.

    The F-cycle of ``mg.fmg`` over the block LevelOps of
    :class:`_MgShardSetup` — per-level transfers and smoothing steps pay
    one halo exchange each (``halos_per_fcycle`` is the pinned budget),
    never a scalar collective — followed by the verified handoff: the
    classical sharded mg-pcg loop warm-started at the F-cycle solution
    (``_shard_init(x0_blk=...)`` rebuilds the TRUE per-shard residual),
    running to the same δ rule as every other engine. Level padding,
    coarsening and the Lanczos interval are exactly the mg-pcg setup's.

    ``config`` is an ``mg.fmg.FMGConfig`` (None: grid-derived defaults
    with the probed interval).
    """
    from poisson_ellipse_tpu.mg.fmg import (
        FMGConfig,
        make_fcycle,
        resolve_fmg_config,
    )

    if mesh is None:
        mesh = make_mesh()
    a0, b0, rhs0 = assembly.assemble(problem, dtype, geometry=geometry,
                                     theta=theta)
    fmg_cfg = resolve_fmg_config(problem, a0, b0, rhs0, config)
    assert isinstance(fmg_cfg, FMGConfig)
    setup = _MgShardSetup(problem, mesh, dtype, "mg",
                          fmg_cfg.precond_config(), geometry=geometry,
                          theta=theta)
    px, py, bm, bn = setup.px, setup.py, setup.bm, setup.bn
    interpret = setup.interpret
    spec = setup.spec
    args = setup.args

    def shard_fn(a_blk, b_blk, rhs_blk, *level_blks):
        level_exts = setup.extend_levels(a_blk, b_blk, level_blks)
        ops = setup.level_ops(level_exts)
        x0 = make_fcycle(
            ops, nu=fmg_cfg.nu, coarse_degree=fmg_cfg.coarse_degree,
            n_vcycles=fmg_cfg.n_vcycles,
        )(rhs_blk)
        precond = vcycle.make_vcycle(
            ops, nu=fmg_cfg.nu, coarse_degree=fmg_cfg.coarse_degree
        )
        stencil, pdot, d, _maskd = _shard_ops(
            problem, px, py, bm, bn, level_exts[0][0], level_exts[0][1],
            dtype, "xla", interpret,
        )
        state0 = _shard_init(
            problem, px, py, bm, bn, pdot, d, rhs_blk, dtype,
            precond=precond, x0_blk=x0, stencil=stencil,
        )
        out = _shard_advance(
            problem, stencil, pdot, d, state0, dtype, precond=precond,
        )
        k, w = out[0], out[1]
        diff, converged, breakdown = out[5], out[6], out[7]
        return (w, k, diff, converged, breakdown)

    mapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec,) * len(args),
        out_specs=(spec, P(), P(), P(), P()),
    )

    def solver(*arrays):
        w_pad, k, diff, converged, breakdown = mapped(*arrays)
        return PCGResult(
            w=w_pad[: problem.M + 1, : problem.N + 1],
            iters=k,
            diff=diff,
            converged=converged,
            breakdown=breakdown,
        )

    return jax.jit(solver), args
