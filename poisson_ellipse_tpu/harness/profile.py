"""Segmented per-phase profiling: the stage4 timer classification, TPU-style.

Stage4 wraps every kernel launch, memcpy and collective in accumulators
``T_gpu / T_copy / T_mpi / T_prec / T_dot``
(``poisson_mpi_cuda2.cu:696-700,855-979``) — it can, because its loop is
fully synchronous. The TPU loop is one fused XLA computation, and a
per-dispatch replay would be swamped by the fixed dispatch + fence cost
(far above the ~20 µs an op takes), so each phase is measured by *chaining the op k times inside an on-device
``lax.fori_loop``* — one dispatch, k data-dependent applications. Phase map:

  reference          here               what is timed
  T_gpu (stencil)  → t_stencil          apply_A chained on the iterate
  T_prec           → t_precond          z = D⁻¹ r chained
  T_dot            → t_dot              inner product (+1 elementwise pass
                                        to carry the data dependency — a
                                        slight overestimate)
  (update kernels) → t_update           fused w/r axpy + ‖Δw‖² partial
  T_copy + T_mpi   → t_halo             halo ppermutes (sharded; ≡0 single)

There is no T_copy analog on the fast path at all: state never leaves the
device (the copies stage4 pays per iteration are exactly what this design
eliminates — BASELINE.json north star).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax import lax, shard_map

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.reduction import grid_dot
from poisson_ellipse_tpu.ops.stencil import apply_a, apply_dinv, diag_d
from poisson_ellipse_tpu.utils.timing import fence


def _time_chain(step, x0, reps: int) -> float:
    """Seconds per application of ``step``.

    Times two on-device ``fori_loop`` chains of k and 5k data-dependent
    applications and returns (t_5k − t_k)/4k: the difference cancels the
    constant dispatch + fence overhead that would otherwise swamp ops
    costing tens of µs.
    """

    def timed(n: int) -> float:
        # the chain length n is baked into the trace, so a fresh jit per
        # timed(n) is the protocol, not a leak: exactly two builds per
        # phase (k and 5k), each dispatched twice
        looped = jax.jit(  # tpulint: disable=TPU006
            lambda x: lax.fori_loop(0, n, lambda _, s: step(s), x)
        )
        out = looped(x0)  # compile + warm-up
        fence(out)
        t0 = time.perf_counter()
        out = looped(x0)
        fence(out)
        return time.perf_counter() - t0

    return max(timed(5 * reps) - timed(reps), 0.0) / (4 * reps)


def profile_single(problem: Problem, dtype=jnp.float32, reps: int = 200):
    """Per-op phase costs of one PCG iteration on one device."""
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    a, b, rhs = assembly.assemble(problem, dtype)
    d = diag_d(a, b, h1, h2)
    r = rhs
    z = apply_dinv(r, d)
    p = z
    ap = apply_a(p, a, b, h1, h2)
    alpha = jnp.asarray(1e-3, dtype)
    w = jnp.zeros_like(rhs)

    def update_step(state):
        w, r, s = state
        w_new = w + alpha * p
        r_new = r - alpha * ap
        dw = w_new - w
        return w_new, r_new, s + jnp.sum(dw * dw)

    phases = {
        "stencil": _time_chain(
            lambda u: apply_a(u, a, b, h1, h2), p, reps
        ),
        # scalar carry keeps the chain data-dependent; costs one extra
        # elementwise pass over the dot itself
        "dot": _time_chain(
            lambda s: grid_dot(p + s, p, h1, h2), jnp.asarray(0.0, dtype), reps
        ),
        "precond": _time_chain(lambda u: apply_dinv(u, d), r, reps),
        "update": _time_chain(
            update_step, (w, r, jnp.asarray(0.0, dtype)), reps
        ),
        "halo": 0.0,
    }
    return phases


def profile_sharded(
    problem: Problem, mesh=None, dtype=jnp.float32, reps: int = 200
):
    """Phase costs on the device mesh, including the halo ppermutes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from poisson_ellipse_tpu.parallel.halo import halo_extend
    from poisson_ellipse_tpu.parallel.mesh import (
        AXIS_X,
        AXIS_Y,
        make_mesh,
        padded_dims,
    )
    from poisson_ellipse_tpu.parallel.pcg_sharded import _pad_to
    from poisson_ellipse_tpu.ops.stencil import apply_a_block, diag_d_block

    if mesh is None:
        mesh = make_mesh()
    px = mesh.shape[AXIS_X]
    py = mesh.shape[AXIS_Y]
    g1p, g2p = padded_dims(problem.node_shape, mesh)
    spec = P(AXIS_X, AXIS_Y)
    sharding = NamedSharding(mesh, spec)

    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    a_np, b_np, rhs_np = assembly.assemble_numpy(problem)
    np_dtype = assembly.numpy_dtype(dtype)
    a, b, rhs = (
        jax.device_put(_pad_to(arr, g1p, g2p).astype(np_dtype), sharding)
        for arr in (a_np, b_np, rhs_np)
    )

    def chained(step_of_blocks, n: int):
        """shard_map a per-block step chained n times on device."""

        def blk_fn(u_blk, a_blk, b_blk):
            a_ext = halo_extend(a_blk, px, py)
            b_ext = halo_extend(b_blk, px, py)
            return lax.fori_loop(
                0, n, lambda _, s: step_of_blocks(s, a_ext, b_ext), u_blk
            )

        # no donation: the operands are re-fed on the second timed
        # dispatch of the (t_5k - t_k) protocol, so every input outlives
        # its call by design
        return jax.jit(  # tpulint: disable=TPU004
            shard_map(
                blk_fn,
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
            )
        )

    def time_fn(step_of_blocks, x0) -> float:
        # same overhead-cancelling (t_5k − t_k)/4k protocol as _time_chain
        def timed(n: int) -> float:
            fn = chained(step_of_blocks, n)
            out = fn(x0, a, b)
            fence(out)
            t0 = time.perf_counter()
            out = fn(x0, a, b)
            fence(out)
            return time.perf_counter() - t0

        return max(timed(5 * reps) - timed(reps), 0.0) / (4 * reps)

    def halo_step(u_blk, a_ext, b_ext):
        return halo_extend(u_blk, px, py)[1:-1, 1:-1]

    def stencil_step(u_blk, a_ext, b_ext):
        u_ext = halo_extend(u_blk, px, py)
        return apply_a_block(u_ext, a_ext, b_ext, h1, h2)

    def precond_step(u_blk, a_ext, b_ext):
        d = diag_d_block(a_ext, b_ext, h1, h2)
        return apply_dinv(u_blk, d)

    def dot_step(u_blk, a_ext, b_ext):
        # the probe times the collective ITSELF (t_dot's psum leg), so
        # it must issue one raw — outside the parallel/ cadence budgets
        # by design, never part of a pinned solver loop
        # tpulint: disable=TPU020
        s = lax.psum(jnp.sum(u_blk * u_blk), (AXIS_X, AXIS_Y)) * h1 * h2
        # rescale to keep the chain alive and the magnitude bounded
        return u_blk * (s / jnp.where(s == 0.0, 1.0, s))

    def time_update() -> float:
        """The per-shard w/r axpy + realised ‖Δw‖² partial (the stage4
        ``update_w_r_kernel`` analog). The partial's psum rides with the
        zr collective in the real loop (one batched psum —
        ``parallel.pcg_sharded._shard_advance``), so only the local
        reduction is timed here; a/b stand in for p/ap (same shapes,
        same sharding). The chain stays data-dependent through a ~1.0
        rescale by the partial, costing one extra elementwise pass —
        a slight overestimate, exactly like the dot phase's carry."""
        alpha = jnp.asarray(1e-3, dtype)

        def make(n: int):
            def blk_fn(w_blk, r_blk, a_blk, b_blk):
                def step(_, st):
                    w, r = st
                    w_new = w + alpha * a_blk
                    r_new = r - alpha * b_blk
                    dw = w_new - w
                    dw2 = jnp.sum(dw * dw)
                    w_new = w_new * (
                        dw2 / jnp.where(dw2 == 0.0, 1.0, dw2)
                    )
                    return (w_new, r_new)

                return lax.fori_loop(0, n, step, (w_blk, r_blk))

            # no donation: same re-fed operands as chained() above
            return jax.jit(  # tpulint: disable=TPU004
                shard_map(
                    blk_fn,
                    mesh=mesh,
                    in_specs=(spec, spec, spec, spec),
                    out_specs=(spec, spec),
                )
            )

        def timed(n: int) -> float:
            fn = make(n)
            out = fn(rhs, rhs, a, b)
            fence(out)
            t0 = time.perf_counter()
            out = fn(rhs, rhs, a, b)
            fence(out)
            return time.perf_counter() - t0

        return max(timed(5 * reps) - timed(reps), 0.0) / (4 * reps)

    phases = {
        "halo": time_fn(halo_step, rhs),
        "stencil": time_fn(stencil_step, rhs),
        "precond": time_fn(precond_step, rhs),
        "dot": time_fn(dot_step, rhs),
        "update": time_update(),
    }
    # the stencil phase includes its own halo exchange (as stage4's T_gpu
    # excludes but T_copy/T_mpi include theirs); subtract for the pure part
    phases["stencil_pure"] = max(phases["stencil"] - phases["halo"], 0.0)
    return phases


def format_phases(phases: dict[str, float], iters: int | None = None) -> str:
    lines = ["Per-iteration phase costs (on-device chained replay):"]
    for name, secs in sorted(phases.items(), key=lambda kv: -kv[1]):
        if secs == 0.0 and name != "halo":
            # the (t_5k - t_k) subtraction clamps at 0 when the phase
            # costs less than the dispatch-time noise
            lines.append(f"  t_{name:<12s}      below noise floor")
            continue
        line = f"  t_{name:<12s} {secs * 1e6:10.1f} us"
        if iters:
            line += f"   (x{iters} iters = {secs * iters:8.4f} s)"
        lines.append(line)
    return "\n".join(lines)
