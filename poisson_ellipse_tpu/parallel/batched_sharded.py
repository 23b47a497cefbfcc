"""Lane-sharded batched solves: throughput scale-out at 1 psum/iter.

The spatial decomposition (``parallel.pcg_sharded`` and friends) splits
ONE problem's grid over the mesh and pays collectives for every global
dot — 2 psums/iteration classical, 1 pipelined. Serving throughput has a
better axis: the *lane* dimension of the batched engines is embarrassingly
parallel, so this module shards lanes over the mesh — every device owns
``lanes / n_devices`` whole problems and runs the production batched
iteration (``batch.batched_pcg.make_lane_step`` /
``batch.batched_pipelined.make_lane_step`` — the identical per-lane
arithmetic, not a reimplementation) on its local lanes.

Collective cost: the per-lane dot bundles never leave the device (each
lane's grid lives whole on its shard — there is nothing to reduce
across the mesh), so the ONLY collective is the loop's convergence word:
one scalar ``lax.psum`` of the local active-lane count per iteration,
which keeps every device in the same fused ``lax.while_loop`` until all
lanes everywhere are done. That is **exactly 1 psum per iteration
independent of the lane count and of the recurrence** — flat where the
spatially-sharded classical loop pays 2 psums for every single solve
(jaxpr-pinned in ``tests/test_batched.py``). For the batched-pipelined
composition the stacked (8, B_local) bundle rides entirely in local
VMEM/HBM; the psum'd word is one int32.

The price is straggler synchronisation: all devices iterate until the
slowest lane converges — the same whole-batch semantics the single-chip
batched loop has, made visible per-device. Mixed-difficulty lanes should
be binned by the caller (the compile-cache's lane buckets are the
natural binning boundary).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from poisson_ellipse_tpu.batch import batched_pcg, batched_pipelined
from poisson_ellipse_tpu.batch.batched_pcg import (
    BatchedPCGResult,
    apply_dinv_batched,
    batched_operands,
    diag_d_batched,
)
from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.parallel.mesh import (
    AXIS_X,
    AXIS_Y,
    make_mesh,
    pcast_varying,
)

MESH_AXES = (AXIS_X, AXIS_Y)


class LaneLayout(NamedTuple):
    """Where the serve scheduler's per-lane arrays live on a mesh: each
    chip holds its own lanes' slices and nothing else."""

    stack: NamedSharding  # (lanes, M+1, N+1): a3, b3, mask, w, r, p
    vector: NamedSharding  # (lanes,): h1, h2, delta and the lane flags
    replicated: NamedSharding  # the carry's clock k, the chunk's limit

    @property
    def operands(self) -> tuple:
        """The shardings of (a3, b3, mask, h1, h2, delta)."""
        return (self.stack,) * 3 + (self.vector,) * 3

    @property
    def state(self) -> tuple:
        """The shardings of the classical batched carry (k, w, r, p, zr,
        diff, conv, bd, quar, iters)."""
        return ((self.replicated,) + (self.stack,) * 3
                + (self.vector,) * 6)


def lane_layout(mesh: Mesh) -> LaneLayout:
    """Whole lanes sharded over every device of ``mesh``."""
    return LaneLayout(
        NamedSharding(mesh, P(MESH_AXES, None, None)),
        NamedSharding(mesh, P(MESH_AXES)),
        NamedSharding(mesh, P()),
    )


def build_batched_sharded_solver(
    problem: Problem,
    mesh: Mesh | None = None,
    lanes: int | None = None,
    dtype=jnp.float32,
    pipelined: bool = False,
):
    """(jitted solver, args) for a lane-sharded batched solve.

    ``lanes`` must be a multiple of the mesh's device count (each device
    owns whole lanes; the compile-cache's lane buckets round requests up
    to exactly such multiples). ``args`` = (a, b, rhs): coefficients
    replicated, the (lanes, M+1, N+1) RHS stack sharded on its lane axis
    over every mesh device. The solver returns a per-lane
    :class:`BatchedPCGResult`, lane order preserved.
    """
    if mesh is None:
        mesh = make_mesh()
    n_devices = mesh.shape[AXIS_X] * mesh.shape[AXIS_Y]
    if lanes is None:
        lanes = n_devices
    if lanes % n_devices != 0:
        raise ValueError(
            f"lanes={lanes} must be a multiple of the mesh's {n_devices} "
            "devices (whole lanes per device; pad the request to the "
            "next lane bucket)"
        )
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    delta = jnp.asarray(problem.delta, dtype)
    weighted = problem.norm == "weighted"
    max_iter = problem.max_iterations
    lane_spec = P(MESH_AXES)

    def shard_fn(a, b, rhs):
        # a/b replicated (shared geometry), rhs = this device's lanes
        a3, b3 = a[None], b[None]
        d = diag_d_batched(a3, b3, h1, h2)
        B_local = rhs.shape[0]
        if pipelined:
            step = batched_pipelined.make_lane_step(
                rhs, a3, b3, d, None, h1, h2, delta, weighted
            )
            r0 = rhs
            u0 = apply_dinv_batched(r0, d)
            w0 = batched_pipelined.apply_a_batched(u0, a3, b3, h1, h2)
            zeros = lambda: pcast_varying(jnp.zeros_like(rhs), MESH_AXES)
            lane_state = (
                jnp.asarray(0, jnp.int32),
                zeros(),  # x
                r0, u0, w0,
                zeros(), zeros(), zeros(),  # z, s, p
                pcast_varying(jnp.ones((B_local,), dtype), MESH_AXES),
                pcast_varying(jnp.full((B_local,), jnp.inf, dtype), MESH_AXES),
                pcast_varying(jnp.zeros((B_local,), bool), MESH_AXES),
                pcast_varying(jnp.zeros((B_local,), bool), MESH_AXES),
                pcast_varying(jnp.zeros((B_local,), bool), MESH_AXES),
                pcast_varying(jnp.zeros((B_local,), jnp.int32), MESH_AXES),
            )
            conv_i, bd_i, quar_i = 10, 11, 12
        else:
            step = batched_pcg.make_lane_step(
                a3, b3, d, None, h1, h2, delta, weighted
            )
            r0 = rhs
            z0 = apply_dinv_batched(r0, d)
            zr0 = jnp.sum(z0 * r0, axis=(1, 2)) * h1 * h2
            lane_state = (
                jnp.asarray(0, jnp.int32),
                pcast_varying(jnp.zeros_like(rhs), MESH_AXES),
                r0,
                z0,
                zr0,
                pcast_varying(jnp.full((B_local,), jnp.inf, dtype), MESH_AXES),
                pcast_varying(jnp.zeros((B_local,), bool), MESH_AXES),
                pcast_varying(jnp.zeros((B_local,), bool), MESH_AXES),
                pcast_varying(jnp.zeros((B_local,), bool), MESH_AXES),
                pcast_varying(jnp.zeros((B_local,), jnp.int32), MESH_AXES),
            )
            conv_i, bd_i, quar_i = 6, 7, 8

        def cond(carry):
            lane_state, n_active = carry
            return (lane_state[0] < max_iter) & (n_active > 0)

        def body(carry):
            lane_state, _ = carry
            new = step(lane_state)
            active = ~new[conv_i] & ~new[bd_i] & ~new[quar_i]
            # THE one collective of the iteration, lane-count-invariant:
            # the cross-device convergence word (dot bundles are
            # lane-local and need no psum at all)
            n_active = lax.psum(
                jnp.sum(active, dtype=jnp.int32), MESH_AXES
            )
            return new, n_active

        out, _ = lax.while_loop(
            cond, body, (lane_state, jnp.asarray(lanes, jnp.int32))
        )
        result = (
            batched_pipelined.result_of(out) if pipelined
            else batched_pcg.result_of(out)
        )
        return tuple(result)

    mapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(), P(MESH_AXES, None, None)),
        out_specs=(
            P(MESH_AXES, None, None),  # w
            lane_spec, lane_spec, lane_spec, lane_spec, lane_spec,
        ),
    )

    a, b, rhs = batched_operands(problem, lanes, dtype)
    args = (
        jax.device_put(a, NamedSharding(mesh, P())),
        jax.device_put(b, NamedSharding(mesh, P())),
        jax.device_put(rhs, NamedSharding(mesh, P(MESH_AXES, None, None))),
    )

    def solver(a, b, rhs):
        return BatchedPCGResult(*mapped(a, b, rhs))

    # no donation: the build-once-call-many contract re-feeds these
    # operands on every dispatch (bench --repeat, chained solves)
    # tpulint: disable=TPU004
    return jax.jit(solver), args


def build_sharded_chunk_advance(
    bucket: tuple[int, int],
    mesh: Mesh | None = None,
    lanes: int | None = None,
    norm: str = "weighted",
    iter_ceiling: int = 1 << 30,
):
    """(jitted carry→carry chunk advance, proto problem) for the serve
    scheduler's lane-refill loop composed with the mesh.

    The refill machinery is host-side between-chunk work, so the traced
    loop body is untouched: this is the classical batched lane step
    (``batch.batched_pcg.make_lane_step`` — the identical per-lane
    arithmetic) sharded whole-lanes-per-device, advancing an existing
    carry up to a traced ``limit``. Per-lane operands, masks, spacings
    and δ are traced arguments (the scheduler's mixed-shape packing),
    so retire/refill/replay never retrace (the compute dtype rides on
    the operands, not on a parameter here). The ONLY collective is the
    convergence word — **exactly 1 psum per iteration**, lane-count- and
    refill-invariant (jaxpr-pinned in ``tests/test_serve.py``).

    Signature of the returned fn (matches the scheduler's single-device
    bucket advance): ``fn(a3, b3, mask, h1, h2, delta, state, limit)``
    where ``state`` is the classical batched carry. Its arguments and
    results are pinned to ``lane_layout(mesh)``: a per-lane array placed
    anywhere else is refused, not copied onto its shards every chunk.
    """
    if mesh is None:
        mesh = make_mesh()
    n_devices = mesh.shape[AXIS_X] * mesh.shape[AXIS_Y]
    if lanes is None:
        lanes = n_devices
    if lanes % n_devices != 0:
        raise ValueError(
            f"lanes={lanes} must be a multiple of the mesh's {n_devices} "
            "devices (whole lanes per device)"
        )
    proto = Problem(
        M=bucket[0], N=bucket[1], norm=norm, max_iter=iter_ceiling
    )
    weighted = norm == "weighted"

    def shard_fn(a3, b3, mask, h1, h2, delta, state, limit):
        d = diag_d_batched(a3, b3, h1, h2, mask)
        step = batched_pcg.make_lane_step(
            a3, b3, d, mask, h1, h2, delta, weighted
        )
        bound = jnp.minimum(
            limit, jnp.asarray(proto.max_iterations, jnp.int32)
        )

        def active_count(lane_state):
            active = ~lane_state[6] & ~lane_state[7] & ~lane_state[8]
            return lax.psum(jnp.sum(active, dtype=jnp.int32), MESH_AXES)

        def cond(carry):
            lane_state, n_active = carry
            return (lane_state[0] < bound) & (n_active > 0)

        def body(carry):
            lane_state, _ = carry
            new = step(lane_state)
            # THE one collective of the iteration: the convergence word
            return new, active_count(new)

        out, _ = lax.while_loop(cond, body, (state, active_count(state)))
        return out

    layout = lane_layout(mesh)
    state_specs = tuple(s.spec for s in layout.state)
    mapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=tuple(s.spec for s in layout.operands) + (state_specs, P()),
        out_specs=state_specs,
    )

    # no donation: the carry is re-read at every chunk boundary for the
    # scheduler's retire/refill host work
    return jax.jit(
        mapped,
        in_shardings=layout.operands + (layout.state, layout.replicated),
        out_shardings=layout.state,
    ), proto


def solve_batched_sharded(
    problem: Problem,
    lanes: int | None = None,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
    pipelined: bool = False,
) -> BatchedPCGResult:
    """Assemble, lane-shard and solve over the mesh."""
    solver, args = build_batched_sharded_solver(
        problem, mesh, lanes, dtype, pipelined=pipelined
    )
    return solver(*args)
