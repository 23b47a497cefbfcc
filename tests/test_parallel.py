"""Distributed-path tests on a virtual CPU mesh (1/2/4/8 devices) —
SURVEY §4's prescription: the identical small-grid test matrix the reference
runs at 1/2/4 mpirun ranks, with simulated devices instead of ranks.

Asserts iteration-count parity with the single-chip solver and elementwise
agreement of the solution — the reference's strongest cross-implementation
oracle (same grid → same iteration count in every implementation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from poisson_ellipse_tpu.models.problem import Problem
from jax import shard_map
from poisson_ellipse_tpu.parallel.halo import halo_extend
from poisson_ellipse_tpu.parallel.mesh import (
    choose_process_grid,
    make_mesh,
    padded_dims,
)
from poisson_ellipse_tpu.parallel.pcg_sharded import solve_sharded
from poisson_ellipse_tpu.solver.pcg import solve
from poisson_ellipse_tpu.utils.error import l2_error_vs_analytic


def mesh_of(n):
    return make_mesh(jax.devices()[:n])


def test_choose_process_grid_matches_reference():
    # stage2-mpi/poisson_mpi_decomp.cpp:60-64 semantics
    assert choose_process_grid(1) == (1, 1)
    assert choose_process_grid(2) == (1, 2)
    assert choose_process_grid(4) == (2, 2)
    assert choose_process_grid(6) == (2, 3)
    assert choose_process_grid(8) == (2, 4)
    assert choose_process_grid(7) == (1, 7)
    assert choose_process_grid(16) == (4, 4)


def test_padded_dims():
    mesh = mesh_of(8)  # 2 x 4
    assert padded_dims((41, 41), mesh) == (42, 44)
    assert padded_dims((42, 44), mesh) == (42, 44)


def test_halo_extend_reconstructs_neighbors():
    """On a 2x4 mesh, halo_extend must deliver exactly the neighbouring
    block rows/cols of a globally known array, zeros at the physical edge."""
    mesh = mesh_of(8)
    g = jnp.arange(8 * 12, dtype=jnp.float64).reshape(8, 12)

    def f(blk):
        return halo_extend(blk, 2, 4)

    ext = jax.jit(
        shard_map(
            f,
            mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec("x", "y"),),
            out_specs=jax.sharding.PartitionSpec("x", "y"),
        )
    )(g)
    # device block (0,0) owns rows 0..3, cols 0..2 → extended 6x5 lives at
    # ext rows 0..5, cols 0..4 of the (12, 20) output
    ext = np.asarray(ext)
    g_np = np.asarray(g)
    blk00 = ext[:6, :5]
    np.testing.assert_array_equal(blk00[1:-1, 1:-1], g_np[0:4, 0:3])
    np.testing.assert_array_equal(blk00[0, :], 0)  # no north neighbour
    np.testing.assert_array_equal(blk00[:, 0], 0)  # no west neighbour
    np.testing.assert_array_equal(blk00[1:-1, -1], g_np[0:4, 3])  # east halo
    np.testing.assert_array_equal(blk00[-1, 1:-1], g_np[4, 0:3])  # south halo
    # an interior device block (1,1): rows 4..7, cols 3..5
    blk11 = ext[6:12, 5:10]
    np.testing.assert_array_equal(blk11[1:-1, 1:-1], g_np[4:8, 3:6])
    np.testing.assert_array_equal(blk11[0, 1:-1], g_np[3, 3:6])  # north halo
    np.testing.assert_array_equal(blk11[1:-1, 0], g_np[4:8, 2])  # west halo
    # corners propagate (second round operates on x-extended block)
    assert blk11[0, 0] == g_np[3, 2]


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_sharded_matches_single_chip(n_devices):
    problem = Problem(M=40, N=40)
    ref = solve(problem, jnp.float64)
    got = solve_sharded(problem, mesh_of(n_devices), jnp.float64)
    assert int(got.iters) == int(ref.iters) == 50
    assert bool(got.converged)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), rtol=0, atol=1e-10
    )


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_sharded_pallas_stencil_matches_single_chip(n_devices):
    """Mesh decomposition × per-shard Pallas stencil kernel in one program
    — the stage4 composition (kernel per rank in the hot loop, halo
    exchange + scalar collectives around it, ``gradient_solver_mpi``,
    ``poisson_mpi_cuda2.cu:846-939``). Interpret mode on CPU devices."""
    problem = Problem(M=40, N=40)
    ref = solve(problem, jnp.float32)
    got = solve_sharded(
        problem, mesh_of(n_devices), jnp.float32, stencil_impl="pallas"
    )
    assert int(got.iters) == int(ref.iters) == 50
    assert bool(got.converged)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), rtol=0, atol=5e-6
    )


def test_sharded_pallas_uneven_blocks():
    """Non-aligned per-shard blocks (padding on both axes) through the
    per-shard kernel path."""
    problem = Problem(M=13, N=17)
    ref = solve(problem, jnp.float32)
    got = solve_sharded(
        problem, mesh_of(8), jnp.float32, stencil_impl="pallas"
    )
    assert got.w.shape == (14, 18)
    assert int(got.iters) == int(ref.iters)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), rtol=0, atol=5e-6
    )


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_fused_sharded_matches_single_chip(n_devices):
    """The fused two-kernel iteration composed with the mesh: K1
    (p-update + stencil + denom partial) and K2 (updates + partials) per
    shard, a stacked (z, p) halo exchange and two psums per iteration —
    2 kernels + 2 psum + 4 ppermute vs the ~8 XLA fusions of the plain
    sharded loop (``parallel.fused_sharded``). Interpret mode on CPU."""
    from poisson_ellipse_tpu.parallel.fused_sharded import solve_fused_sharded

    problem = Problem(M=40, N=40)
    ref = solve(problem, jnp.float32)
    got = solve_fused_sharded(problem, mesh_of(n_devices))
    assert int(got.iters) == int(ref.iters) == 50
    assert bool(got.converged)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), rtol=0, atol=5e-6
    )


def test_fused_sharded_headline_oracle():
    """546 iterations at 400×600 (the published stage1-4 oracle) on the
    full 8-device mesh — the fused-sharded path at a bench-relevant
    size, through the ``stencil_impl`` dispatch."""
    problem = Problem(M=400, N=600)
    got = solve_sharded(
        problem, mesh_of(8), jnp.float32, stencil_impl="fused"
    )
    assert bool(got.converged)
    assert int(got.iters) == 546


def test_fused_sharded_uneven_blocks():
    """Both axes need tile-aligned shard padding (13×17 nodes over 2×4)."""
    problem = Problem(M=13, N=17)
    ref = solve(problem, jnp.float32)
    got = solve_sharded(
        problem, mesh_of(8), jnp.float32, stencil_impl="fused"
    )
    assert got.w.shape == (14, 18)
    assert int(got.iters) == int(ref.iters)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), rtol=0, atol=5e-6
    )


def test_fused_sharded_rejects_f64():
    from poisson_ellipse_tpu.parallel.fused_sharded import solve_fused_sharded

    with pytest.raises(ValueError, match="f32/bf16"):
        solve_fused_sharded(Problem(M=10, N=10), mesh_of(2), jnp.float64)


def test_fused_sharded_rejects_device_assembly():
    with pytest.raises(ValueError, match="host"):
        solve_sharded(
            Problem(M=10, N=10), mesh_of(2), jnp.float32,
            assembly_mode="device", stencil_impl="fused",
        )


def test_halo_extend_stacked_matches_per_array():
    """The stacked (k, bm, bn) exchange must deliver exactly what k
    separate halo_extend calls deliver, in 4 ppermutes instead of 4k."""
    from jax.sharding import PartitionSpec as P

    from poisson_ellipse_tpu.parallel.halo import halo_extend_stacked
    from poisson_ellipse_tpu.parallel.mesh import AXIS_X, AXIS_Y

    mesh = mesh_of(8)
    px, py = mesh.shape[AXIS_X], mesh.shape[AXIS_Y]
    u = jnp.arange(8 * 12, dtype=jnp.float64).reshape(8, 12)
    v = -2.0 * u + 1.0
    spec = P(AXIS_X, AXIS_Y)

    singles = jax.jit(
        shard_map(
            lambda a, b: (halo_extend(a, px, py), halo_extend(b, px, py)),
            mesh=mesh,
            in_specs=(spec, spec),
            out_specs=(spec, spec),
        )
    )(u, v)
    stacked = jax.jit(
        shard_map(
            lambda a, b: halo_extend_stacked(jnp.stack([a, b]), px, py),
            mesh=mesh,
            in_specs=(spec, spec),
            out_specs=P(None, AXIS_X, AXIS_Y),
        )
    )(u, v)
    np.testing.assert_array_equal(np.asarray(stacked[0]), np.asarray(singles[0]))
    np.testing.assert_array_equal(np.asarray(stacked[1]), np.asarray(singles[1]))


def test_sharded_rejects_unknown_stencil_impl():
    with pytest.raises(ValueError, match="stencil_impl"):
        solve_sharded(
            Problem(M=10, N=10), mesh_of(1), jnp.float32, stencil_impl="cuda"
        )


@pytest.mark.parametrize("assembly_mode", ["host", "device"])
def test_assembly_modes_agree(assembly_mode):
    problem = Problem(M=24, N=20)
    ref = solve(problem, jnp.float64)
    got = solve_sharded(
        problem, mesh_of(4), jnp.float64, assembly_mode=assembly_mode
    )
    assert int(got.iters) == int(ref.iters)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), rtol=0, atol=1e-10
    )


def test_sharded_uneven_grid_padding():
    # node grid 14x18 over a 2x4 mesh: both axes need padding
    problem = Problem(M=13, N=17)
    ref = solve(problem, jnp.float64)
    got = solve_sharded(problem, mesh_of(8), jnp.float64)
    assert got.w.shape == (14, 18)
    assert int(got.iters) == int(ref.iters)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), rtol=0, atol=1e-10
    )


def test_sharded_l2_error_matches():
    problem = Problem(M=40, N=40)
    got = solve_sharded(problem, mesh_of(8), jnp.float64)
    err = float(l2_error_vs_analytic(problem, got.w))
    assert err == pytest.approx(3.677e-3, rel=1e-3)


def test_halo_extend_wider_width():
    """width>1 slab exchange (the CP-analog primitive, SURVEY §5)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from poisson_ellipse_tpu.parallel.mesh import AXIS_X, AXIS_Y, make_mesh

    mesh = make_mesh(jax.devices()[:4])
    px, py = mesh.shape[AXIS_X], mesh.shape[AXIS_Y]
    bm, bn = 6, 6
    global_u = jnp.arange(px * bm * py * bn, dtype=jnp.float64).reshape(
        px * bm, py * bn
    )
    width = 2
    spec = P(AXIS_X, AXIS_Y)
    ext = jax.jit(
        shard_map(
            lambda u: halo_extend(u, px, py, width=width),
            mesh=mesh,
            in_specs=spec,
            out_specs=spec,
        )
    )(global_u)
    ext = np.asarray(ext)
    # device (0,0)'s extended block sits at rows 0..bm+2w of the stacked
    # output; its interior must match, its high-x halo must equal the
    # first `width` rows of device (1,0)'s block, and the boundary side
    # must be zero
    blk = ext[: bm + 2 * width, : bn + 2 * width]
    np.testing.assert_array_equal(
        blk[width:-width, width:-width], np.asarray(global_u[:bm, :bn])
    )
    np.testing.assert_array_equal(
        blk[-width:, width:-width], np.asarray(global_u[bm : bm + width, :bn])
    )
    np.testing.assert_array_equal(blk[:width, :], np.zeros((width, bn + 2 * width)))


def test_halo_extend_rejects_bad_width():
    with pytest.raises(ValueError, match="width"):
        halo_extend(jnp.zeros((4, 4)), 1, 1, width=0)
    with pytest.raises(ValueError, match="width"):
        halo_extend(jnp.zeros((4, 4)), 1, 1, width=5)


def test_multihost_helpers_single_process():
    """Single-process semantics of the MPI-lifecycle analogs."""
    from poisson_ellipse_tpu.parallel.multihost import (
        global_mesh,
        process_info,
    )

    pid, nproc = process_info()
    assert pid == 0 and nproc == 1
    mesh = global_mesh()
    assert mesh.devices.size == len(jax.devices())


def test_initialize_multihost_idempotent_guard():
    """The is_initialized() guard path (single-process: not initialised)."""
    from poisson_ellipse_tpu.parallel.multihost import shutdown_multihost

    # not initialised -> shutdown is a no-op rather than an error
    shutdown_multihost()
