"""Pipelined PCG: parity, breakdown, chunking, sharding, and the
one-psum-per-iteration structural guarantee.

The pipelined engine's contract is deliberately weaker than the classical
engines' bitwise oracle parity — it is a *reordering* of the recurrence
(``ops.pipelined_pcg``), so iteration counts are held to ±2 of the
``xla`` engine and solutions to a fraction of the L2 error, while the
structural claim that motivates it (ONE stacked psum collective per
sharded iteration, versus the classical loop's two) is pinned exactly,
from the jaxpr."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.pipelined_pcg import (
    advance,
    init_state,
    pcg_pipelined,
    result_of,
    solve as solve_pipelined,
)
from poisson_ellipse_tpu.ops.reduction import grid_dots
from poisson_ellipse_tpu.parallel.mesh import make_mesh
from poisson_ellipse_tpu.solver.pcg import pcg, solve as solve_xla
from poisson_ellipse_tpu.utils.error import l2_error_vs_analytic

# committed reference code oracles (provenance: tests/test_pcg.py)
UNWEIGHTED_ORACLE = {(10, 10): 17, (20, 20): 31, (40, 40): 61}
WEIGHTED_ORACLE = {(10, 10): 15, (20, 20): 26, (40, 40): 50}


def mesh_of(n):
    return make_mesh(jax.devices()[:n])


# ------------------------------------------------------------- parity


@pytest.mark.parametrize("norm,oracle", [
    ("unweighted", UNWEIGHTED_ORACLE), ("weighted", WEIGHTED_ORACLE),
])
@pytest.mark.parametrize("M,N", sorted(WEIGHTED_ORACLE))
def test_oracle_parity_within_two(M, N, norm, oracle):
    """Iters within ±2 of xla (and of the published count), converged,
    L2-vs-analytic within 10% — the pipelined accuracy contract."""
    problem = Problem(M=M, N=N, norm=norm)
    ref = solve_xla(problem, jnp.float64)
    got = solve_pipelined(problem, jnp.float64)
    assert abs(int(got.iters) - int(ref.iters)) <= 2
    assert abs(int(got.iters) - oracle[(M, N)]) <= 2
    assert bool(got.converged)
    assert not bool(got.breakdown)
    l2_ref = float(l2_error_vs_analytic(problem, ref.w))
    l2_got = float(l2_error_vs_analytic(problem, got.w))
    assert l2_got <= 1.1 * l2_ref


@pytest.mark.parametrize("stencil", ["xla", "pallas"])
def test_f32_parity_general_grid(stencil):
    """f32 on a non-square, non-aligned grid, both stencil flavours —
    the fused stencil+partials kernel drives the 'pallas' loop."""
    problem = Problem(M=44, N=132)
    ref = solve_xla(problem, jnp.float32)
    got = solve_pipelined(problem, jnp.float32, stencil=stencil)
    assert abs(int(got.iters) - int(ref.iters)) <= 2
    assert bool(got.converged)
    l2_ref = float(l2_error_vs_analytic(problem, ref.w))
    assert float(l2_error_vs_analytic(problem, got.w)) <= 1.1 * l2_ref


@pytest.mark.parametrize("seed", range(3))
def test_parity_on_random_configurations(seed):
    """±2-parity over randomly drawn boxes/ε/f/grids (the SURVEY §4
    invariance suite, under the pipelined tolerance)."""
    rng = np.random.default_rng(2000 + seed)
    problem = Problem(
        M=int(rng.integers(24, 56)),
        N=int(rng.integers(24, 56)),
        a1=-float(rng.uniform(1.05, 1.6)),
        b1=float(rng.uniform(1.05, 1.6)),
        a2=-float(rng.uniform(0.55, 1.0)),
        b2=float(rng.uniform(0.55, 1.0)),
        eps=float(10.0 ** rng.uniform(-6, -1)),
        f_val=float(rng.uniform(0.2, 3.0)),
    )
    ref = solve_xla(problem, jnp.float64)
    got = solve_pipelined(problem, jnp.float64)
    assert bool(ref.converged) and bool(got.converged), problem
    assert abs(int(got.iters) - int(ref.iters)) <= 2, problem


def test_headline_grid_f32_oracle():
    """546±2 at 400×600 f32 — the smallest published bench oracle, the
    regime where the unstabilised recurrence used to break down (the
    residual-replacement cadence is load-bearing here)."""
    problem = Problem(M=400, N=600)
    got = solve_pipelined(problem, jnp.float32)
    assert bool(got.converged)
    assert not bool(got.breakdown)
    assert abs(int(got.iters) - 546) <= 2
    assert float(l2_error_vs_analytic(problem, got.w)) < 1e-3


# ------------------------------------------------------------- breakdown


def test_breakdown_guard_exit():
    """Zero coefficients make the α-denominator 0 < DENOM_GUARD on the
    first iteration: the pipelined loop must exit via the breakdown flag
    with the pre-update iterate held — the same exit the classical loop
    takes (stage0/Withoutopenbmp1.cpp:128-style early return)."""
    problem = Problem(M=10, N=10)
    _, _, rhs = assembly.assemble(problem, jnp.float64)
    zeros = jnp.zeros_like(rhs)
    got = pcg_pipelined(problem, zeros, zeros, rhs)
    ref = pcg(problem, zeros, zeros, rhs)
    assert bool(got.breakdown) and bool(ref.breakdown)
    assert not bool(got.converged)
    assert int(got.iters) == int(ref.iters) == 1
    np.testing.assert_array_equal(np.asarray(got.w), np.asarray(zeros))


# ------------------------------------------------------------- chunking


def test_chunked_advance_bit_identical():
    """init_state + advance in limit-chunks is bit-identical to one
    straight run (the resumable-solver contract ``solver.pcg`` has,
    carried over: chunking moves the while_loop boundary only)."""
    problem = Problem(M=20, N=20)
    a, b, rhs = assembly.assemble(problem, jnp.float64)
    straight = advance(problem, a, b, rhs, init_state(problem, a, b, rhs))

    state = init_state(problem, a, b, rhs)
    for limit in (3, 7, 11, 200):
        state = advance(problem, a, b, rhs, state, limit=limit)
    chunked = state

    for lhs, rhs_ in zip(straight, chunked):
        np.testing.assert_array_equal(np.asarray(lhs), np.asarray(rhs_))
    result = result_of(chunked)
    assert int(result.iters) == WEIGHTED_ORACLE[(20, 20)]
    assert bool(result.converged)


def test_chunk_boundary_on_replacement_iteration():
    """A chunk boundary landing exactly on the residual-replacement
    cadence must not change anything — the replacement is keyed on the
    iteration counter, not the dispatch."""
    from poisson_ellipse_tpu.ops.pipelined_pcg import REPLACE_EVERY

    problem = Problem(M=40, N=40)  # 50 iterations: crosses k=32
    a, b, rhs = assembly.assemble(problem, jnp.float64)
    straight = advance(problem, a, b, rhs, init_state(problem, a, b, rhs))
    state = init_state(problem, a, b, rhs)
    for limit in (REPLACE_EVERY, REPLACE_EVERY + 1, 200):
        state = advance(problem, a, b, rhs, state, limit=limit)
    for lhs, rhs_ in zip(straight, state):
        np.testing.assert_array_equal(np.asarray(lhs), np.asarray(rhs_))


# ------------------------------------------------------------- sharded


@pytest.mark.parametrize("mesh_shape", [(1, 2), (2, 2)])
def test_sharded_pipelined_matches_single_chip(mesh_shape):
    """The one-psum sharded variant on a CPU mesh (through
    ``jax.shard_map``): iters within ±2 of the sharded
    xla path and elementwise agreement with the single-chip pipelined
    solve."""
    from poisson_ellipse_tpu.parallel.pcg_sharded import solve_sharded
    from poisson_ellipse_tpu.parallel.pipelined_sharded import (
        solve_pipelined_sharded,
    )

    px, py = mesh_shape
    mesh = mesh_of(px * py)
    problem = Problem(M=40, N=40)
    single = solve_pipelined(problem, jnp.float64)
    ref = solve_sharded(problem, mesh, jnp.float64)
    got = solve_pipelined_sharded(problem, mesh, jnp.float64)
    assert abs(int(got.iters) - int(ref.iters)) <= 2
    assert bool(got.converged)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(single.w), rtol=0, atol=1e-10
    )


def test_sharded_pipelined_uneven_grid():
    """Shard padding on both axes (14×18 nodes over a 2×4 mesh)."""
    from poisson_ellipse_tpu.parallel.pipelined_sharded import (
        solve_pipelined_sharded,
    )

    problem = Problem(M=13, N=17)
    ref = solve_pipelined(problem, jnp.float64)
    got = solve_pipelined_sharded(problem, mesh_of(8), jnp.float64)
    assert got.w.shape == (14, 18)
    assert abs(int(got.iters) - int(ref.iters)) <= 2
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), rtol=0, atol=1e-10
    )


def test_sharded_pipelined_through_dispatch_and_cli():
    """stencil_impl='pipelined' routes through build_sharded_solver and
    the harness sharded mode (the product entry points)."""
    from poisson_ellipse_tpu.harness.run import run_once
    from poisson_ellipse_tpu.parallel.pcg_sharded import solve_sharded

    problem = Problem(M=20, N=20)
    got = solve_sharded(
        problem, mesh_of(2), jnp.float64, stencil_impl="pipelined"
    )
    assert abs(int(got.iters) - WEIGHTED_ORACLE[(20, 20)]) <= 2
    report = run_once(
        problem, mode="sharded", mesh_shape=(1, 2), dtype="f64",
        engine="pipelined",
    )
    assert report.engine == "pipelined"
    assert report.converged
    with pytest.raises(ValueError, match="host"):
        solve_sharded(
            problem, mesh_of(2), jnp.float64,
            assembly_mode="device", stencil_impl="pipelined",
        )


# ------------------------------------------------ structural (static cost)


def test_pipelined_iteration_issues_exactly_one_psum():
    """THE structural claim, asserted from the declared contract
    (``analysis.contracts`` — the same checker the matrix CLI sweeps,
    with expectations derived from ENGINE_CAPS, not a test-local jaxpr
    walk): the pipelined sharded loop body holds exactly 1 psum
    collective per iteration; the classical sharded loop holds 2 with
    the 4-ppermute halo ring. (The pipelined body's ppermutes are
    deliberately unpinned: the replacement branch's stacked exchanges
    are static upper-bound accounting, not steady-state cost.)"""
    from poisson_ellipse_tpu.analysis.contracts import assert_contract

    problem = Problem(M=40, N=40)
    pipe = assert_contract(
        "collective-cadence", "pipelined", problem=problem,
        mesh_shape=(2, 2),
    )
    classical = assert_contract(
        "collective-cadence", "xla", problem=problem, mesh_shape=(2, 2),
    )
    assert pipe.expected["psum"] == 1
    assert classical.expected == {"psum": 2, "ppermute": 4}


# ------------------------------------------------------------ grid_dots


def test_grid_dots_matches_individual_sums():
    rng = np.random.default_rng(7)
    u = jnp.asarray(rng.standard_normal((13, 17)))
    v = jnp.asarray(rng.standard_normal((13, 17)))
    w = jnp.asarray(rng.standard_normal((13, 17)))
    sums = grid_dots((u, v), (v, w), (w, w))
    assert sums.shape == (3,)
    for got, (x, y) in zip(sums, ((u, v), (v, w), (w, w))):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(jnp.sum(x * y))
        )


# ------------------------------------------- fused stencil+partials kernel


def test_apply_a_dots_pallas_matches_stencil_and_dots():
    """The fused kernel must agree with its two unfused constituents:
    the Pallas stencil twin (exactly — same expression tree, same
    tiling) and the separate dot sums (to f32 reduction-order slack)."""
    from poisson_ellipse_tpu.ops.pallas_kernels import (
        apply_a_dots_pallas,
        apply_a_pallas,
    )

    problem = Problem(M=44, N=132)
    a, b, rhs = assembly.assemble(problem, jnp.float32)
    rng = np.random.default_rng(3)
    mk = lambda: jnp.asarray(rng.standard_normal(rhs.shape), jnp.float32)
    m, r, u, w, p = mk(), mk(), mk(), mk(), mk()
    pairs = ((r, u), (w, u), (u, u), (u, p), (p, p))
    n, sums = apply_a_dots_pallas(m, a, b, problem.h1, problem.h2, pairs)
    np.testing.assert_array_equal(
        np.asarray(n), np.asarray(apply_a_pallas(m, a, b, problem.h1, problem.h2))
    )
    expected = [
        float(jnp.sum(x[1:-1, 1:-1] * y[1:-1, 1:-1])) for x, y in pairs
    ]
    np.testing.assert_allclose(np.asarray(sums), expected, rtol=2e-5)
    with pytest.raises(ValueError, match="pair"):
        apply_a_dots_pallas(m, a, b, problem.h1, problem.h2, ())


# ------------------------------------------------------------ engine zoo


def test_engine_registration_and_policy():
    from poisson_ellipse_tpu.solver.engine import (
        ENGINES,
        build_solver,
        select_engine,
    )

    assert "pipelined" in ENGINES and "pipelined-pallas" in ENGINES
    # auto never picks it: single-chip it is a collectives optimisation
    # paying ~2x streamed passes — the policy table documents why
    for problem in (Problem(M=40, N=40), Problem(M=4096, N=4096)):
        assert select_engine(problem) != "pipelined"

    problem = Problem(M=20, N=20)
    ref = solve_xla(problem, jnp.float32)
    for engine in ("pipelined", "pipelined-pallas"):
        solver, args, resolved = build_solver(problem, engine, jnp.float32)
        assert resolved == engine
        got = solver(*args)
        assert abs(int(got.iters) - int(ref.iters)) <= 2
        assert bool(got.converged)


def test_run_once_single_pipelined_reports_roofline():
    from poisson_ellipse_tpu.harness.run import run_once

    report = run_once(
        Problem(M=20, N=20), mode="single", engine="pipelined"
    )
    assert report.engine == "pipelined"
    assert report.converged
    assert report.passes_per_iter > 13.0  # the documented traffic price
