"""Pallas kernels vs the XLA ops (interpret mode on the CPU backend).

The reference's cross-implementation oracle is agreement between its CPU
and CUDA paths on identical grids (SURVEY §4.2); here the analog is
Pallas-vs-XLA agreement on the same arrays, plus solver-level parity of
iteration counts. On real TPU the compiled kernels match the XLA path to
1-2 ulps (verified on-chip); in interpret mode most are exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly, pallas_kernels as pk
from poisson_ellipse_tpu.ops.reduction import grid_dot
from poisson_ellipse_tpu.ops.stencil import apply_a_block, apply_dinv
from poisson_ellipse_tpu.solver.pcg import pcg


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


@pytest.mark.parametrize("bm,bn", [(16, 18), (24, 130), (15, 33)])
def test_stencil_matches_xla(rng, bm, bn):
    w = jnp.asarray(rng.standard_normal((bm + 2, bn + 2)))
    a = jnp.asarray(rng.random((bm + 2, bn + 2)) + 0.5)
    b = jnp.asarray(rng.random((bm + 2, bn + 2)) + 0.5)
    ref = apply_a_block(w, a, b, 0.01, 0.02)
    out = pk.apply_a_block_pallas(w, a, b, 0.01, 0.02)
    assert out.shape == (bm, bn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-12)


def test_stencil_on_assembled_problem(rng):
    problem = Problem(M=24, N=16)
    a, b, rhs = assembly.assemble(problem, jnp.float64)
    w = jnp.asarray(rng.standard_normal(problem.node_shape))
    ref = apply_a_block(w, a, b, problem.h1, problem.h2)
    out = pk.apply_a_block_pallas(w, a, b, problem.h1, problem.h2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-12)


def test_dinv_matches(rng):
    d = jnp.asarray(rng.standard_normal((32, 40)))
    d = jnp.where(jnp.abs(d) < 0.3, 0.0, d)  # exercise the zero guard
    r = jnp.asarray(rng.standard_normal((32, 40)))
    assert bool(jnp.all(pk.apply_dinv_pallas(r, d) == apply_dinv(r, d)))


def test_dot_matches(rng):
    x = jnp.asarray(rng.standard_normal((32, 40)))
    y = jnp.asarray(rng.standard_normal((32, 40)))
    got = pk.dot_pallas(x, y, 0.01, 0.02)
    want = grid_dot(x, y, 0.01, 0.02)
    assert float(abs(got - want)) < 1e-12 * abs(float(want))


def test_update_w_r_fused(rng):
    w = jnp.asarray(rng.standard_normal((16, 24)))
    r = jnp.asarray(rng.standard_normal((16, 24)))
    p = jnp.asarray(rng.standard_normal((16, 24)))
    ap = jnp.asarray(rng.standard_normal((16, 24)))
    alpha = jnp.asarray(0.37)
    w_new, r_new, dw2 = pk.update_w_r_pallas(alpha, w, r, p, ap)
    # FMA contraction differs between the paths: ulp-level agreement only
    np.testing.assert_allclose(
        np.asarray(w_new), np.asarray(w + alpha * p), rtol=1e-13
    )
    np.testing.assert_allclose(
        np.asarray(r_new), np.asarray(r - alpha * ap), rtol=1e-13
    )
    assert float(abs(dw2 - jnp.sum((alpha * p) ** 2))) < 1e-12


def test_update_p(rng):
    z = jnp.asarray(rng.standard_normal((16, 24)))
    p = jnp.asarray(rng.standard_normal((16, 24)))
    beta = jnp.asarray(0.9)
    # rtol alone is not enough: where z + βp cancels to ~0 the FMA-vs-mul
    # ulp difference is unbounded relatively
    np.testing.assert_allclose(
        np.asarray(pk.update_p_pallas(beta, z, p)),
        np.asarray(z + beta * p),
        rtol=1e-13,
        atol=1e-14,
    )


def test_pcg_with_pallas_stencil_matches_oracle():
    problem = Problem(M=10, N=10, norm="unweighted")
    a, b, rhs = assembly.assemble(problem, jnp.float64)
    res = pcg(problem, a, b, rhs, stencil="pallas")
    # unweighted-norm oracle @ 10x10 (compiled reference stage0 binary)
    assert int(res.iters) == 17
    assert bool(res.converged)
    res_xla = pcg(problem, a, b, rhs, stencil="xla")
    np.testing.assert_allclose(
        np.asarray(res.w), np.asarray(res_xla.w), rtol=1e-10, atol=1e-14
    )


def test_pcg_rejects_unknown_stencil():
    problem = Problem(M=8, N=8)
    a, b, rhs = assembly.assemble(problem, jnp.float64)
    with pytest.raises(ValueError, match="unknown stencil"):
        pcg(problem, a, b, rhs, stencil="cuda")


def test_fused_stencil_takes_differences_first():
    """The fused K1 stencil keeps f32 accuracy on a smooth direction.

    Against an f64 ``apply_a`` of the same f32 ``p``, on the nodes where
    the coefficients are the constant inner ones: the expanded form
    D*p - Σ coef*p_nb cancels digits (measured 5.8e-6 relative here;
    at 4096² on the chip it doubled the solve's l2), the differences-
    first form stays at ~1e-7. A non-power-of-two grid, so 1/h² is not
    exact in f32.
    """
    from poisson_ellipse_tpu.ops.fused_pcg import (
        build_kernels,
        fused_operands,
        interior_normalized,
    )
    from poisson_ellipse_tpu.ops.stencil import apply_a

    problem = Problem(M=60, N=90)
    g1, g2 = problem.node_shape
    kern = build_kernels(problem, g1, g2, jnp.float32, interpret=True)
    an, as_, bw, be, _dinv = fused_operands(problem, kern.g1p, kern.g2p,
                                            jnp.float32)
    x = np.linspace(-1.0, 1.0, g1)[:, None]
    y = np.linspace(-0.6, 0.6, g2)[None, :]
    p = np.zeros((kern.g1p, kern.g2p), np.float32)
    p[1 : g1 - 1, 1 : g2 - 1] = (np.cos(1.3 * x) * np.cos(2.1 * y))[1:-1, 1:-1]
    _pn, ap, _ = kern.k1(jnp.float32(0.0), jnp.asarray(p),
                         jnp.zeros_like(jnp.asarray(p)), an, as_, bw, be)

    a64, b64, _ = assembly.assemble_numpy(problem)
    ref = np.asarray(apply_a(jnp.asarray(p[:g1, :g2], jnp.float64),
                             jnp.asarray(a64), jnp.asarray(b64),
                             problem.h1, problem.h2))
    c = interior_normalized(problem, a64, b64)[:4]
    inner = np.ones((g1, g2), bool)
    for coef, h in zip(c, (problem.h1, problem.h1, problem.h2, problem.h2)):
        inner &= np.abs(coef * h * h - 1.0) < 1e-12
    assert inner.sum() > 1000
    err = np.abs(np.asarray(ap)[:g1, :g2] - ref)[inner].max()
    assert err / np.abs(ref[inner]).max() < 1e-6
