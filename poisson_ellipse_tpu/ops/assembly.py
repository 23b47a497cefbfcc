"""Fictitious-domain coefficient assembly (reference layer L1), vectorised.

The reference assembles per-edge diffusion coefficients a_ij (vertical faces)
and b_ij (horizontal faces) plus the indicator RHS B_ij in nested loops on
the CPU host (``stage0/Withoutopenmp1.cpp:42-61``; the distributed variant
``fictitious_regions_setup_local`` at ``stage4-mpi+cuda/poisson_mpi_cuda2.cu:146-192``
assembles each rank's block + one halo ring from *global* indices, with no
communication).

This module keeps exactly that contract, TPU-style: every function takes
arrays of **global node indices** ``gi``/``gj`` and evaluates the closed-form
geometry by broadcasting — so the same code assembles the whole grid on one
chip (``gi = 0..M``) or any device's halo-extended block inside ``shard_map``
(``gi = r0-1 .. r1``), with out-of-range indices masked to zero. No loops,
no communication, no host work.

Coefficient law (``stage0/Withoutopenmp1.cpp:53-54``; README.md:44-57):
    a_ij = 1                         face fully inside D  (|l − h2| < 1e-9)
         = 1/eps                     face fully outside   (l < 1e-9)
         = l/h2 + (1 − l/h2)/eps     cut face (length-weighted blend)
with eps = max(h1,h2)² by default, and symmetrically for b with h1.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from poisson_ellipse_tpu.models import ellipse
from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs import trace as obs_trace

# Tolerances from the reference's blend law (stage0/Withoutopenmp1.cpp:53-54).
_FULL_TOL = 1e-9
_EMPTY_TOL = 1e-9


def _blend(length, h, eps, xp=jnp):
    """Piecewise coefficient law for one face of length-in-D ``length``."""
    frac = length / h
    cut = frac + (1.0 - frac) / eps
    return xp.where(
        xp.abs(length - h) < _FULL_TOL,
        1.0,
        xp.where(length < _EMPTY_TOL, 1.0 / eps, cut),
    )


def _coefficients_xp(problem: Problem, x, y, xp):
    """Shared closed-form coefficient evaluation at node coordinates x × y.

    The single source of truth for the blend law applied to the segment
    closed forms; serves both the traced path (xp=jnp) and the float64
    host path (xp=numpy).
    """
    h1, h2 = problem.h1, problem.h2
    eps = problem.eps_value
    xc = x[:, None]
    yc = y[None, :]
    la = ellipse.segment_length_vertical(
        xc - 0.5 * h1, yc - 0.5 * h2, yc + 0.5 * h2, xp
    )
    lb = ellipse.segment_length_horizontal(
        yc - 0.5 * h2, xc - 0.5 * h1, xc + 0.5 * h1, xp
    )
    return _blend(la, h2, eps, xp), _blend(lb, h1, eps, xp)


def coefficients_at(problem: Problem, gi, gj, dtype=jnp.float32):
    """Assemble (a, b) at the outer product of global node indices gi × gj.

    a[i,j] lives on the vertical face x = x_i − h1/2, y ∈ [y_j − h2/2, y_j + h2/2];
    b[i,j] on the horizontal face y = y_j − h2/2, x ∈ [x_i − h1/2, x_i + h1/2]
    (``stage0/Withoutopenmp1.cpp:49-54``). Valid for 1 ≤ gi ≤ M, 1 ≤ gj ≤ N;
    indices outside that range (physical boundary ring, shard padding) yield 0,
    mirroring the zero-initialised (M+1)×(N+1) arrays of the reference.
    """
    gi = jnp.asarray(gi)
    gj = jnp.asarray(gj)
    x = problem.a1 + gi.astype(dtype) * jnp.asarray(problem.h1, dtype)
    y = problem.a2 + gj.astype(dtype) * jnp.asarray(problem.h2, dtype)
    a, b = _coefficients_xp(problem, x, y, jnp)
    valid = (
        ((gi >= 1) & (gi <= problem.M))[:, None]
        & ((gj >= 1) & (gj <= problem.N))[None, :]
    )
    zero = jnp.asarray(0.0, dtype)
    return jnp.where(valid, a, zero), jnp.where(valid, b, zero)


def rhs_at(problem: Problem, gi, gj, dtype=jnp.float32):
    """Indicator right-hand side B_ij = f_val·1[node inside D] on the interior.

    Reference: ``stage0/Withoutopenmp1.cpp:57-60`` — B is f_val at interior
    nodes (1 ≤ i ≤ M−1, 1 ≤ j ≤ N−1) strictly inside the ellipse, else 0.
    """
    gi = jnp.asarray(gi)
    gj = jnp.asarray(gj)
    x = problem.a1 + gi.astype(dtype) * jnp.asarray(problem.h1, dtype)
    y = problem.a2 + gj.astype(dtype) * jnp.asarray(problem.h2, dtype)
    inside = ellipse.is_in_d(x[:, None], y[None, :])
    interior = interior_mask(problem, gi, gj)
    return jnp.where(
        inside & interior, jnp.asarray(problem.f_val, dtype), jnp.asarray(0.0, dtype)
    )


def interior_mask(problem: Problem, gi, gj):
    """Boolean mask of interior nodes 1 ≤ gi ≤ M−1, 1 ≤ gj ≤ N−1."""
    gi = jnp.asarray(gi)
    gj = jnp.asarray(gj)
    return (
        ((gi >= 1) & (gi <= problem.M - 1))[:, None]
        & ((gj >= 1) & (gj <= problem.N - 1))[None, :]
    )


def assemble_numpy(problem: Problem, geometry=None, theta=None):
    """Full-precision host assembly in vectorised numpy float64.

    The geometry MUST be evaluated in f64 regardless of the solve dtype:
    segment lengths carry absolute rounding noise ~machine-eps of O(1)
    coordinates, and the cut-face blend amplifies any noise in l/h by
    1/eps = 1/max(h1,h2)² — in f32 that turns into O(10) errors (and even
    negative, SPD-breaking coefficients) on fine grids like 1024²+.
    Evaluating in f64 and *then* casting keeps coefficients exact to the
    target dtype's resolution. This mirrors the reference, which always
    assembles on the host in double (``poisson_mpi_cuda2.cu:146-192``).

    ``geometry=None`` (the default) is the hard-coded ellipse through
    its closed forms — BIT-identical to every pre-geometry release.
    A ``geom.sdf`` shape switches the face lengths to the adaptive
    bisection quadrature (``geom.quadrature``) and the RHS indicator to
    the SDF sign, with the degenerate-cut clamp at threshold ``theta``
    (default ``geom.quadrature.DEFAULT_THETA``; 0 disables). Every
    clamped face is REPORTED as one ``geom:degenerate-cut`` trace event
    carrying the counts — the defense is observable, never silent.

    Public API: the sharded solver pads/casts/lays these arrays out over
    the mesh. Uses the same closed forms as the traced path via
    ``_coefficients_xp(…, xp=numpy)``. Traced as an ``assembly.host``
    span: every engine's build and every serve refill assembles here.
    """
    with obs_trace.span("assembly.host"):
        M, N = problem.M, problem.N
        gi = np.arange(M + 1, dtype=np.float64)
        gj = np.arange(N + 1, dtype=np.float64)
        x = problem.a1 + gi * problem.h1
        y = problem.a2 + gj * problem.h2
        if geometry is None:
            a, b = _coefficients_xp(problem, x, y, np)
            inside = ellipse.is_in_d(x[:, None], y[None, :])
        else:
            a, b, inside = _geometry_coefficients(problem, geometry, theta,
                                                  x, y)

        valid = (((gi >= 1) & (gi <= M))[:, None]
                 & ((gj >= 1) & (gj <= N))[None, :])
        a = np.where(valid, a, 0.0)
        b = np.where(valid, b, 0.0)

        interior = ((gi >= 1) & (gi <= M - 1))[:, None] & (
            (gj >= 1) & (gj <= N - 1)
        )[None, :]
        rhs = np.where(inside & interior, problem.f_val, 0.0)
        return a, b, rhs


# memo over the quadrature assembly, keyed (problem, geometry, theta):
# one geometry-threaded BUILD legitimately assembles 2-3 times (the
# operand set, the mg hierarchy's finest level, a validation pass), and
# the bisection sweep is the expensive host step — pay it once per
# distinct key, and emit the geom:degenerate-cut event once per
# distinct assembly rather than once per call. SDF shapes are frozen
# dataclasses (hashable); an unhashable custom shape just skips the
# memo. Entries are f64 read-backs — copies go out, so a caller
# mutating its arrays cannot poison later builds.
_GEOM_MEMO: dict = {}
_GEOM_MEMO_MAX = 8


def _geometry_coefficients(problem: Problem, geometry, theta, x, y):
    """The SDF-general twin of ``_coefficients_xp``: face lengths by
    bisection quadrature, the degenerate-cut clamp, the same blend law.
    Host f64 only (the traced path stays closed-form ellipse)."""
    from poisson_ellipse_tpu.geom import quadrature, sdf as geom_sdf

    if theta is None:
        theta = quadrature.DEFAULT_THETA
    try:
        key = (problem, geometry, float(theta))
        hash(key)
    except TypeError:
        key = None
    if key is not None and key in _GEOM_MEMO:
        a, b, inside = _GEOM_MEMO[key]
        return a.copy(), b.copy(), inside.copy()
    la, lb = quadrature.segment_lengths(problem, geometry)
    la, a_empty, a_full = quadrature.clamp_lengths(la, problem.h2, theta)
    lb, b_empty, b_full = quadrature.clamp_lengths(lb, problem.h1, theta)
    clamped = a_empty + a_full + b_empty + b_full
    if clamped:
        obs_trace.event(
            "geom:degenerate-cut",
            theta=theta,
            clamped=clamped,
            to_empty=a_empty + b_empty,
            to_full=a_full + b_full,
            grid=[problem.M, problem.N],
        )
    eps = problem.eps_value
    a = _blend(la, problem.h2, eps, np)
    b = _blend(lb, problem.h1, eps, np)
    inside = np.asarray(
        geom_sdf.is_inside(geometry, x[:, None], y[None, :], np)
    )
    if key is not None:
        if len(_GEOM_MEMO) >= _GEOM_MEMO_MAX:
            _GEOM_MEMO.pop(next(iter(_GEOM_MEMO)))
        _GEOM_MEMO[key] = (a, b, inside)
        return a.copy(), b.copy(), inside.copy()
    return a, b, inside


def assemble(problem: Problem, dtype=jnp.float32, geometry=None, theta=None):
    """Assemble the full global (a, b, rhs) node-grid arrays, shape (M+1, N+1).

    Geometry is evaluated on the host in float64 (see ``assemble_numpy``
    for why this is mandatory) and cast to ``dtype`` — a one-time setup cost,
    exactly as the reference assembles on the CPU host before uploading
    (``poisson_mpi_cuda2.cu:716-759``). Row/col 0 of a,b are zero, matching
    the reference's (M+1)×(N+1) zero-initialised vectors
    (``stage0/Withoutopenmp1.cpp:111-112``). ``geometry``/``theta``
    select the SDF quadrature path (see ``assemble_numpy``); None keeps
    the closed-form ellipse bit-identical to before.
    """
    a, b, rhs = assemble_numpy(problem, geometry=geometry, theta=theta)
    return (
        jnp.asarray(a.astype(numpy_dtype(dtype))),
        jnp.asarray(b.astype(numpy_dtype(dtype))),
        jnp.asarray(rhs.astype(numpy_dtype(dtype))),
    )


def numpy_dtype(dtype):
    """The numpy dtype corresponding to a jax dtype spec."""
    return np.dtype(jnp.dtype(dtype).name)


def assemble_on_device(problem: Problem, dtype=jnp.float32):
    """Assemble the full grid with traced jnp ops (no host work).

    Only use where the trace dtype is f64 (e.g. the CPU-mesh distributed
    tests with x64 enabled) or on coarse grids — see ``assemble_numpy``
    for the f32 precision hazard.
    """
    gi = jnp.arange(problem.M + 1)
    gj = jnp.arange(problem.N + 1)
    a, b = coefficients_at(problem, gi, gj, dtype)
    rhs = rhs_at(problem, gi, gj, dtype)
    return a, b, rhs
