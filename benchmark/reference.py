"""The plain reference: the fictitious-domain Poisson problem solved by a
textbook preconditioned conjugate-gradient loop, written from the problem
statement alone.

It imports nothing of the program under test. The coefficients are
assembled here on the host in float64 from the closed forms of the
ellipse x² + 4y² < 1 (face lengths inside the domain, the blend
l/h + (1 - l/h)/ε on cut faces, 1/ε on faces outside), and the loop is
the classical one with the diagonal (Jacobi) preconditioner:

    Ap = A p;  α = (z, r)/(Ap, p);  w += α p;  r -= α Ap;  z = r / D
    stop when ‖α p‖ < δ (weighted by h1·h2, the configuration's norm)
    β = (z_new, r_new)/(z, r);  p = z + β p

``storage`` is the width the arrays are kept in between operations:
``float32`` is the configuration; ``bfloat16`` keeps the operands and the
Krylov vectors in bfloat16 and computes in float32 — the control, the
precision one step below what the configuration states.
"""

from __future__ import annotations

import functools

import numpy as np

_FULL_TOL = 1e-9
_EMPTY_TOL = 1e-9
DENOM_GUARD = 1e-15


def problem_spec(config: dict, eps) -> dict:
    """The problem one solve of ``config`` poses, with ``eps`` resolved:
    None stands for the configuration's default, max(h1, h2)²."""
    M, N = config["grid"]
    x0, x1, y0, y1 = config["box"]
    h1, h2 = (x1 - x0) / M, (y1 - y0) / N
    if eps is None:
        eps = max(h1, h2) ** 2
    return {"M": M, "N": N, "x0": x0, "y0": y0, "h1": h1, "h2": h2,
            "eps": float(eps), "f": config["f"], "delta": config["delta"]}


def _inside_length_vertical(x, y_lo, y_hi):
    """Length of the segment {x} × [y_lo, y_hi] inside x² + 4y² < 1."""
    half = np.sqrt(np.maximum((1.0 - x * x) / 4.0, 0.0))
    length = np.maximum(0.0, np.minimum(y_hi, half) - np.maximum(y_lo, -half))
    return np.where(np.abs(x) >= 1.0, 0.0, length)


def _inside_length_horizontal(y, x_lo, x_hi):
    """Length of the segment [x_lo, x_hi] × {y} inside x² + 4y² < 1."""
    half = np.sqrt(np.maximum(1.0 - 4.0 * y * y, 0.0))
    length = np.maximum(0.0, np.minimum(x_hi, half) - np.maximum(x_lo, -half))
    return np.where(np.abs(2.0 * y) >= 1.0, 0.0, length)


def _coefficient(length, h, eps):
    frac = length / h
    return np.where(np.abs(length - h) < _FULL_TOL, 1.0,
                    np.where(length < _EMPTY_TOL, 1.0 / eps,
                             frac + (1.0 - frac) / eps))


def assemble(spec: dict):
    """(a, b, f) on the (M+1)×(N+1) node grid, float64.

    a[i, j] belongs to the vertical face x = x_i - h1/2 between
    y_j ± h2/2, b[i, j] to the horizontal face y = y_j - h2/2 between
    x_i ± h1/2; both are 0 outside 1 ≤ i ≤ M, 1 ≤ j ≤ N. f is the
    right-hand side on interior nodes inside the ellipse, else 0.
    """
    M, N, h1, h2, eps = spec["M"], spec["N"], spec["h1"], spec["h2"], spec["eps"]
    i = np.arange(M + 1, dtype=np.float64)
    j = np.arange(N + 1, dtype=np.float64)
    x = (spec["x0"] + i * h1)[:, None]
    y = (spec["y0"] + j * h2)[None, :]
    a = _coefficient(
        _inside_length_vertical(x - 0.5 * h1, y - 0.5 * h2, y + 0.5 * h2),
        h2, eps)
    b = _coefficient(
        _inside_length_horizontal(y - 0.5 * h2, x - 0.5 * h1, x + 0.5 * h1),
        h1, eps)
    faces = ((i >= 1) & (i <= M))[:, None] & ((j >= 1) & (j <= N))[None, :]
    a = np.where(faces, a, 0.0)
    b = np.where(faces, b, 0.0)
    interior = ((i >= 1) & (i <= M - 1))[:, None] & ((j >= 1) & (j <= N - 1))[None, :]
    f = np.where(interior & (x * x + 4.0 * y * y < 1.0), spec["f"], 0.0)
    return a, b, f


@functools.lru_cache(maxsize=4)
def _loop(storage: str):
    import jax
    import jax.numpy as jnp
    from jax import lax

    st = jnp.dtype(storage)

    def keep(v):
        return v.astype(st).astype(jnp.float32)

    def solve(a, b, f, h1, h2, delta, max_iter):
        a, b, f = keep(a), keep(b), keep(f)
        weight = h1 * h2

        def apply_a(w):
            wc = w[1:-1, 1:-1]
            ax = -(a[2:, 1:-1] * (w[2:, 1:-1] - wc) / h1
                   - a[1:-1, 1:-1] * (wc - w[:-2, 1:-1]) / h1) / h1
            ay = -(b[1:-1, 2:] * (w[1:-1, 2:] - wc) / h2
                   - b[1:-1, 1:-1] * (wc - w[1:-1, :-2]) / h2) / h2
            return jnp.pad(ax + ay, 1)

        d = jnp.pad((a[2:, 1:-1] + a[1:-1, 1:-1]) / (h1 * h1)
                    + (b[1:-1, 2:] + b[1:-1, 1:-1]) / (h2 * h2), 1)

        def precondition(r):
            return jnp.where(d != 0.0, r / jnp.where(d != 0.0, d, 1.0), 0.0)

        def dot(u, v):
            return jnp.sum(u * v) * weight

        w0 = jnp.zeros_like(f)
        z0 = keep(precondition(f))
        # carry: k, w, r, p, (z, r), step norm, converged, broke down
        init = (jnp.int32(0), w0, f, z0, dot(z0, f), jnp.float32(jnp.inf),
                jnp.bool_(False), jnp.bool_(False))

        def cond(s):
            k, _, _, _, _, _, conv, broke = s
            return (k < max_iter) & ~conv & ~broke

        def body(s):
            k, w, r, p, zr, _, _, _ = s
            ap = apply_a(p)
            denom = dot(ap, p)
            broke = denom < DENOM_GUARD
            alpha = zr / jnp.where(broke, 1.0, denom)
            step = alpha * p
            w_new = keep(w + step)
            r_new = keep(r - alpha * ap)
            z = precondition(r_new)
            zr_new = dot(z, r_new)
            diff = jnp.sqrt(jnp.sum(step * step) * weight)
            conv = diff < delta
            beta = zr_new / jnp.where(zr == 0.0, 1.0, zr)
            p_new = keep(z + beta * p)
            # a breakdown keeps the last good iterate
            return (k + 1, jnp.where(broke, w, w_new),
                    jnp.where(broke, r, r_new), jnp.where(broke, p, p_new),
                    jnp.where(broke, zr, zr_new), diff, conv & ~broke, broke)

        k, w, _, _, _, diff, conv, _ = lax.while_loop(cond, body, init)
        return w, k, diff, conv

    return jax.jit(solve)


def solve(spec: dict, storage: str = "float32", max_iter: int | None = None,
          device=None):
    """Solve the problem ``spec`` poses; returns (w as float64 numpy,
    iterations, converged). ``max_iter`` defaults to (M-1)(N-1)."""
    import jax
    import jax.numpy as jnp

    a, b, f = assemble(spec)
    if max_iter is None:
        max_iter = (spec["M"] - 1) * (spec["N"] - 1)
    device = device if device is not None else jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    args = [put(np.asarray(v, np.float32)) for v in (a, b, f)]
    del a, b, f
    w, k, _, conv = _loop(storage)(
        *args, jnp.float32(spec["h1"]), jnp.float32(spec["h2"]),
        jnp.float32(spec["delta"]), jnp.int32(max_iter))
    return np.asarray(w, np.float64), int(k), bool(conv)
