"""5-point variable-coefficient stencil and diagonal preconditioner (layer L3).

Two layouts are supported by parallel function pairs:

- **global**: arrays are the full (M+1, N+1) node grid with an implicit
  Dirichlet boundary at rows/cols 0, M, N; the stencil writes the interior
  and leaves the boundary ring at zero — the TPU-native equivalent of the
  reference's interior loops (``stage0/Withoutopenmp1.cpp:75-103``, CUDA
  ``apply_A_kernel`` / ``apply_Dinv_kernel`` at
  ``stage4-mpi+cuda/poisson_mpi_cuda2.cu:507-562``).

- **block**: arrays are one device's halo-extended (bm+2, bn+2) block; the
  stencil evaluates all bm×bn owned nodes (the caller masks non-interior
  nodes), matching the per-rank contract of ``mat_A_local``
  (``stage2-mpi/poisson_mpi_decomp.cpp:194-213``: "requires fresh halos").

Floating-point forms mirror the reference exactly (each difference divided
by h before combining) so iteration counts are bit-comparable.
"""

from __future__ import annotations

import jax.numpy as jnp


def apply_a(w, a, b, h1, h2):
    """A·w on the full node grid; boundary ring stays zero.

    (Aw)_ij = −(a_{i+1,j}(w_{i+1,j}−w_ij)/h1 − a_ij(w_ij−w_{i−1,j})/h1)/h1
              −(b_{i,j+1}(w_{i,j+1}−w_ij)/h2 − b_ij(w_ij−w_{i,j−1})/h2)/h2
    Reference: ``stage0/Withoutopenmp1.cpp:83-85``.
    """
    return jnp.pad(apply_a_block(w, a, b, h1, h2), 1)


def apply_a_block(w_ext, a_ext, b_ext, h1, h2):
    """A·w over one halo-extended block: (bm+2, bn+2) inputs → (bm, bn) output.

    Evaluates every owned node; the caller is responsible for masking nodes
    that are not global-interior (physical boundary / shard padding), exactly
    as ``mat_A_local`` only writes owned interior nodes
    (``stage2-mpi/poisson_mpi_decomp.cpp:194-213``).
    """
    return apply_a_neighbours(
        w_ext[1:-1, 1:-1],
        w_ext[2:, 1:-1], w_ext[:-2, 1:-1], w_ext[1:-1, 2:], w_ext[1:-1, :-2],
        *coefficient_faces(a_ext, b_ext), h1, h2,
    )


def coefficient_faces(a_ext, b_ext):
    """The four (bm, bn) coefficient faces ``apply_a_neighbours`` reads,
    sliced from halo-extended (bm+2, bn+2) blocks: a at i+1 and i, b at
    j+1 and j."""
    return (a_ext[2:, 1:-1], a_ext[1:-1, 1:-1],
            b_ext[1:-1, 2:], b_ext[1:-1, 1:-1])


def apply_a_neighbours(wc, w_e, w_w, w_n, w_s, a_e, a_c, b_n, b_c, h1, h2):
    """A·w from the owned values ``wc`` and their four neighbours (i+1,
    i−1, j+1, j−1), all (bm, bn), and the coefficient faces
    (``coefficient_faces``): ``apply_a_block``'s expression, term for
    term, for callers that hold the neighbours in another form."""
    ax = -(a_e * (w_e - wc) / h1 - a_c * (wc - w_w) / h1) / h1
    ay = -(b_n * (w_n - wc) / h2 - b_c * (wc - w_s) / h2) / h2
    return ax + ay


def diag_d(a, b, h1, h2):
    """Diagonal of A on the full node grid: zero on the boundary ring.

    D_ij = (a_{i+1,j} + a_ij)/h1² + (b_{i,j+1} + b_ij)/h2²
    Reference: ``stage0/Withoutopenmp1.cpp:99``.
    """
    return jnp.pad(diag_d_block(a, b, h1, h2), 1)


def diag_d_block(a_ext, b_ext, h1, h2):
    """Diagonal of A over one halo-extended block → (bm, bn); caller masks."""
    return (a_ext[2:, 1:-1] + a_ext[1:-1, 1:-1]) / (h1 * h1) + (
        b_ext[1:-1, 2:] + b_ext[1:-1, 1:-1]
    ) / (h2 * h2)


def apply_dinv(r, d):
    """z = r / D with the reference's divide-by-zero guard.

    Where D == 0 (boundary ring, padding, degenerate cells) z is 0
    (``stage0/Withoutopenmp1.cpp:100``). Keeping the division explicit
    (rather than precomputing 1/D) preserves bitwise agreement with the
    reference's ``r[i][j] / D_ij``.
    """
    safe = jnp.where(d != 0.0, d, 1.0)
    return jnp.where(d != 0.0, r / safe, 0.0)
