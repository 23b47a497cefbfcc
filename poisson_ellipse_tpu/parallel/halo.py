"""1-cell halo-ring exchange over the device mesh (reference layer L4).

TPU-native replacement for the reference's halo machinery: where stage2
packs first/last interior rows+columns into staging buffers and posts
``MPI_Irecv/Isend`` (``stage2-mpi/poisson_mpi_decomp.cpp:241-347``) and
stage4 additionally stages every halo through the host with
``cudaMemcpy``/``cudaMemcpy2D`` around blocking ``MPI_Sendrecv``
(``poisson_mpi_cuda2.cu:331-500``), here each direction is a single
``lax.ppermute`` of a boundary slice over ICI — device-to-device, no
packing, no host.

Design facts carried over from the reference (SURVEY §5):
- corners ride along: the y-direction exchange operates on the already
  x-extended block, so corner cells propagate in one round
  (``stage2:263-280``),
- missing neighbours (physical boundary, and here also mesh-padding edges)
  receive zeros — exactly the Dirichlet substitution of
  ``stage2:288-324``: ``lax.ppermute`` leaves non-receiving devices with
  zeros by construction, so the boundary condition costs nothing.

Must be called inside ``shard_map`` over a mesh with axes ('x', 'y').
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from poisson_ellipse_tpu.parallel.mesh import AXIS_X, AXIS_Y


def _shift_lo_to_hi(edge, axis_name: str, n: int):
    """Send each device's high edge to its successor; first device gets 0."""
    return lax.ppermute(edge, axis_name, [(i, i + 1) for i in range(n - 1)])


def _shift_hi_to_lo(edge, axis_name: str, n: int):
    """Send each device's low edge to its predecessor; last device gets 0."""
    return lax.ppermute(edge, axis_name, [(i + 1, i) for i in range(n - 1)])


def halo_extend(u, px: int, py: int, width: int = 1):
    """Extend a local (bm, bn) block to (bm+2w, bn+2w) with neighbour halos.

    Zeros appear wherever there is no neighbour (Dirichlet boundary /
    padding). One x-round then one y-round on the extended block, so the
    corner cells are correct after two rounds.

    ``width`` generalises the 5-point stencil's 1-cell ring to w-cell
    slabs — the same nearest-neighbour slab exchange that sequence/
    context parallelism (ring attention) performs on sequence shards, so
    this is the framework's reusable CP-style primitive (SURVEY §5);
    wider stencils or multi-step fusion set width>1. Requires
    width <= min(bm, bn).
    """
    if width < 1:
        raise ValueError("halo width must be >= 1")
    if width > min(u.shape):
        raise ValueError(
            f"halo width {width} exceeds block extent {min(u.shape)}"
        )
    lo_x = _shift_lo_to_hi(u[-width:, :], AXIS_X, px)
    hi_x = _shift_hi_to_lo(u[:width, :], AXIS_X, px)
    u = jnp.concatenate([lo_x, u, hi_x], axis=0)
    lo_y = _shift_lo_to_hi(u[:, -width:], AXIS_Y, py)
    hi_y = _shift_hi_to_lo(u[:, :width], AXIS_Y, py)
    return jnp.concatenate([lo_y, u, hi_y], axis=1)


def halo_edges(u, px: int, py: int):
    """The four 1-cell edges ``halo_extend`` would ring a (bm, bn) block
    with, as received: (lo_x, hi_x) are the (bn,) rows of the x
    predecessor and successor, (lo_y, hi_y) the (bm,) columns of the y
    predecessor and successor, zeros where there is none. No corners: a
    5-point stencil never reads them. The same four ``ppermute``s.

    Every edge travels as a vector: slicing a (bm, 1) column makes XLA
    transpose the whole block it is cut from."""
    lo_x = _shift_lo_to_hi(u[-1], AXIS_X, px)
    hi_x = _shift_hi_to_lo(u[0], AXIS_X, px)
    lo_y = _shift_lo_to_hi(u[:, -1], AXIS_Y, py)
    hi_y = _shift_hi_to_lo(u[:, 0], AXIS_Y, py)
    return lo_x, hi_x, lo_y, hi_y


def halo_extend_stacked(us, px: int, py: int, width: int = 1):
    """Halo exchange for k arrays in one message round.

    ``us`` is (k, bm, bn): k same-shape local blocks stacked on a leading
    axis; returns (k, bm+2w, bn+2w). vmap's collective batching keeps one
    ``ppermute`` per direction carrying the whole (k, w, ·) slab — so
    this is ``halo_extend``'s four messages for all k arrays together,
    halving the message count versus k separate exchanges, which matters
    on ICI where 1-cell halos are latency-bound, not bandwidth-bound.
    The fused-sharded engine uses this to ship the (z, p) pair per
    iteration (``parallel.fused_sharded``)."""
    return jax.vmap(lambda u: halo_extend(u, px, py, width=width))(us)
