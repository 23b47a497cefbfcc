"""Distributed layer (reference layers L2 partitioning + L4 communication).

The reference's distribution machinery — ``choose_process_grid`` /
``decompose_2d`` (2D block partition of the interior,
``stage2-mpi/poisson_mpi_decomp.cpp:60-111``), nonblocking/blocking halo
exchange (``:241-347``, ``poisson_mpi_cuda2.cu:331-500``) and
``MPI_Allreduce`` scalar reductions — becomes here:

- ``mesh``:   device-mesh factorisation (= choose_process_grid) and global
              grid padding to even shards (= decompose_2d, with the uneven
              remainder handled by zero-padding instead of ±1 block sizes),
- ``halo``:   1-cell halo ring exchange via ``lax.ppermute`` over ICI,
              corners riding along in the second round exactly as the
              reference's edge buffers include corner cells,
- ``pcg_sharded``: the whole PCG solve as ONE ``shard_map``-ped program —
              per iteration: one halo exchange (4 ppermutes) + two ``psum``
              collectives, vs the reference's 4 MPI_Sendrecv (with
              host-staged D2H/H2D copies) + 3 MPI_Allreduce + ≥3
              device-host partial-sum round-trips,
- ``pipelined_sharded``: the Ghysels–Vanroose reordering of the same
              solve — ONE stacked ``psum`` per iteration (all dot
              partials together), overlapped by XLA with the halo
              exchange + stencil; the collective-latency engine,
- ``multihost``: ``jax.distributed.initialize`` lifecycle (= MPI_Init/
              Finalize) and the all-hosts global mesh — the same solver
              code rides ICI within a slice and DCN across hosts.
"""

from poisson_ellipse_tpu.parallel.mesh import choose_process_grid, make_mesh
from poisson_ellipse_tpu.parallel.halo import halo_extend
from poisson_ellipse_tpu.parallel.multihost import (
    global_mesh,
    initialize_multihost,
    process_info,
    shutdown_multihost,
)
from poisson_ellipse_tpu.parallel.pcg_sharded import (
    build_sharded_solver,
    solve_sharded,
)
from poisson_ellipse_tpu.parallel.pipelined_sharded import (
    build_pipelined_sharded_solver,
    solve_pipelined_sharded,
)

__all__ = [
    "choose_process_grid",
    "make_mesh",
    "halo_extend",
    "build_sharded_solver",
    "build_pipelined_sharded_solver",
    "solve_sharded",
    "solve_pipelined_sharded",
    "global_mesh",
    "initialize_multihost",
    "process_info",
    "shutdown_multihost",
]
