"""Multi-host initialisation and mesh construction (DCN scale-out).

The reference scales across nodes with ``mpirun``-launched processes and
Spectrum MPI over the cluster fabric (``README.md:102``; SURVEY §2.7).
The TPU-native equivalent: one Python process per host calls
``jax.distributed.initialize`` (coordinator + process_id, typically all
inferred from the TPU pod metadata/launcher env), after which
``jax.devices()`` spans every host and the same ``Mesh`` + ``shard_map``
code from ``parallel.pcg_sharded`` runs unchanged — XLA routes the halo
``ppermute`` over ICI within a slice and DCN across slices; nothing in
the solver needs to know which.

Thin by design: the entire MPI lifecycle surface of the reference
(``MPI_Init/Comm_rank/Comm_size/Finalize``, ``poisson_mpi_cuda2.cu:
986-990,1036``) collapses into initialize()/shutdown() here.
"""

from __future__ import annotations

from typing import Optional

import jax

from poisson_ellipse_tpu.parallel.mesh import make_mesh


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[list[int]] = None,
) -> None:
    """``MPI_Init`` analog. On TPU pods all arguments are usually inferred
    from the environment (TPU metadata / launcher-set variables); pass
    them explicitly for other fabrics.

    Call exactly once per process, before any other jax API touches the
    backend. Idempotence guard: a second call is a no-op rather than an
    error, matching how the reference tolerates only one MPI_Init.
    """
    if jax.distributed.is_initialized():
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )


def shutdown_multihost() -> None:
    """``MPI_Finalize`` analog."""
    if jax.distributed.is_initialized():
        jax.distributed.shutdown()


def global_mesh():
    """Near-square 2D mesh over every device of every host.

    ``jax.devices()`` is globally consistent across processes after
    ``initialize_multihost``, so each host builds the identical mesh —
    the multi-host replacement for the reference's per-rank
    ``choose_process_grid`` call (``stage2-mpi/poisson_mpi_decomp.cpp:
    60-64``).
    """
    return make_mesh(jax.devices())


def process_info() -> tuple[int, int]:
    """(process_id, num_processes) — the Comm_rank/Comm_size analog."""
    return jax.process_index(), jax.process_count()
