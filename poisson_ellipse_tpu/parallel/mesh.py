"""Device-mesh construction (reference layer L2: process-grid + partition).

``choose_process_grid`` reproduces the reference's factorisation exactly
(``stage2-mpi/poisson_mpi_decomp.cpp:60-64``): Px = ⌊√size⌋ decremented to
the nearest divisor, Py = size/Px — a near-square grid with Px ≤ Py.

Where ``decompose_2d`` (``:75-111``) hands out blocks differing by ≤1 row
to low ranks, XLA sharding wants equal shards: we instead zero-pad the
global node grid up to a multiple of the mesh shape. The padding carries
zero coefficients and a zero RHS, so padded nodes behave exactly like the
exterior Dirichlet ring and never influence the interior solve.
"""

from __future__ import annotations

import math
import os

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh

AXIS_X = "x"
AXIS_Y = "y"


def virtual_cpu_devices(n: int):
    """Provision virtual CPU devices without touching the default backend.

    The order-sensitive ritual shared by the driver's multichip dryrun
    gate and the virtual-mesh benchmarks: XLA parses XLA_FLAGS exactly
    once, at the first backend initialisation, so the host-device-count
    flag must be in the environment before any device query; and the
    environment may pin JAX_PLATFORMS to a hardware plugin — under an
    explicit pin, backend discovery REQUIRES that plugin to come up, so a
    sick accelerator runtime would kill even ``jax.devices("cpu")``.
    Platform discovery is therefore restricted to the CPU client, which
    is all these paths need. Backend discovery is one-shot per process:
    after this call the whole process is CPU-only, so callers that need
    accelerator work afterwards must run this in a separate process.

    Returns the CPU client's device list. If XLA_FLAGS already pins a
    host-device count, that count wins (XLA reads the flag once);
    callers needing exactly ``n`` devices must check the length. If the
    flag is absent and some backend already initialised in this process,
    raises RuntimeError immediately (the env edit would be silently
    ignored) instead of letting callers hit a confusing downstream
    device-count error.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # XLA parses XLA_FLAGS exactly once, at the first backend init: if
        # any backend already came up in this process, the flag edit below
        # would be silently ignored and the caller would only see a
        # confusing "need N devices" error far downstream — fail at the
        # cause instead, naming the ordering requirement.
        try:
            from jax._src import xla_bridge as _xb

            initialized = _xb.backends_are_initialized()
        except (ImportError, AttributeError):  # jax internals moved on
            initialized = False
        if initialized:
            raise RuntimeError(
                "virtual_cpu_devices must run before any JAX backend is "
                "initialized in this process (XLA reads XLA_FLAGS only at "
                "the first backend init, so setting the host-device-count "
                "flag now would be silently ineffective). Call it before "
                "any jax.devices()/jit work, or start the process with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={n}."
            )
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
        )
    jax.config.update("jax_platforms", "cpu")
    return jax.devices("cpu")


def pcast_varying(x, axis_names):
    """Mark ``x`` varying over each of ``axis_names`` it is not already
    varying over — a literal built inside ``shard_map`` is invariant, and
    a while_loop carry must match the per-device updates' type.
    ``lax.pcast`` refuses varying→varying, so only the missing axes are
    cast (``jax.typeof(x).vma`` is what ``x`` already varies over)."""
    missing = tuple(a for a in axis_names if a not in jax.typeof(x).vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def choose_process_grid(size: int) -> tuple[int, int]:
    """Factor ``size`` devices into a near-square (px, py), px ≤ py.

    Reference: ``stage2-mpi/poisson_mpi_decomp.cpp:60-64``.
    """
    if size < 1:
        raise ValueError("need at least one device")
    px = int(math.isqrt(size))
    while size % px:
        px -= 1
    return px, size // px


def make_mesh(devices=None) -> Mesh:
    """Build a 2D ('x', 'y') mesh over the given (or all) devices."""
    if devices is None:
        devices = jax.devices()
    px, py = choose_process_grid(len(devices))
    return Mesh(np.asarray(devices).reshape(px, py), (AXIS_X, AXIS_Y))


def padded_dims_of(problem_nodes: tuple[int, int], px: int,
                   py: int) -> tuple[int, int]:
    """Global node-grid dims padded up to multiples of (px, py) — the
    shape-only form, usable when the mesh itself no longer exists (a
    checkpoint written by a dead mesh still names its shape)."""
    g1, g2 = problem_nodes
    return (-(-g1 // px) * px, -(-g2 // py) * py)


def padded_dims(problem_nodes: tuple[int, int], mesh: Mesh) -> tuple[int, int]:
    """Global node-grid dims padded up to multiples of the mesh shape."""
    return padded_dims_of(
        problem_nodes, mesh.shape[AXIS_X], mesh.shape[AXIS_Y]
    )
