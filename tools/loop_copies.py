"""Whole-block layout copies in the compiled sharded PCG loop, with no chip.

Compiles ``parallel.pcg_sharded.build_sharded_solver`` for a described
v5e 2x2 (``jax.experimental.topologies``; the TPU compiler is installed,
the chip need not be) and reads the compiled ``shard_map`` while body
with ``obs.static_cost.loop_body_ops``:

    python3 -m tools.loop_copies 1024 2048 4096x4096

Prints one JSON line per grid: the mesh, the per-device block, every
copy, pad or transpose in the body whose result spans
``obs.static_cost.WHOLE_BLOCK_EXTENT`` or more in each dimension (name,
opcode, extents, estimated cycles), and the body's summed
``estimated_cycles`` — the TPU compiler's own per-iteration estimate,
to size a change to the loop before a chip run. Nothing runs and no
operand is assembled. Run it with ``JAX_PLATFORMS=cpu`` where no chip
is attached. ``tests/test_chip_compile.py`` guards the loop through the
same ``compiled_body``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from unittest import mock

TOPOLOGY = "v5e:2x2"


def _operand_specs(problem, mesh, dtype, g1p, g2p, spec, **_):
    """``_host_sharded_args``' stand-in: the operands' shapes and
    shardings, since a described device can hold no data."""
    import jax
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, spec)
    return tuple(jax.ShapeDtypeStruct((g1p, g2p), dtype, sharding=sharding)
                 for _ in range(3))


def compiled_body(problem, mesh) -> list:
    """``loop_body_ops`` of the float32 sharded solver compiled over
    ``mesh`` (described devices: its operands are shapes only)."""
    import jax.numpy as jnp

    from poisson_ellipse_tpu.obs.static_cost import loop_body_ops
    from poisson_ellipse_tpu.parallel import pcg_sharded

    with mock.patch.object(pcg_sharded, "_host_sharded_args", _operand_specs):
        solver, specs = pcg_sharded.build_sharded_solver(
            problem, mesh, jnp.float32)
    return loop_body_ops(solver.lower(*specs).compile().as_text())


def report(problem, mesh) -> dict:
    from poisson_ellipse_tpu.obs.static_cost import whole_block_relayouts
    from poisson_ellipse_tpu.parallel.mesh import AXIS_X, AXIS_Y, padded_dims

    ops = compiled_body(problem, mesh)
    g1p, g2p = padded_dims(problem.node_shape, mesh)
    px, py = mesh.shape[AXIS_X], mesh.shape[AXIS_Y]
    return {
        "grid": [problem.M, problem.N],
        "mesh": [px, py],
        "block": [g1p // px, g2p // py],
        "relayouts": [[o["name"], o["op"], list(o["dims"]), o["cycles"]]
                      for o in whole_block_relayouts(ops)],
        "body_cycles": sum(o["cycles"] for o in ops),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("grids", nargs="+",
                    help="M or MxN, one or more (M x M when N is left out)")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from poisson_ellipse_tpu.models.problem import Problem
    from poisson_ellipse_tpu.parallel.mesh import make_mesh

    # the chip runs without x64, and a compile for a described device
    # cannot be read back from the persistent cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    mesh = make_mesh(topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY).devices)
    for grid in args.grids:
        m, _, n = grid.partition("x")
        problem = Problem(M=int(m), N=int(n or m))
        print(json.dumps(report(problem, mesh)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
