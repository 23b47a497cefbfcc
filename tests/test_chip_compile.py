"""Ahead-of-time compiles of the main path's kernels for a described v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). Each test
builds an engine through ``solver.engine.build_solver`` at a published
grid, compiles it for one v5e chip and asserts a Mosaic kernel
(``tpu_custom_call``) is in the compiled program — what the chip's own
compiler refuses (a misaligned slice, too much VMEM) fails here, at no
chip time. Nothing runs: this says nothing about results or speed.

The topology is described inside module-scoped fixtures, never at
import: only one process may hold the TPU library, and the suite's
workers all import this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.solver.engine import build_solver


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # tpulint: disable=TPU009 — no describable topology: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around
    # them. x64 goes off too — the chip runs without it, and the suite's
    # conftest turns it on for the f64 oracles
    from jax.experimental.compilation_cache import compilation_cache

    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_enable_x64", prev[1])
    compilation_cache.reset_cache()


@pytest.mark.parametrize("engine,grid", [
    ("resident", (800, 1200)),
    ("streamed", (2400, 3200)),
    ("xl", (4096, 4096)),
    ("pallas", (800, 1200)),
])
def test_engine_compiles_a_mosaic_kernel_for_v5e(one_chip, engine, grid):
    solver, args, resolved = build_solver(
        Problem(M=grid[0], N=grid[1]), engine, jnp.float32, interpret=False
    )
    assert resolved == engine
    specs = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
             for x in args]
    compiled = solver.lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("grid", [2048, 4096])
def test_sharded_loop_keeps_blocks_in_their_layout(one_chip, topo, grid):
    """The 2x2 sharded PCG loop body holds no whole-block copy, pad or
    transpose: every block stays in the carry's layout, iteration after
    iteration (the loop re-laid 11 blocks out per iteration at both
    grids when its stencil read a halo-extended block)."""
    from poisson_ellipse_tpu.obs.static_cost import whole_block_relayouts
    from poisson_ellipse_tpu.parallel.mesh import make_mesh
    from tools.loop_copies import compiled_body

    ops = compiled_body(Problem(M=grid, N=grid), make_mesh(topo.devices))
    assert any(o["op"] == "collective-permute-start" for o in ops)
    assert whole_block_relayouts(ops) == []


@pytest.mark.parametrize("engine,grid", [
    ("resident", (40, 60)),
    ("streamed", (400, 600)),
    ("xl", (400, 600)),
])
def test_megakernel_keeps_its_name_in_the_compiled_program(one_chip, engine,
                                                           grid):
    """The device trace names a custom call by its HLO instruction, which
    takes the jitted function's name: each engine's kernel has its own."""
    solver, args, _ = build_solver(
        Problem(M=grid[0], N=grid[1]), engine, jnp.float32, interpret=False
    )
    specs = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
             for x in args]
    text = solver.lower(*specs).compile().as_text()
    calls = [line.split(" = ", 1)[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls == [f"%{engine}_pcg.1"]


def test_served_mesh_programs_keep_each_lane_on_its_chip(one_chip, topo):
    """serve-400x600-4chip's programs at its size, 16 lanes of the
    400x600 request's 512x768 bucket on a 2x2: the refill scatter and
    the lane park hold no collective (left to the partitioner, a lane
    index known only at run time gathers whole stacks onto every chip),
    and the chunk advance only its convergence psum, each chip reading
    its own 4 lanes."""
    from jax.sharding import Mesh

    from poisson_ellipse_tpu.obs.static_cost import compiled_collectives
    from poisson_ellipse_tpu.parallel.batched_sharded import (
        build_sharded_chunk_advance,
        lane_layout,
    )
    from poisson_ellipse_tpu.runtime.compile_cache import grid_bucket
    from poisson_ellipse_tpu.serve.scheduler import (
        ITER_CEILING,
        _mesh_park,
        _mesh_refill_scatter,
    )

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("x", "y"))
    layout = lane_layout(mesh)
    bucket = grid_bucket(400, 600)
    lane = (bucket[0] + 1, bucket[1] + 1)

    def shapes(lead, shardings, dtypes):
        return tuple(
            jax.ShapeDtypeStruct(lead + (lane if s.spec[1:] else ()), d,
                                 sharding=s)
            for s, d in zip(shardings, dtypes))

    f32, i32 = jnp.float32, jnp.int32
    carry = (i32,) + (f32,) * 5 + (jnp.bool_,) * 3 + (i32,)
    operands = shapes((16,), layout.operands, (f32,) * 6)
    state = ((jax.ShapeDtypeStruct((), i32, sharding=layout.replicated),)
             + shapes((16,), layout.state[1:], carry[1:]))
    k = jax.ShapeDtypeStruct((), i32, sharding=layout.replicated)
    unit = (jax.ShapeDtypeStruct((), i32),) + tuple(
        jax.ShapeDtypeStruct((1,) + x.shape[1:], x.dtype) for x in state[1:])
    new_lane = jax.ShapeDtypeStruct(lane, f32)
    scalar = jax.ShapeDtypeStruct((), f32)

    scatter = _mesh_refill_scatter(layout).lower(
        *operands, state, unit, new_lane, new_lane, new_lane,
        scalar, scalar, scalar, jax.ShapeDtypeStruct((), i32)).compile()
    assert set(compiled_collectives(scatter.as_text()).values()) == {0}
    park = _mesh_park(layout).lower(state, jax.ShapeDtypeStruct((), i32))
    assert set(compiled_collectives(park.compile().as_text()).values()) \
        == {0}

    advance, _ = build_sharded_chunk_advance(
        bucket, mesh=mesh, lanes=16, iter_ceiling=ITER_CEILING)
    compiled = advance.lower(*operands, state, k).compile()
    counts = compiled_collectives(compiled.as_text())
    assert {op: n for op, n in counts.items() if n} == {"all-reduce": 2}
    stack = 4 * lane[0] * lane[1] * 4  # a chip's 4 float32 lanes
    assert compiled.memory_analysis().argument_size_in_bytes < 7 * stack
