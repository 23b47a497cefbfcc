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
