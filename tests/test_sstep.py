"""The memory-bandwidth-frontier battery: s-step CG + bf16 storage.

Covers the two new axes end to end:

- s-step parity: exact f64 oracle counts, the 400×600 f32 headline at
  EXACT classical parity (the f64-Gram accumulator fact —
  ``ops.sstep_pcg.gram_dtype``), sharded 1×2/2×2 parity, and the
  chunk-limit contract.
- the collective-cadence pins: ONE stacked psum + one 4-ppermute deep
  halo round per s iterations, abft on/off byte-identical, vs the
  classical 2-psum body — read from the jaxpr via ``obs.static_cost``.
- the storage axis: ``storage_dtype=None`` traces the byte-identical
  pre-storage jaxpr (pinned), the modeled HBM bytes halve under bf16,
  raw narrow engines converge to the storage floor, and the GUARD's
  storage-promotion rung recovers f32-level l2 on every loop engine.
- composition: streamed/xl operand narrowing, batched lanes, the warm
  pool's storage-keyed executables, harness reports and CLI flags.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.precision import (
    replace_every,
    resolve_storage_dtype,
    storage_itemsize,
)
from poisson_ellipse_tpu.ops.pipelined_pcg import pcg_pipelined
from poisson_ellipse_tpu.ops.sstep_pcg import (
    SSTEP_CHOICES,
    advance as sstep_advance,
    init_state as sstep_init,
    pcg_sstep,
)
from poisson_ellipse_tpu.solver.pcg import pcg
from poisson_ellipse_tpu.solver.engine import (
    ENGINES,
    SSTEP_ENGINES,
    STORAGE_ENGINES,
    build_solver,
    solve,
)

WEIGHTED_ORACLE = {(10, 10): 15, (20, 20): 26, (40, 40): 50}


def _mesh(shape):
    n = shape[0] * shape[1]
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), ("x", "y"))


def _operands(problem, dtype=jnp.float32):
    return assembly.assemble(problem, dtype)


# -- registry / validation ---------------------------------------------------


def test_engine_registry_carries_the_new_axes():
    assert "sstep" in ENGINES and "sstep-pallas" in ENGINES
    assert set(SSTEP_ENGINES) <= set(ENGINES)
    assert "sstep" in STORAGE_ENGINES and "xla" in STORAGE_ENGINES
    # the identity request normalises away; widening is refused
    assert resolve_storage_dtype("f32", jnp.float32) is None
    assert resolve_storage_dtype(None, jnp.float32) is None
    assert resolve_storage_dtype("bf16", jnp.float32) == jnp.dtype(
        jnp.bfloat16
    )
    with pytest.raises(ValueError, match="wider"):
        resolve_storage_dtype("f32", jnp.bfloat16)
    with pytest.raises(ValueError, match="unknown storage dtype"):
        resolve_storage_dtype("nonsense", jnp.float32)
    with pytest.raises(ValueError, match="floating"):
        resolve_storage_dtype("int8", jnp.float32)


def test_build_solver_validates_the_new_axes():
    problem = Problem(M=10, N=10)
    with pytest.raises(ValueError, match="no storage-dtype form"):
        build_solver(problem, "resident", storage_dtype="bf16")
    with pytest.raises(ValueError, match="history"):
        build_solver(problem, "sstep", history=True)
    with pytest.raises(ValueError, match="s must be one of"):
        pcg_sstep(problem, *_operands(problem), s=3)
    # the cadence tightens under sub-compute storage and divides both s
    assert replace_every(None) == 32 and replace_every(jnp.bfloat16) == 8
    for s in SSTEP_CHOICES:
        assert replace_every(None) % s == 0
        assert replace_every(jnp.bfloat16) % s == 0


def test_storage_none_traces_the_identical_jaxpr():
    """The storage axis at None is byte-identical to the pre-storage
    code: same jaxpr for classical AND pipelined — the declared
    ``storage-identity`` contract (expectations from ENGINE_CAPS)."""
    from poisson_ellipse_tpu.analysis.contracts import assert_contract

    problem = Problem(M=20, N=20)
    assert_contract("storage-identity", "xla", problem=problem)
    assert_contract("storage-identity", "pipelined", problem=problem)


# -- s-step parity -----------------------------------------------------------


@pytest.mark.parametrize("s", SSTEP_CHOICES)
@pytest.mark.parametrize("grid", sorted(WEIGHTED_ORACLE))
def test_sstep_f64_oracle_parity(grid, s):
    """f64: exact classical-oracle iteration counts, both block sizes."""
    problem = Problem(M=grid[0], N=grid[1])
    a, b, rhs = _operands(problem, jnp.float64)
    r = pcg_sstep(problem, a, b, rhs, s=s)
    assert bool(r.converged)
    assert int(r.iters) == WEIGHTED_ORACLE[grid]


@pytest.mark.parametrize("s", SSTEP_CHOICES)
def test_sstep_headline_grid_f32_exact_parity(s):
    """400×600 f32: the published 546-iteration oracle, EXACTLY — the
    measured f64-Gram-accumulator fact (an f32 Gram loses it: 773)."""
    problem = Problem(M=400, N=600)
    a, b, rhs = _operands(problem)
    r = pcg_sstep(problem, a, b, rhs, s=s)
    assert bool(r.converged)
    assert int(r.iters) == 546


@pytest.mark.slow
def test_sstep_800x1200_f32_parity_within_replacement_band():
    """The second acceptance grid (slow: ~2000 iterations on CPU):
    iteration count within ±2 per replacement of the 989 oracle."""
    problem = Problem(M=800, N=1200)
    a, b, rhs = _operands(problem)
    r = pcg_sstep(problem, a, b, rhs, s=4)
    band = 2 * (989 // replace_every(None) + 1)
    assert bool(r.converged)
    assert abs(int(r.iters) - 989) <= band


def test_sstep_chunked_advance_honours_limit_exactly():
    """A chunk limit mid-block stops at EXACTLY that iteration (the
    guard/fault-injection contract) and the chunked run converges at
    the straight run's count (iteration-equivalence; the mid-block
    basis re-anchor is documented as not bitwise)."""
    problem = Problem(M=40, N=40)
    a, b, rhs = _operands(problem)
    straight = pcg_sstep(problem, a, b, rhs, s=4)
    state = sstep_init(problem, a, b, rhs)
    for limit in (13, 26, 39, problem.max_iterations):
        state = sstep_advance(problem, a, b, rhs, state, s=4, limit=limit)
        assert int(state[0]) <= max(limit, int(straight.iters))
        if not bool(state[6]):
            assert int(state[0]) == limit  # exact stop, not block-rounded
    assert bool(state[6])
    assert int(state[0]) == int(straight.iters)


@pytest.mark.parametrize("mesh_shape", [(1, 2), (2, 2)])
def test_sstep_sharded_matches_single_chip(mesh_shape):
    from poisson_ellipse_tpu.parallel.sstep_sharded import (
        solve_sstep_sharded,
    )

    problem = Problem(M=40, N=40)
    a, b, rhs = _operands(problem)
    single = pcg(problem, a, b, rhs)
    r = solve_sstep_sharded(problem, _mesh(mesh_shape), jnp.float32, s=4)
    assert bool(r.converged)
    assert abs(int(r.iters) - int(single.iters)) <= 2
    rel = np.linalg.norm(np.asarray(r.w) - np.asarray(single.w)) / (
        np.linalg.norm(np.asarray(single.w))
    )
    assert rel < 5e-3


# -- the collective-cadence pins --------------------------------------------


@pytest.mark.parametrize("s", SSTEP_CHOICES)
def test_sstep_sharded_pins_one_psum_per_s_iterations(s):
    """THE acceptance pin, as declared contracts: the sharded s-step
    while body holds exactly 1 psum and 4 ppermutes — per body = per s
    iterations — abft on and off byte-identical, vs the classical
    body's 2 psums. Expectations derive from ENGINE_CAPS; the exact
    (1, 4) cadence is re-pinned on the results."""
    from poisson_ellipse_tpu.analysis.contracts import assert_contract
    from poisson_ellipse_tpu.obs.static_cost import iters_per_loop_body

    problem = Problem(M=40, N=40)
    r = assert_contract(
        "collective-cadence", "sstep", problem=problem,
        mesh_shape=(1, 2), sstep_s=s,
    )
    assert r.expected == {"psum": 1, "ppermute": 4}
    assert iters_per_loop_body("sstep", s) == s
    # the stepper form, abft on == off, at the same (1, 4) cadence
    ra = assert_contract(
        "abft-identity", "sstep", problem=problem, mesh_shape=(1, 2),
        sstep_s=s,
    )
    assert ra.actual == {"off": (1, 4), "on": (1, 4)}, ra.actual
    rc = assert_contract(
        "collective-cadence", "xla", problem=problem, mesh_shape=(1, 2)
    )
    assert rc.expected["psum"] == 2


def test_engine_report_divides_body_counts_per_iteration():
    from poisson_ellipse_tpu.obs.static_cost import engine_report

    rep = engine_report(
        Problem(M=40, N=40), "sstep", mode="sharded", mesh_shape=(1, 2),
        with_xla_cost=False, sstep_s=4,
    )
    assert rep["iters_per_body"] == 4
    assert rep["psum_per_body"] == 1
    assert rep["ppermute_per_body"] == 4
    assert rep["psum_per_iter"] == pytest.approx(0.25)


# -- the storage axis --------------------------------------------------------


def test_modeled_bytes_halve_under_bf16():
    """The modeled-byte acceptance: every loop engine's bf16 bill sits
    at ~half the f32 bill and inside the ≤0.6× gate. The classical loop
    is exactly 0.5×; the recurrence engines carry the extra rebuild
    passes of their TIGHTENED replacement cadence (32 → 8 under bf16) in
    the narrow model, so their ratio sits slightly above 0.5 — the model
    tells the truth about the narrow build, not the optimistic half."""
    from poisson_ellipse_tpu.harness.roofline import (
        modeled_hbm_bytes_per_iter,
    )

    problem = Problem(M=400, N=600)
    for engine in ("xla", "pipelined", "sstep"):
        full = modeled_hbm_bytes_per_iter(problem, engine, jnp.float32)
        narrow = modeled_hbm_bytes_per_iter(
            problem, engine, jnp.float32, storage_dtype="bf16"
        )
        ratio = narrow / full
        assert 0.45 <= ratio <= 0.6, (engine, ratio)
    xla_full = modeled_hbm_bytes_per_iter(problem, "xla", jnp.float32)
    xla_narrow = modeled_hbm_bytes_per_iter(
        problem, "xla", jnp.float32, storage_dtype="bf16"
    )
    assert xla_narrow / xla_full == pytest.approx(0.5)
    assert storage_itemsize(jnp.float32, "bf16") == 2
    assert storage_itemsize(jnp.float32) == 4


@pytest.mark.parametrize("engine", ["xla", "pipelined", "sstep"])
def test_guarded_bf16_recovers_f32_l2_parity(engine):
    """The accuracy-recovered-not-hoped acceptance: the guard's
    storage-promotion rung finishes every narrow solve at full width,
    landing within a tight band of the f32 solution's analytic error."""
    from poisson_ellipse_tpu.resilience.guard import guarded_solve
    from poisson_ellipse_tpu.utils.error import l2_error_vs_analytic

    problem = Problem(M=40, N=40)
    ref = solve(problem, "xla", jnp.float32)
    ref_l2 = float(l2_error_vs_analytic(problem, ref.w))
    g = guarded_solve(
        problem, engine, jnp.float32, storage_dtype="bf16", chunk=64
    )
    assert bool(g.result.converged)
    got_l2 = float(
        l2_error_vs_analytic(problem, g.result.w.astype(jnp.float32))
    )
    assert got_l2 <= 1.05 * ref_l2, (engine, got_l2, ref_l2)
    kinds = [e.kind for e in g.recoveries]
    # the promotion rung fired (directly, or as the escalation rung
    # after a restart — both spellings are the designed ladder)
    assert "storage-promotion" in kinds or "precision-escalation" in kinds


def test_raw_bf16_classical_converges_and_carries_bf16_state():
    problem = Problem(M=40, N=40)
    a, b, rhs = _operands(problem)
    r = pcg(problem, a, b, rhs, storage_dtype="bf16")
    assert r.w.dtype == jnp.bfloat16
    assert bool(r.converged)
    # the raw narrow engine's answer sits at the storage floor — close
    # to, but NOT at, f32 accuracy (which is the guard's job)
    ref = pcg(problem, a, b, rhs)
    rel = float(
        jnp.linalg.norm(r.w.astype(jnp.float32) - ref.w)
        / jnp.linalg.norm(ref.w)
    )
    assert rel < 0.05


def test_streamed_and_xl_narrow_operand_streams():
    """streamed/xl: bf16 operand streaming converges at the f32 cell's
    iteration count (the operator rounds once; state stays full-width)."""
    from poisson_ellipse_tpu.ops.streamed_pcg import build_streamed_solver
    from poisson_ellipse_tpu.ops.xl_pcg import build_xl_solver

    problem = Problem(M=20, N=20)
    for build in (build_streamed_solver, build_xl_solver):
        s_full, a_full = build(problem, jnp.float32, interpret=True)
        r_full = s_full(*a_full)
        s_bf, a_bf = build(
            problem, jnp.float32, interpret=True, storage_dtype="bf16"
        )
        assert a_bf[0].dtype == jnp.bfloat16  # dinv streams narrow
        assert a_bf[3].dtype == jnp.float32   # r0 stays compute-width
        r_bf = s_bf(*a_bf)
        assert bool(r_bf.converged)
        assert int(r_bf.iters) == int(r_full.iters)
        rel = float(
            jnp.linalg.norm(r_bf.w - r_full.w) / jnp.linalg.norm(r_full.w)
        )
        assert rel < 5e-3


def test_batched_lanes_compose_with_bf16_storage():
    from poisson_ellipse_tpu.batch.batched_pcg import pcg_batched

    problem = Problem(M=20, N=20)
    a, b, rhs = _operands(problem)
    stacked = jnp.stack([rhs, rhs * 1.5, rhs * 0.5])
    r = pcg_batched(problem, a, b, stacked, storage_dtype="bf16")
    assert r.w.dtype == jnp.bfloat16
    assert bool(jnp.all(r.converged))
    assert not bool(jnp.any(r.quarantined))
    # linearity spot-check at the storage floor: lane 1 ≈ 1.5 × lane 0
    w0 = np.asarray(r.w[0].astype(jnp.float32))
    w1 = np.asarray(r.w[1].astype(jnp.float32))
    assert np.linalg.norm(w1 - 1.5 * w0) / np.linalg.norm(w1) < 0.05


def test_warm_pool_keys_on_storage_dtype():
    from poisson_ellipse_tpu.runtime.compile_cache import WarmPool

    pool = WarmPool()
    full = pool.warmup("batched", (10, 10), lanes=2)
    again = pool.warmup("batched", (10, 10), lanes=2)
    narrow = pool.warmup("batched", (10, 10), lanes=2,
                         storage_dtype="bf16")
    assert again.compiled is full.compiled  # the hit-identity contract
    assert narrow.compiled is not full.compiled
    assert narrow.storage == "bfloat16" and full.storage == ""
    assert pool.hits == 1 and pool.misses == 2


def test_sstep_bf16_sharded_ships_narrow_state():
    """The sharded composition of BOTH axes: bf16 blocks through the
    (s+1)-deep exchange, converging to the storage floor with the
    cadence pin intact."""
    from poisson_ellipse_tpu.obs.static_cost import loop_collectives
    from poisson_ellipse_tpu.parallel.sstep_sharded import (
        build_sstep_sharded_stepper,
    )

    problem = Problem(M=40, N=40)
    mesh = _mesh((1, 2))
    init, adv = build_sstep_sharded_stepper(
        problem, mesh, jnp.float32, s=4, storage_dtype="bf16"
    )
    state = init()
    assert state[1].dtype == jnp.bfloat16
    assert loop_collectives(lambda st: adv(st, 100), (state,)) == (1, 4)
    out = adv(state, problem.max_iterations)
    # the raw narrow run reaches the storage floor and stays finite —
    # full-width finishing is the guard's promotion rung
    assert float(out[5]) < 1e-3
    assert bool(jnp.all(jnp.isfinite(out[1].astype(jnp.float32))))


# -- harness surfaces --------------------------------------------------------


def test_run_once_sstep_and_storage_reports():
    from poisson_ellipse_tpu.harness.run import run_once

    problem = Problem(M=20, N=20)
    rep = run_once(problem, mode="single", engine="sstep")
    assert rep.engine == "sstep" and rep.converged
    assert rep.json_dict()["engine"] == "sstep"
    guarded = run_once(
        problem, mode="single", engine="xla", guard=True,
        storage_dtype="bf16",
    )
    assert guarded.converged
    assert guarded.storage_dtype == "bf16"
    assert guarded.json_dict()["storage_dtype"] == "bf16"
    assert "storage bf16" in guarded.summary()
    with pytest.raises(ValueError, match="storage"):
        run_once(problem, mode="sharded", engine="xla",
                 mesh_shape=(1, 2), storage_dtype="bf16")


def test_harness_inspect_cli_reports_sstep_cadence(capsys):
    from poisson_ellipse_tpu.harness.__main__ import main as harness_main

    rc = harness_main([
        "inspect", "sstep", "--mode", "sharded", "--mesh", "1", "2",
        "--grid", "20x20", "--no-xla-cost",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "per while-body (4 iters): 1 psum, 4 ppermute" in out
    rc = harness_main([
        "inspect", "sstep", "--grid", "20x20", "--no-xla-cost",
        "--storage-dtype", "bf16",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "storage bfloat16" in out


@pytest.mark.parametrize("ceiling, ok", [(68, True), (55, False)])
def test_acceptance_sstep_row_gates_f32_gram_on_its_band(monkeypatch,
                                                         ceiling, ok):
    """x64 off (the chip's default) the acceptance s-step row holds the
    count to [oracle, F32_GRAM_CEILINGS] — the CPU's f32-Gram count at
    40² (61) is inside the committed band and outside a tighter one."""
    from poisson_ellipse_tpu.harness import acceptance

    monkeypatch.setitem(acceptance.F32_GRAM_CEILINGS, (40, 40), ceiling)
    with jax.enable_x64(False):
        row_ok, note = acceptance._row("sstep", 40, 40, 50)
    assert row_ok is ok, note
    assert f"..{ceiling}, f32 Gram" in note
    # with x64 (the suite's default) the classical oracle applies exactly
    assert acceptance._row("sstep", 40, 40, 50) == (
        True, "iters=50 (oracle 50)")
