"""Full-multigrid (``mg.fmg``) + autotuner (``runtime.autotune``) tests.

Four layers of assertion, mirroring the tentpole's claims:

- **O(N) solver contract**: the F-cycle reaches analytic-solution l2
  parity with mg-pcg across grids; the work-unit model is constant per
  grid point (±20%) across sizes — the asymptotic-work pin;
- **verified handoff**: accuracy is measured, never assumed — a
  crippled F-cycle (zero correction V-cycles) still converges to δ
  through the warm-started mg-pcg handoff, just with more iterations;
- **sharded + guarded forms**: 1×2/2×2 mesh parity with single-chip,
  the jaxpr-pinned per-level halo budget (``halos_per_fcycle``) with
  the classical psum cadence in the handoff loop, and NaN-injection
  recovery through the guard at clean-run iteration parity;
- **autotuner closed loop**: selection is a pure function of the
  telemetry (same telemetry → same config), the static default is
  never beaten by prediction noise (the margin rule), configs persist
  and reload deterministically from the registry file, and
  ``build_solver(engine="auto")`` / the serve scheduler consult them.
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poisson_ellipse_tpu.mg import coarsen
from poisson_ellipse_tpu.mg.fmg import (
    FMGConfig,
    build_fmg_solver,
    default_fmg_config,
    work_units_per_point,
)
from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.runtime import autotune
from poisson_ellipse_tpu.solver.engine import (
    ENGINE_CAPS,
    ENGINES,
    build_solver,
    solve as engine_solve,
)
from poisson_ellipse_tpu.utils.error import l2_error_vs_analytic


def mesh_of(n):
    from poisson_ellipse_tpu.parallel.mesh import make_mesh

    return make_mesh(jax.devices()[:n])


# engine solves reused across tests (each fmg/mg-pcg build pays a
# Lanczos probe + hierarchy + compile — the suite sits near the tier-1
# wall-clock ceiling, so identical solves are computed once)
_SOLVES: dict = {}


def solved(engine: str, grid=(24, 24)):
    key = (engine, grid)
    if key not in _SOLVES:
        _SOLVES[key] = engine_solve(
            Problem(M=grid[0], N=grid[1]), engine, jnp.float32
        )
    return _SOLVES[key]


# -- the O(N) solver contract ------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("grid", [(24, 24), (40, 40)])
def test_fcycle_l2_parity_with_mg_pcg(grid):
    """F-cycle + handoff reaches the same discretization-level accuracy
    as mg-pcg (one-sided: ≤10% worse; the seed usually lands below) —
    the bench `fmg` key's parity rule at test scale."""
    problem = Problem(M=grid[0], N=grid[1])
    fmg = solved("fmg", grid)
    mg = solved("mg-pcg", grid)
    assert bool(fmg.converged) and bool(mg.converged)
    l2_fmg = float(l2_error_vs_analytic(problem, fmg.w))
    l2_mg = float(l2_error_vs_analytic(problem, mg.w))
    assert l2_mg > 0 and l2_fmg <= l2_mg * 1.10, (l2_fmg, l2_mg)
    # the handoff is a WARM start: it must not pay mg-pcg's full count
    assert int(fmg.iters) <= int(mg.iters)


def test_work_units_per_point_constant_across_grids():
    """The O(N) pin: fine-grid-equivalent stencil applications per grid
    point stay within ±20% across ≥3 grid sizes (the geometric level
    sum bounds the model regardless of depth)."""
    units = [
        work_units_per_point(coarsen.num_levels(M, N))
        for M, N in ((64, 64), (256, 256), (1024, 1024), (4096, 4096))
    ]
    assert max(units) <= min(units) * 1.20, units
    # and deeper hierarchies must not grow the per-point bill unboundedly
    assert all(u < 120.0 for u in units), units


@pytest.mark.slow
def test_fcycle_handoff_exits_fast_when_seed_is_good():
    """The verification loop's whole point: when the F-cycle already
    landed at discretization accuracy the handoff is a few polish
    iterations, not an mg-pcg solve from zero."""
    diag = solved("xla", (24, 24))
    fmg = solved("fmg", (24, 24))
    assert bool(fmg.converged)
    assert int(fmg.iters) < int(diag.iters) / 4


# -- the verified handoff ----------------------------------------------------


@pytest.mark.slow
def test_miss_delta_hands_off_to_mg_pcg():
    """A deliberately crippled F-cycle (zero correction V-cycles, a
    2-step coarsest sweep) misses δ — the handoff loop must still
    carry the solve to convergence, with MORE iterations than the
    healthy config: accuracy verified, never assumed."""
    problem = Problem(M=24, N=24)
    crippled = FMGConfig(
        levels=coarsen.num_levels(24, 24),
        n_vcycles=0,
        coarse_degree=2,
    )
    solver, args, _ = build_fmg_solver(problem, jnp.float32,
                                       config=crippled)
    res = solver(*args)
    healthy = solved("fmg", (24, 24))
    assert bool(res.converged)
    assert float(res.diff) < problem.delta
    assert int(res.iters) > int(healthy.iters)
    l2 = float(l2_error_vs_analytic(problem, res.w))
    l2_h = float(l2_error_vs_analytic(problem, healthy.w))
    assert l2 <= l2_h * 1.10


def test_warm_start_init_state_builds_true_residual():
    """``init_state(x0=...)`` must seed w = x0 with r = rhs − A·x0 (the
    handoff's verification contract); x0=None stays the historical
    zero start byte for byte."""
    from poisson_ellipse_tpu.ops import assembly
    from poisson_ellipse_tpu.ops.stencil import apply_a
    from poisson_ellipse_tpu.solver.pcg import init_state

    problem = Problem(M=10, N=10)
    a, b, rhs = assembly.assemble(problem, jnp.float32)
    x0 = jnp.ones_like(rhs) * 0.01
    state = init_state(problem, a, b, rhs, x0=x0)
    h1 = jnp.asarray(problem.h1, jnp.float32)
    h2 = jnp.asarray(problem.h2, jnp.float32)
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(x0))
    np.testing.assert_allclose(
        np.asarray(state[2]),
        np.asarray(rhs - apply_a(x0, a, b, h1, h2)),
        rtol=0, atol=0,
    )
    zero = init_state(problem, a, b, rhs)
    assert not np.asarray(zero[1]).any()
    np.testing.assert_array_equal(np.asarray(zero[2]), np.asarray(rhs))


@pytest.mark.slow
def test_fmg_history_records_the_handoff():
    """``history=True`` returns the handoff loop's ConvergenceTrace with
    iterates bit-identical to the historyless run (the obs contract)."""
    problem = Problem(M=24, N=24)
    solver, args, _ = build_solver(problem, "fmg", jnp.float32,
                                   history=True)
    res, trace = solver(*args)
    plain = solved("fmg", (24, 24))
    assert int(res.iters) == int(plain.iters)
    assert float(res.diff) == float(plain.diff)
    np.testing.assert_array_equal(np.asarray(res.w), np.asarray(plain.w))


# -- sharded + guarded forms -------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("shape", [(1, 2), (2, 2)])
def test_fmg_sharded_parity(shape):
    from jax.sharding import Mesh

    from poisson_ellipse_tpu.parallel.mesh import AXIS_X, AXIS_Y
    from poisson_ellipse_tpu.parallel.mg_sharded import (
        build_fmg_sharded_solver,
    )

    problem = Problem(M=16, N=16)
    single = solved("fmg", (16, 16))
    devs = np.asarray(jax.devices()[: shape[0] * shape[1]]).reshape(shape)
    mesh = Mesh(devs, (AXIS_X, AXIS_Y))
    solver, args = build_fmg_sharded_solver(problem, mesh)
    res = solver(*args)
    assert bool(res.converged)
    assert int(res.iters) == int(single.iters)
    np.testing.assert_allclose(
        np.asarray(res.w), np.asarray(single.w), rtol=0, atol=5e-6,
    )


def test_fmg_sharded_collective_budget_jaxpr_pinned():
    """The sharded F-cycle's collective budget, read from the jaxpr:
    the handoff loop keeps the classical cadence (2 psum/iter — denom +
    the stacked convergence word — and the V-cycle's halo budget), and
    the whole computation's ppermute count covers exactly ONE F-cycle
    (``halos_per_fcycle``) + one handoff-loop body + the per-dispatch
    operand extension — no hidden exchanges."""
    from poisson_ellipse_tpu.analysis.contracts import assert_contract
    from poisson_ellipse_tpu.mg.fmg import DEFAULT_FMG_VCYCLES
    from poisson_ellipse_tpu.parallel.mg_sharded import (
        halos_per_fcycle,
        halos_per_precond,
    )

    problem = Problem(M=16, N=16)
    levels = coarsen.num_levels(16, 16)
    # per handoff iteration: one fine stencil + the V-cycle's halos
    r = assert_contract(
        "collective-cadence", "fmg", problem=problem, mesh_shape=(1, 2)
    )
    assert r.expected == {
        "psum": 2,  # the classical scalar cadence, untouched
        "ppermute": 4 * (1 + halos_per_precond(levels)),
    }, "contract derivation drifted from the hand budget"
    # whole-computation budget: levels' coefficient extensions (once per
    # dispatch), ONE F-cycle, init's precond+stencil, the loop body
    rb = assert_contract(
        "fcycle-budget", "fmg", problem=problem, mesh_shape=(1, 2)
    )
    fcycle_halos = halos_per_fcycle(levels,
                                    n_vcycles=DEFAULT_FMG_VCYCLES)
    init_halos = 1 + halos_per_precond(levels)  # r0 stencil + z0 precond
    loop_halos = 1 + halos_per_precond(levels)
    # coefficient extension: each level's (a, b) PAIR is halo-extended
    # once per dispatch — two exchanges per level
    extend = 2 * levels
    assert rb.expected["ppermute_total"] == 4 * (
        extend + fcycle_halos + init_halos + loop_halos
    ), (rb.expected, fcycle_halos)


@pytest.mark.slow
def test_fmg_guarded_nan_recovery():
    """A NaN injected into the handoff carry must be detected by the
    per-chunk health word and recovered by the residual restart — and
    because every recovery keeps the iterate, the F-cycle's head start
    survives: iteration parity with the clean run."""
    from poisson_ellipse_tpu.resilience import (
        FaultPlan,
        guarded_solve,
        inject_nan,
    )

    problem = Problem(M=24, N=24)
    clean = solved("fmg", (24, 24))
    guarded = guarded_solve(
        problem, "fmg", jnp.float32, chunk=2,
        faults=FaultPlan(inject_nan(2, "r")),
    )
    assert guarded.engine == "fmg"
    assert [e.kind for e in guarded.recoveries] == ["residual-restart"]
    assert bool(guarded.result.converged)
    assert np.isfinite(np.asarray(guarded.result.w)).all()
    assert abs(int(guarded.result.iters) - int(clean.iters)) <= 2


# -- the engine-capability table (the de-dup fix) ----------------------------


def test_engine_caps_is_the_single_source():
    """Every derived tuple must agree with the capability table — the
    one-row-per-engine contract a new engine registers through."""
    from poisson_ellipse_tpu.solver.engine import (
        BATCHED_ENGINES,
        CAPACITY_LADDER,
        HISTORY_ENGINES,
        PRECOND_ENGINES,
        PRECOND_KIND_BY_ENGINE,
        SSTEP_ENGINES,
        STORAGE_ENGINES,
    )

    assert set(ENGINES) == {"auto"} | set(ENGINE_CAPS)
    assert "fmg" in ENGINE_CAPS and ENGINE_CAPS["fmg"]["family"] == "fmg"
    assert set(STORAGE_ENGINES) == {
        e for e, c in ENGINE_CAPS.items() if c["storage"]
    }
    assert set(HISTORY_ENGINES) == {"auto"} | {
        e for e, c in ENGINE_CAPS.items() if c["history"]
    }
    assert set(BATCHED_ENGINES) == {
        e for e, c in ENGINE_CAPS.items() if c["family"] == "batched"
    }
    assert set(SSTEP_ENGINES) == {
        e for e, c in ENGINE_CAPS.items() if c["family"] == "sstep"
    }
    assert PRECOND_KIND_BY_ENGINE == {"mg-pcg": "mg", "cheb-pcg": "cheb"}
    assert set(PRECOND_ENGINES) == {"mg-pcg", "cheb-pcg"}
    assert CAPACITY_LADDER == ("resident", "streamed", "xl", "xla")
    # every tunable knob the table declares is a knob the lint rule
    # fences and the autotuner can sweep
    for engine, caps in ENGINE_CAPS.items():
        for knob in caps["tunables"]:
            assert knob in (
                "levels", "nu", "coarse_degree", "n_vcycles",
                "cheb_degree", "sstep_s", "chunk",
            ), (engine, knob)


# -- the autotuner closed loop -----------------------------------------------


def _fake_telemetry(predicted_iters=500, kappa=4.0e4, gbps=800.0):
    return {
        "grid": [400, 600], "delta": 1e-6, "kappa": kappa,
        "predicted_iters": predicted_iters, "probe_iters": 48,
        "probe_converged": False, "gbps": gbps,
    }


def test_select_is_deterministic_in_the_telemetry():
    """Same telemetry → same config, bit for bit — the replayability
    pin that makes a persisted registry auditable."""
    problem = Problem(M=400, N=600)
    tel = _fake_telemetry()
    a, rows_a = autotune.select(problem, tel)
    b, rows_b = autotune.select(problem, tel)
    assert a == b
    assert rows_a == rows_b


def test_select_never_beats_default_on_noise():
    """A candidate inside the margin of the static default's predicted
    cost must NOT displace it (coin-flip predictions keep the known-
    good policy)."""
    problem = Problem(M=40, N=40)
    # few predicted iterations: the diagonal default is already cheap,
    # so no iteration-count engine can clear the margin
    tel = _fake_telemetry(predicted_iters=3, kappa=4.0)
    chosen, _rows = autotune.select(problem, tel)
    assert chosen.engine == chosen.static_engine


def test_select_prefers_fmg_at_iteration_walls():
    """Many predicted iterations → the F-cycle's constant work wins on
    the model (the 8192²/28.7 s story in miniature)."""
    problem = Problem(M=400, N=600)
    chosen, _rows = autotune.select(
        problem, _fake_telemetry(predicted_iters=5000)
    )
    assert chosen.engine == "fmg"
    assert chosen.static_engine != "fmg"
    assert chosen.predicted_t_s < chosen.static_predicted_t_s
    # the serve chunk knob rides along for the scheduler's consult
    assert 8 <= chosen.knobs["chunk"] <= 128


def test_registry_persistence_round_trip(tmp_path):
    """put → save → load → get hands back the exact config (the
    determinism of select plus this round-trip is what makes the
    persisted winners reproducible)."""
    problem = Problem(M=40, N=40)
    path = os.path.join(tmp_path, "autotune.json")
    reg = autotune.TuneRegistry(path)
    chosen, _ = autotune.select(problem, _fake_telemetry())
    key = autotune.tune_key(problem)
    reg.put(key, chosen)
    reg.save()
    reloaded = autotune.TuneRegistry(path).load()
    assert reloaded.get(key) == chosen
    # the on-disk form is schema-versioned JSON (torn/old files refuse)
    with open(path) as fh:
        rec = json.load(fh)
    assert rec["version"] == autotune.SCHEMA_VERSION
    assert key in rec["entries"]


def test_registry_rejects_wrong_schema_and_torn_files(tmp_path):
    path = os.path.join(tmp_path, "autotune.json")
    with open(path, "w") as fh:
        json.dump({"version": 999, "entries": {"k": {}}}, fh)
    assert autotune.TuneRegistry(path).load().entries == {}
    with open(path, "w") as fh:
        fh.write("{torn")
    assert autotune.TuneRegistry(path).load().entries == {}


def test_tune_key_components(tmp_path):
    """Keys must separate everything that changes the executable or the
    accuracy contract: grid bucket, geometry, dtype, storage, norm."""
    p = Problem(M=40, N=40)
    base = autotune.tune_key(p)
    assert autotune.tune_key(Problem(M=38, N=38)) == base  # same bucket
    assert autotune.tune_key(Problem(M=100, N=100)) != base
    assert autotune.tune_key(p, storage_dtype="bf16") != base
    assert autotune.tune_key(p, jnp.float64) != base
    assert autotune.tune_key(Problem(M=40, N=40, norm="unweighted")) != base
    geom = {"kind": "circle", "r": 0.3}
    assert autotune.tune_key(p, geometry=geom) != base
    # geometry fingerprints are content-stable (key order irrelevant)
    assert autotune.geometry_fingerprint(
        {"r": 0.3, "kind": "circle"}
    ) == autotune.geometry_fingerprint(geom)


def test_build_solver_auto_consults_registry(tmp_path, monkeypatch):
    """A persisted tuned config must steer ``engine="auto"`` — and an
    absent registry must leave the static ladder byte-identical."""
    problem = Problem(M=16, N=16)
    path = os.path.join(tmp_path, "autotune.json")
    reg = autotune.TuneRegistry(path)
    key = autotune.tune_key(problem)
    reg.put(key, autotune.TunedConfig(engine="mg-pcg",
                                      static_engine="resident"))
    reg.save()
    monkeypatch.setattr(autotune, "_REGISTRY", None)
    monkeypatch.setattr(autotune, "registry_path", lambda *a, **k: path)
    _solver, _args, engine = build_solver(problem, "auto", jnp.float32)
    assert engine == "mg-pcg"
    # the kill switch: POISSON_AUTOTUNE=off restores the static pick
    monkeypatch.setenv(autotune.ENV_DISABLE, "off")
    _solver, _args, engine = build_solver(problem, "auto", jnp.float32)
    assert engine != "mg-pcg"


@pytest.mark.slow
def test_tune_end_to_end_persists_and_looks_up(tmp_path):
    """The closed loop on a real (tiny) shape: tune → persist → lookup
    hands back the same engine/knobs the report chose."""
    problem = Problem(M=24, N=24)
    reg = autotune.TuneRegistry(os.path.join(tmp_path, "autotune.json"))
    report = autotune.tune(problem, registry=reg, persist=True)
    got = autotune.lookup(problem, registry=reg)
    assert got is not None
    assert got.engine == report["chosen"]["engine"]
    assert got.knobs == report["chosen"]["knobs"]
    # determinism against the recorded telemetry
    again, _ = autotune.select(problem, report["telemetry"])
    assert again.engine == got.engine


@pytest.mark.slow
def test_scheduler_consults_tuned_chunk(tmp_path, monkeypatch):
    """Warm-pool admission (the scheduler's batch-context creation)
    picks up the tuned per-shape chunk; untuned shapes keep the
    scheduler-wide default."""
    from poisson_ellipse_tpu.serve import Scheduler

    problem = Problem(M=10, N=10)
    path = os.path.join(tmp_path, "autotune.json")
    reg = autotune.TuneRegistry(path)
    reg.put(
        autotune.tune_key(problem),
        autotune.TunedConfig(engine="resident", knobs={"chunk": 24}),
    )
    reg.save()
    monkeypatch.setattr(autotune, "_REGISTRY", None)
    monkeypatch.setattr(autotune, "registry_path", lambda *a, **k: path)
    sched = Scheduler(lanes=2, chunk=8)
    assert sched.submit(problem, request_id="t-0") is None
    sched.drain()
    ctx = next(iter(sched._ctxs.values()))
    assert ctx.chunk == 24
    # an untuned shape's context stays on the scheduler default
    other = Problem(M=100, N=100)
    sched2 = Scheduler(lanes=2, chunk=8)
    assert sched2.submit(other, request_id="t-1") is None
    sched2.drain()
    ctx2 = next(iter(sched2._ctxs.values()))
    assert ctx2.chunk is None


@pytest.mark.slow
def test_default_fmg_config_resolves_probe_once():
    """resolve_fmg_config fills the Lanczos interval only when the
    supplied config is degenerate — a probed config passes through."""
    from poisson_ellipse_tpu.mg.fmg import resolve_fmg_config
    from poisson_ellipse_tpu.ops import assembly

    problem = Problem(M=16, N=16)
    a, b, rhs = assembly.assemble(problem, jnp.float32)
    cfg = resolve_fmg_config(problem, a, b, rhs)
    assert cfg.lo > 0.0
    assert cfg.levels == default_fmg_config(problem).levels
    again = resolve_fmg_config(problem, a, b, rhs, cfg)
    assert again == cfg
    manual = dataclasses.replace(cfg, lo=0.25)
    assert resolve_fmg_config(problem, a, b, rhs, manual) == manual
