"""The single-chip engine suite: fused/resident/streamed/xl vs the XLA path.

The reference's cross-implementation correctness oracle is iteration-count
invariance across its five implementations (SURVEY §4.2: the same grid
converges in the same number of PCG iterations in every stage). The
TPU engines are held to the same standard — identical iteration counts and
matching solutions on the oracle grids — plus capacity-gate and selection-
policy checks. Pallas kernels run in interpret mode on the CPU backend
(the engines' own ``_interpret_default``), so this suite needs no TPU.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from poisson_ellipse_tpu.harness.__main__ import main as cli_main
from poisson_ellipse_tpu.harness.run import _chain_solver, run_once
from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops.fused_pcg import interior_normalized, solve_fused
from poisson_ellipse_tpu.ops.resident_pcg import fits_resident, solve_resident
from poisson_ellipse_tpu.ops.streamed_pcg import (
    StreamPlan,
    build_streamed_solver,
    fits_streamed,
    solve_streamed,
)
from poisson_ellipse_tpu.ops.xl_pcg import XLPlan, build_xl_solver, solve_xl
from poisson_ellipse_tpu.solver.engine import build_solver, select_engine, solve
from poisson_ellipse_tpu.solver.pcg import solve as solve_xla

ENGINES = {
    "fused": solve_fused,
    "resident": solve_resident,
    "streamed": solve_streamed,
    "xl": solve_xl,
}

# committed reference code oracles (see tests/test_pcg.py for provenance)
UNWEIGHTED_ORACLE = {(10, 10): 17, (20, 20): 31, (40, 40): 61}
WEIGHTED_ORACLE = {(10, 10): 15, (20, 20): 26, (40, 40): 50}


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("M,N", sorted(UNWEIGHTED_ORACLE))
def test_parity_unweighted(engine, M, N):
    problem = Problem(M=M, N=N, norm="unweighted")
    ref = solve_xla(problem, jnp.float32)
    got = ENGINES[engine](problem, jnp.float32)
    assert int(got.iters) == int(ref.iters) == UNWEIGHTED_ORACLE[(M, N)]
    assert bool(got.converged)
    assert not bool(got.breakdown)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), atol=5e-6
    )


@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("M,N", [(20, 20), (40, 40)])
def test_parity_weighted(engine, M, N):
    problem = Problem(M=M, N=N, norm="weighted")
    ref = solve_xla(problem, jnp.float32)
    got = ENGINES[engine](problem, jnp.float32)
    assert int(got.iters) == int(ref.iters) == WEIGHTED_ORACLE[(M, N)]
    assert bool(got.converged)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), atol=5e-6
    )


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_parity_non_aligned_multi_tile(engine):
    """A shape that is neither row-tile- nor lane-aligned, spanning
    multiple tiles in every engine's tiling."""
    problem = Problem(M=44, N=132, norm="weighted")
    ref = solve_xla(problem, jnp.float32)
    got = ENGINES[engine](problem, jnp.float32)
    assert int(got.iters) == int(ref.iters)
    assert bool(got.converged)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), atol=5e-6
    )


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_max_iter_cap(engine):
    problem = Problem(M=40, N=40, max_iter=5)
    got = ENGINES[engine](problem, jnp.float32)
    assert int(got.iters) == 5
    assert not bool(got.converged)
    assert not bool(got.breakdown)


def test_bf16_path_converges_on_every_engine():
    """bf16 is an advertised dtype on every Pallas engine and the XLA
    path: with a bf16-reachable threshold each converges to an L2 error
    in the same decade as the converged f32/f64 result at this grid
    (~3.7e-3), and iteration counts stay within bf16-rounding slack of
    the XLA path (exact invariance is an f32/f64 contract only)."""
    problem = Problem(M=40, N=40, delta=1e-4)
    ref = solve_xla(problem, jnp.bfloat16)
    assert bool(ref.converged)
    from poisson_ellipse_tpu.utils.error import l2_error_vs_analytic

    for name, fn in {**ENGINES, "xla": solve_xla}.items():
        got = fn(problem, jnp.bfloat16)
        assert bool(got.converged), name
        assert abs(int(got.iters) - int(ref.iters)) <= 3, name
        assert float(l2_error_vs_analytic(problem, got.w)) < 1e-2, name


@pytest.mark.parametrize("dtype", ["f64"])
def test_engines_reject_f64(dtype):
    problem = Problem(M=10, N=10)
    for fn in ENGINES.values():
        with pytest.raises(ValueError):
            fn(problem, jnp.float64)


# ---------------------------------------------------------------- capacity


def test_fits_resident_small_and_large():
    assert fits_resident(Problem(M=40, N=40))
    assert fits_resident(Problem(M=800, N=1200))
    assert not fits_resident(Problem(M=1600, N=2400))


def test_fits_streamed_gate():
    assert fits_streamed(Problem(M=1600, N=2400))
    assert fits_streamed(Problem(M=2400, N=3200))
    # north-star 4096²: state alone (~201 MB) exceeds VMEM
    assert not fits_streamed(Problem(M=4096, N=4096))


def test_streamed_build_rejects_oversize():
    with pytest.raises(ValueError, match="VMEM"):
        build_streamed_solver(Problem(M=4096, N=4096))


def test_streamed_forced_all_streaming_parity(monkeypatch):
    """Force resident={all False} so the double-buffered DMA pipeline
    (slot reads, ap store lag, tail drain) actually executes — every grid
    small enough for tests otherwise resolves to an all-resident plan."""
    import poisson_ellipse_tpu.ops.streamed_pcg as sp

    problem = Problem(M=200, N=132, norm="weighted")
    ref = solve_xla(problem, jnp.float32)
    # pin tm=64: the budget arithmetic below assumes one tile size (the
    # auto policy would otherwise re-spend the forced budget on tm=128)
    base_plan = StreamPlan(problem, jnp.float32, tm=64)
    state_bytes = (3 * base_plan.g1p + 16) * base_plan.g2p * 4
    monkeypatch.setattr(
        sp, "_VMEM_USABLE", state_bytes + base_plan.min_stream_bytes
    )
    plan = sp.StreamPlan(problem, jnp.float32, tm=64)
    assert plan.fits and not any(plan.resident.values())
    assert plan.n_tiles >= 3  # exercises even/odd slots + tail drain
    solver, args = sp.build_streamed_solver(problem, jnp.float32, tm=64)
    got = solver(*args)
    assert int(got.iters) == int(ref.iters)
    assert bool(got.converged)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), atol=5e-6
    )


def test_stream_plan_residency_prefers_ap(monkeypatch):
    """The greedy residency upgrade takes ap (written+read = 2 HBM
    passes/iter) before dinv (1 pass — the z-state regime reads it only
    in pass C); with budget for exactly one full array the plan must
    keep ap resident and stream dinv, and the solve in that mixed
    regime (z-state + resident ap) must still match the XLA path."""
    import poisson_ellipse_tpu.ops.streamed_pcg as sp

    problem = Problem(M=200, N=132, norm="weighted")
    ref = solve_xla(problem, jnp.float32)
    base = StreamPlan(problem, jnp.float32, tm=64)
    state_bytes = (3 * base.g1p + 16) * base.g2p * 4
    ap_upgrade = (
        base.full_rows["ap"] - base.tile_rows["ap"]
    ) * base.g2p * 4
    monkeypatch.setattr(
        sp, "_VMEM_USABLE",
        state_bytes + base.min_stream_bytes + ap_upgrade,
    )
    plan = sp.StreamPlan(problem, jnp.float32, tm=64)
    assert plan.resident["ap"] and not plan.resident["dinv"]
    solver, args = sp.build_streamed_solver(problem, jnp.float32, tm=64)
    got = solver(*args)
    assert int(got.iters) == int(ref.iters)
    assert bool(got.converged)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), atol=5e-6
    )


def test_select_engine_scales_with_device_vmem(monkeypatch):
    """The capacity gates key off device_kind VMEM capacity
    (``utils.device``): a small-VMEM part must drop 800x1200 out of the
    resident engine, a large-VMEM part must pull 1600x2400 into it —
    both with the injected kinds, while unknown kinds reproduce the
    measured bench-part behaviour exactly."""
    from poisson_ellipse_tpu.solver.engine import select_engine
    from poisson_ellipse_tpu.utils import device as devmod

    class _Fake:
        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setitem(devmod._VMEM_CAPACITY, "TPU tiny-test", 32 * 1024 * 1024)
    monkeypatch.setitem(devmod._VMEM_CAPACITY, "TPU big-test", 512 * 1024 * 1024)
    small, big = _Fake("TPU tiny-test"), _Fake("TPU big-test")
    # measured part: 800x1200 resident, 1600x2400 streamed
    assert select_engine(Problem(M=800, N=1200)) == "resident"
    assert select_engine(Problem(M=1600, N=2400)) == "streamed"
    # quarter-VMEM part: 800x1200 no longer fits resident
    assert not fits_resident(Problem(M=800, N=1200), device=small)
    assert select_engine(Problem(M=800, N=1200), device=small) == "streamed"
    # 4x-VMEM part: 1600x2400 becomes resident, 4096^2 becomes streamable
    assert select_engine(Problem(M=1600, N=2400), device=big) == "resident"
    assert select_engine(Problem(M=4096, N=4096), device=big) == "streamed"
    # a grid beyond the small part's streamed gate takes the xl kernel
    assert select_engine(Problem(M=2400, N=3200), device=small) == "xl"
    # a non-TPU device (no platform "tpu") takes the measured budgets
    assert select_engine(
        Problem(M=800, N=1200), device=_Fake("mystery")
    ) == "resident"


def test_vmem_capacity_table_and_scaling():
    """utils.device directly: the chip's kind hits the table, non-TPU
    devices (including the CPU devices the suite runs on) take the
    measured 128 MiB part — so a budget scales by exactly 1.0 there —
    and a TPU of a kind the table does not hold is an error naming the
    kind, never a guessed budget."""
    from poisson_ellipse_tpu.utils.device import (
        scaled_vmem_budget,
        vmem_capacity_bytes,
    )

    class _Fake:
        def __init__(self, kind, platform="cpu"):
            self.device_kind = kind
            self.platform = platform

    mib = 1024 * 1024
    assert vmem_capacity_bytes(_Fake("TPU v5 lite", "tpu")) == 128 * mib
    assert vmem_capacity_bytes(_Fake("not-a-tpu")) == 128 * mib
    assert scaled_vmem_budget(114 * mib, _Fake("unknown")) == 114 * mib
    with pytest.raises(ValueError, match="TPU v9 mystery"):
        vmem_capacity_bytes(_Fake("TPU v9 mystery", "tpu"))
    # the suite's default (CPU) device takes the fallback too
    assert scaled_vmem_budget(125 * mib) == 125 * mib


def test_cli_engine_xl(capsys):
    """--engine xl through the CLI surface (interpret mode on CPU)."""
    rc = cli_main(["40", "40", "--mode", "single", "--engine", "xl", "--json"])
    assert rc == 0
    import json as _json

    rec = _json.loads(capsys.readouterr().out.strip())
    assert rec["engine"] == "xl" and rec["iters"] == 50
    assert rec["converged"] is True


def test_xl_plan_tile_policy_and_forced_tiles():
    """The default tile minimises padded rows (96 at 4097 node rows ->
    g1p 4128, vs 4224 with 128); forced small tiles exercise the
    multi-tile ring/store-lag pipeline on a grid tests can afford."""
    plan = XLPlan(Problem(M=4096, N=4096), jnp.float32)
    assert plan.tm == 96 and plan.g1p == 4128
    assert XLPlan(Problem(M=4096, N=4096), jnp.float32).passes_per_iter() \
        == pytest.approx(12.0 + 8.0 / 96)
    with pytest.raises(ValueError, match="multiple of 8"):
        XLPlan(Problem(M=100, N=100), jnp.float32, tm=100)
    problem = Problem(M=40, N=40)
    ref = solve_xla(problem, jnp.float32)
    for tm in (8, 16):
        solver, args = build_xl_solver(problem, tm=tm)
        got = solver(*args)
        assert int(got.iters) == int(ref.iters) == 50, tm
        np.testing.assert_allclose(
            np.asarray(got.w), np.asarray(ref.w), atol=5e-6
        )


def test_stream_plan_shapes():
    plan = StreamPlan(Problem(M=1600, N=2400), jnp.float32)
    assert plan.g1p % plan.tm == 0
    assert plan.g2p % 128 == 0
    assert plan.n_tiles == plan.g1p // plan.tm
    assert plan.fits
    # residency must be a subset of what the budget allows; the always-
    # resident state is excluded from the dict
    assert set(plan.resident) == {"dinv", "ap", "a", "b"}
    assert plan.streamed_passes_per_iter() >= 0.0


def test_stream_plan_auto_tile_policy():
    # all-resident at both tile sizes -> auto takes the bigger tile
    p_mid = Problem(M=1600, N=2400)
    assert StreamPlan(p_mid, jnp.float32).tm == 128
    assert StreamPlan(p_mid, jnp.float32, tm=64).tm == 64
    # auto never trades HBM traffic for tile size: whatever it picks
    # streams no more passes per iteration than tm=64 would
    for M, N in ((1600, 2400), (2000, 2800), (2400, 3200)):
        plan = StreamPlan(Problem(M=M, N=N), jnp.float32)
        plan64 = StreamPlan(Problem(M=M, N=N), jnp.float32, tm=64)
        assert (
            plan.streamed_passes_per_iter()
            <= plan64.streamed_passes_per_iter()
        )
    with pytest.raises(ValueError, match="multiple of 8"):
        StreamPlan(p_mid, jnp.float32, tm=100)


# ---------------------------------------------------------------- policy


def test_select_engine_policy():
    assert select_engine(Problem(M=40, N=40)) == "resident"
    assert select_engine(Problem(M=800, N=1200)) == "resident"
    assert select_engine(Problem(M=1600, N=2400)) == "streamed"
    # past the streamed gate the state-streaming xl kernel beats the
    # XLA loop (measured 4.28 s vs 5.16 s at the 4096² north-star)
    assert select_engine(Problem(M=4096, N=4096)) == "xl"
    # f64 always takes the XLA path (Pallas engines are f32/bf16)
    assert select_engine(Problem(M=40, N=40), jnp.float64) == "xla"


def test_build_solver_resolves_auto_and_rejects_unknown():
    solver, args, engine = build_solver(Problem(M=20, N=20), "auto")
    assert engine == "resident"
    result = solver(*args)
    assert int(result.iters) == WEIGHTED_ORACLE[(20, 20)]
    with pytest.raises(ValueError, match="unknown engine"):
        build_solver(Problem(M=20, N=20), "cuda")


def test_engine_solve_entry_point():
    result = solve(Problem(M=20, N=20), engine="auto")
    assert int(result.iters) == WEIGHTED_ORACLE[(20, 20)]
    assert bool(result.converged)


# ---------------------------------------------------------------- shared ops


def test_interior_normalized_shared_dinv():
    """The streamed engine's dinv must be the exact fused-engine value
    (they share interior_normalized — this pins the contract)."""
    problem = Problem(M=20, N=20)
    from poisson_ellipse_tpu.ops import assembly

    a64, b64, _ = assembly.assemble_numpy(problem)
    an, as_, bw, be, d, dinv = interior_normalized(problem, a64, b64)
    assert dinv.dtype == np.float64
    inner = d[1:-1, 1:-1]
    np.testing.assert_allclose(
        dinv[1:-1, 1:-1][inner != 0], 1.0 / inner[inner != 0], rtol=0
    )
    # ring is exactly zero
    assert (dinv[0] == 0).all() and (dinv[-1] == 0).all()


# ---------------------------------------------------------------- protocol


def test_chain_solver_value_exact():
    """The chained differential timing protocol must not change values."""
    problem = Problem(M=20, N=20)
    solver, args, _ = build_solver(problem, "xla", jnp.float32)
    ref = solver(*args)
    chained = _chain_solver(solver, args, 3)
    got = chained(*args)
    assert int(got.iters) == int(ref.iters)
    np.testing.assert_array_equal(np.asarray(got.w), np.asarray(ref.w))


def test_run_once_engine_auto_reports_engine():
    report = run_once(
        Problem(M=20, N=20), mode="single", engine="auto", repeat=1, batch=2
    )
    assert report.engine == "resident"
    assert report.iters == WEIGHTED_ORACLE[(20, 20)]
    assert report.converged


# ---------------------------------------------------------------- roofline


def test_roofline_passes_model():
    from poisson_ellipse_tpu.harness.roofline import passes_per_iter, roofline

    p_small = Problem(M=40, N=40)
    assert passes_per_iter(p_small, "resident") == 0.0
    assert passes_per_iter(p_small, "xla") == 13.0
    assert passes_per_iter(p_small, "fused") == 16.0
    # streamed: a fully resident plan streams nothing
    assert passes_per_iter(p_small, "streamed") == 0.0
    big = Problem(M=2400, N=3200)
    plan = StreamPlan(big, jnp.float32)
    assert passes_per_iter(big, "streamed") == pytest.approx(
        plan.streamed_passes_per_iter()
    )
    assert plan.streamed_passes_per_iter() > 0
    with pytest.raises(ValueError, match="traffic model"):
        passes_per_iter(p_small, "cuda")

    # 13 passes * 41*41*4 bytes * 10 iters in 1 ms => 0.874 GB/s
    r = roofline(p_small, "xla", iters=10, t_solver=1e-3, dtype=jnp.float32)
    assert r["hbm_gbps"] == pytest.approx(0.874, rel=1e-2)
    # CPU test runs have no known HBM peak
    assert r["hbm_peak_frac"] is None


def test_run_once_carries_roofline():
    report = run_once(Problem(M=20, N=20), mode="single", engine="xla")
    assert report.passes_per_iter == 13.0
    assert report.hbm_gbps > 0
    rec = report.json_dict()
    assert {"passes_per_iter", "hbm_gbps", "hbm_peak_frac"} <= set(rec)
    assert "Roofline:" in report.summary()


def test_fits_resident_measured_edge():
    # chip-measured envelope (resident_pcg._ARRAYS_RESIDENT comment):
    # 1100x1650 compiles and solves on the bench part; 1200x1800 does not
    assert fits_resident(Problem(M=1100, N=1650))
    assert not fits_resident(Problem(M=1200, N=1800))
    assert select_engine(Problem(M=1100, N=1650)) == "resident"
    assert select_engine(Problem(M=1200, N=1800)) == "streamed"


def test_auto_falls_back_when_selected_engine_fails(monkeypatch):
    """Capacity gates are bench-chip budgets; on a part where the chosen
    Pallas engine cannot build, auto must degrade down the chain instead
    of surfacing the compile error."""
    import poisson_ellipse_tpu.ops.resident_pcg as rp

    def boom(*a, **k):
        raise RuntimeError("Mosaic: RESOURCE_EXHAUSTED (simulated)")

    monkeypatch.setattr(rp, "build_resident_solver", boom)
    problem = Problem(M=40, N=40)
    # degradation must be loud: the failed engine is named in a warning
    with pytest.warns(RuntimeWarning, match="'resident' failed"):
        solver, args, engine = build_solver(problem, "auto")
    assert engine in ("streamed", "xla")  # resident was the selection
    result = solver(*args)
    assert int(result.iters) == WEIGHTED_ORACLE[(40, 40)]
    # explicit requests still fail loudly
    with pytest.raises(RuntimeError, match="simulated"):
        build_solver(problem, "resident")


def test_auto_raises_when_selected_engine_is_refused(monkeypatch):
    """Only memory exhaustion degrades: a kernel the compiler refuses
    for any other reason is a bug, and auto must raise it rather than
    hide it behind a slower engine."""
    import poisson_ellipse_tpu.ops.resident_pcg as rp

    def refused(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel (simulated)")

    monkeypatch.setattr(rp, "build_resident_solver", refused)
    with pytest.raises(RuntimeError, match="simulated"):
        build_solver(Problem(M=40, N=40), "auto")


@pytest.mark.parametrize("cfg", [
    dict(a1=-1.5, b1=1.5, a2=-1.0, b2=1.0, f_val=2.5),
    dict(a1=-1.2, b1=1.1, a2=-0.7, b2=0.65, delta=1e-5, norm="unweighted"),
    dict(eps=1e-3, f_val=0.5),
])
def test_engines_agree_on_general_problems(cfg):
    """The reference hardcodes its box/rhs/eps as compile-time constants;
    the framework generalises them. Every engine must track the XLA path
    on arbitrary configurations — the engines' geometry/masking logic
    cannot be specialised to the reference's exact domain."""
    problem = Problem(M=52, N=44, **cfg)
    ref = solve_xla(problem, jnp.float32)
    assert bool(ref.converged)
    for name, fn in ENGINES.items():
        got = fn(problem, jnp.float32)
        assert int(got.iters) == int(ref.iters), name
        assert bool(got.converged), name
        np.testing.assert_allclose(
            np.asarray(got.w), np.asarray(ref.w), atol=5e-6, err_msg=name
        )


@pytest.mark.parametrize("seed", range(4))
def test_engine_parity_on_random_configurations(seed):
    """Oracle invariance over RANDOM configurations (SURVEY §4): every
    engine must converge in the same iteration count as the XLA path on
    randomly drawn boxes/ε/f/grids, seed-parametrised — the fixed-config
    generality cases above can miss mask geometries the random draw
    hits (cut cells at different face fractions, extreme ε)."""
    rng = np.random.default_rng(1000 + seed)
    problem = Problem(
        M=int(rng.integers(24, 56)),
        N=int(rng.integers(24, 56)),
        a1=-float(rng.uniform(1.05, 1.6)),
        b1=float(rng.uniform(1.05, 1.6)),
        a2=-float(rng.uniform(0.55, 1.0)),
        b2=float(rng.uniform(0.55, 1.0)),
        eps=float(10.0 ** rng.uniform(-6, -1)),
        f_val=float(rng.uniform(0.2, 3.0)),
    )
    ref = solve_xla(problem, jnp.float32)
    assert bool(ref.converged)
    for name, fn in ENGINES.items():
        got = fn(problem, jnp.float32)
        assert int(got.iters) == int(ref.iters), (name, problem)
        assert bool(got.converged), (name, problem)
        np.testing.assert_allclose(
            np.asarray(got.w), np.asarray(ref.w), atol=5e-6, err_msg=name
        )


def test_sharded_agrees_on_general_problem():
    from poisson_ellipse_tpu.parallel.pcg_sharded import solve_sharded
    from poisson_ellipse_tpu.solver.pcg import solve as solve_single

    problem = Problem(M=36, N=28, a1=-1.4, b1=1.3, a2=-0.8, b2=0.75,
                      f_val=1.7)
    single = solve_single(problem, jnp.float64)
    sharded = solve_sharded(problem, dtype=jnp.float64)
    assert int(sharded.iters) == int(single.iters)
    np.testing.assert_allclose(
        np.asarray(sharded.w), np.asarray(single.w), rtol=1e-12, atol=1e-16
    )
