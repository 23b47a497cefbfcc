"""Batched multi-solve engines (`batch/`) + the serving cache layer.

The contracts this file pins (ISSUE 5):

- lane 0 of a batched solve is BIT-identical to the single-engine solve
  (lane batching is free of cross-lane arithmetic, not approximately so);
- mixed-ε lanes each converge at their own single-solve oracle count;
- a NaN-poisoned lane is quarantined — masked out with a
  ``recovery:lane-quarantine`` trace event — while the healthy lanes
  match their oracle exactly;
- the lane-sharded composition issues EXACTLY one psum per while-body
  (jaxpr-pinned), independent of recurrence;
- a re-request for a bucketed shape is a warm-pool cache HIT returning
  the same executable object (no recompile);
- the batched Pallas kernels (lane dim on the kernel grid) are bitwise
  twins of the single-lane kernels, per lane.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poisson_ellipse_tpu.batch import (
    batched_operands,
    pcg_batched,
    pcg_batched_pipelined,
    solve_batched,
)
from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.pipelined_pcg import pcg_pipelined
from poisson_ellipse_tpu.solver.engine import build_solver
from poisson_ellipse_tpu.solver.pcg import pcg


@pytest.fixture(scope="module")
def problem():
    return Problem(M=40, N=40)


@pytest.fixture(scope="module")
def single(problem):
    a, b, rhs = assembly.assemble(problem, jnp.float32)
    return jax.jit(lambda a, b, r: pcg(problem, a, b, r))(a, b, rhs)


# -- lane-0 bit parity -------------------------------------------------------


def test_lane0_bit_identical_to_single_solve(problem, single):
    solver, args, engine = build_solver(problem, "batched", jnp.float32,
                                        lanes=3)
    res = solver(*args)
    assert engine == "batched"
    assert bool(jnp.all(res.converged)) and not bool(jnp.any(res.quarantined))
    assert int(res.iters[0]) == int(single.iters) == 50
    assert float(res.diff[0]) == float(single.diff)
    assert bool(jnp.all(res.w[0] == single.w)), "lane 0 must be bitwise"
    # identical lanes take identical trajectories: all lanes bitwise
    assert bool(jnp.all(res.w[1] == res.w[0]))


def test_lane0_bit_identical_pipelined(problem):
    a, b, rhs = assembly.assemble(problem, jnp.float32)
    sp = jax.jit(lambda a, b, r: pcg_pipelined(problem, a, b, r))(a, b, rhs)
    solver, args, _ = build_solver(problem, "batched-pipelined",
                                  jnp.float32, lanes=3)
    res = solver(*args)
    assert bool(jnp.all(res.converged))
    assert int(res.iters[0]) == int(sp.iters)
    assert bool(jnp.all(res.w[0] == sp.w)), "pipelined lane 0 must be bitwise"


def test_distinct_rhs_lanes_solve_their_own_problems(problem, single):
    a, b, rhs = assembly.assemble(problem, jnp.float32)
    # lane 1 solves the doubled-RHS problem: by linearity its solution is
    # 2x lane 0's (up to round-off) and its iteration count the same
    rb = jnp.stack([rhs, rhs * 2.0])
    res = jax.jit(lambda a, b, r: pcg_batched(problem, a, b, r))(a, b, rb)
    assert bool(jnp.all(res.converged))
    assert bool(jnp.all(res.w[0] == single.w))
    # lane 1's 2x-scaled step norms cross δ a step later, so its tail
    # iterations differ — value-equivalence, not bitwise scaling
    np.testing.assert_allclose(
        np.asarray(res.w[1]), 2.0 * np.asarray(res.w[0]), rtol=1e-3,
        atol=1e-7,
    )


def test_refilled_lane_bit_identical_to_single_solve():
    """The lane-refill correctness pin (ISSUE 7): a lane swapped in
    MID-batch — nonzero global k, another lane still iterating — must
    produce the bit-identical solution of the same request solved
    single-lane. Per-lane arithmetic is lane-decoupled and k-independent,
    so swap-in is bitwise-free exactly like lane packing at k=0."""
    from poisson_ellipse_tpu.serve import Scheduler
    from poisson_ellipse_tpu.solver.pcg import solve as pcg_solve

    # 12x12 is bucket-exact (bucket_dim(12) == 12): no padding, so the
    # embedded problem IS the problem and bitwise comparison is fair
    p = Problem(M=12, N=12)
    single = pcg_solve(p, jnp.float32)
    sched = Scheduler(lanes=2, chunk=4)
    # lane 0 hosts a longer request; lane 1's first tenant retires early
    sched.submit(Problem(M=12, N=12, delta=1e-7), request_id="long")
    sched.submit(Problem(M=12, N=12, delta=5e-6), request_id="short")
    for _ in range(100):
        sched.step()
        if "short" in sched.results:
            break
    assert "short" in sched.results and "long" not in sched.results, (
        "need a retirement while the other lane is still in flight"
    )
    sched.submit(p, request_id="swapped")
    # dispatch at the next boundary, and read the swap-in offset BEFORE
    # any chunk advance: retirements rebase the batch clock, so base_k
    # is only meaningful at the moment of the swap-in itself
    sched._fill_lanes()
    located = sched._slot_of("swapped")
    assert located is not None and located[1].base_k > 0, (
        "the swap-in must happen mid-batch"
    )
    results = sched.drain()
    res = results["swapped"]
    assert res.outcome == "completed"
    assert res.iters == int(single.iters)
    assert float(res.diff) == float(single.diff)
    assert bool(np.all(res.w == np.asarray(single.w))), (
        "a refilled lane's solution must be bitwise identical to the "
        "single-lane solve"
    )


# -- mixed-ε lanes -----------------------------------------------------------


def test_mixed_eps_lanes_each_hit_their_oracle():
    base = Problem(M=32, N=32)
    eps_values = (base.eps_value, 1e-2, 1e-4)
    oracles = []
    for eps in eps_values:
        p = Problem(M=32, N=32, eps=eps)
        a, b, rhs = assembly.assemble(p, jnp.float32)
        r = jax.jit(lambda a, b, r: pcg(p, a, b, r))(a, b, rhs)
        assert bool(r.converged)
        oracles.append(int(r.iters))
    a, b, rhs = batched_operands(base, 3, jnp.float32,
                                 eps_values=eps_values)
    assert a.ndim == 3  # per-lane coefficients
    res = jax.jit(lambda a, b, r: pcg_batched(base, a, b, r))(a, b, rhs)
    assert bool(jnp.all(res.converged))
    for lane, oracle in enumerate(oracles):
        assert abs(int(res.iters[lane]) - oracle) <= 2, (
            f"lane {lane}: {int(res.iters[lane])} vs oracle {oracle}"
        )


# -- NaN-lane quarantine -----------------------------------------------------


def test_nan_lane_quarantined_healthy_lanes_match_oracle(problem, single):
    from poisson_ellipse_tpu.resilience.faultinject import (
        FaultPlan,
        inject_nan,
    )

    guarded = solve_batched(
        problem, 3, "batched", jnp.float32, chunk=16,
        faults=FaultPlan(inject_nan(10, "r", lane=1)),
    )
    res = guarded.result
    assert list(np.asarray(res.quarantined)) == [False, True, False]
    assert list(np.asarray(res.converged)) == [True, False, True]
    # the poisoned lane was masked out at the iteration after injection
    assert int(res.iters[1]) == 11
    # healthy lanes are untouched: oracle-exact, finite, mutually bitwise
    for lane in (0, 2):
        assert int(res.iters[lane]) == int(single.iters)
        assert np.isfinite(np.asarray(res.w[lane])).all()
    assert bool(jnp.all(res.w[0] == res.w[2]))
    kinds = [e.kind for e in guarded.recoveries]
    assert kinds == ["lane-quarantine"]
    assert guarded.recoveries[0].detail == "lane 1"


def test_quarantine_event_reaches_the_trace(problem, tmp_path):
    from poisson_ellipse_tpu.obs import trace as obs_trace
    from poisson_ellipse_tpu.resilience.faultinject import (
        FaultPlan,
        inject_nan,
    )

    path = tmp_path / "quarantine.jsonl"
    obs_trace.start(str(path))
    try:
        solve_batched(
            problem, 2, "batched", jnp.float32, chunk=16,
            faults=FaultPlan(inject_nan(8, "r", lane=0)),
        )
    finally:
        obs_trace.stop()
    assert obs_trace.validate_file(str(path)) == []
    names = {r["name"] for r in obs_trace.read_jsonl(str(path))}
    assert "recovery:lane-quarantine" in names


def test_chunked_driver_matches_fused_iteration_counts(problem):
    fused_solver, args, _ = build_solver(problem, "batched", jnp.float32,
                                         lanes=2)
    fused = fused_solver(*args)
    chunked = solve_batched(problem, 2, "batched", jnp.float32, chunk=16)
    assert chunked.recoveries == ()
    assert list(np.asarray(chunked.result.iters)) == list(
        np.asarray(fused.iters)
    )
    np.testing.assert_allclose(
        np.asarray(chunked.result.w), np.asarray(fused.w), rtol=0,
        atol=5e-6,
    )


def test_driver_rejects_unaddressed_or_out_of_range_faults(problem):
    from poisson_ellipse_tpu.resilience.faultinject import (
        FaultPlan,
        inject_nan,
    )

    with pytest.raises(ValueError, match="lane-addressed"):
        solve_batched(problem, 2, "batched", jnp.float32,
                      faults=FaultPlan(inject_nan(10, "r")))
    with pytest.raises(ValueError, match="outside"):
        solve_batched(problem, 2, "batched", jnp.float32,
                      faults=FaultPlan(inject_nan(10, "r", lane=5)))


def test_lane_fault_on_scalar_field_quarantines(problem):
    # zr is a (B,) per-lane scalar: lane addressing must work there too
    from poisson_ellipse_tpu.resilience.faultinject import (
        Fault,
        FaultPlan,
    )

    guarded = solve_batched(
        problem, 2, "batched", jnp.float32, chunk=16,
        faults=FaultPlan(Fault("nan", at_iter=10, field="zr", lane=0)),
    )
    assert bool(guarded.result.quarantined[0])
    assert bool(guarded.result.converged[1])


def test_pipelined_lane_fault_also_quarantined(problem):
    from poisson_ellipse_tpu.resilience.faultinject import (
        FaultPlan,
        inject_nan,
    )

    guarded = solve_batched(
        problem, 2, "batched-pipelined", jnp.float32, chunk=16,
        faults=FaultPlan(inject_nan(10, "r", lane=1)),
    )
    res = guarded.result
    assert bool(res.quarantined[1]) and not bool(res.quarantined[0])
    assert bool(res.converged[0])
    assert [e.kind for e in guarded.recoveries] == ["lane-quarantine"]


# -- lane-sharded mesh: the 1-psum pin ---------------------------------------


@pytest.mark.parametrize("pipelined", [True, False])
def test_lane_sharded_exactly_one_psum_per_while_body(pipelined):
    from poisson_ellipse_tpu.analysis.contracts import assert_contract

    # exactly ONE collective — the convergence word; the dot bundles are
    # lane-local (whole lanes per device), so the count is flat in B:
    # the declared batched-cadence contract, from the ENGINE_CAPS row
    engine = "batched-pipelined" if pipelined else "batched"
    r = assert_contract(
        "batched-cadence", engine, problem=Problem(M=40, N=40),
        mesh_shape=(1, 2), lanes=4,
    )
    assert r.expected == {"psum": 1, "ppermute": 0}


def test_lane_sharded_solves_match_single(problem, single):
    from poisson_ellipse_tpu.parallel.batched_sharded import (
        build_batched_sharded_solver,
    )
    from poisson_ellipse_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices()[:2])
    solver, args = build_batched_sharded_solver(
        problem, mesh, lanes=4, dtype=jnp.float32
    )
    res = solver(*args)
    assert bool(jnp.all(res.converged))
    assert all(int(i) == int(single.iters) for i in res.iters)
    np.testing.assert_allclose(
        np.asarray(res.w[0]), np.asarray(single.w), rtol=0, atol=5e-6
    )


def test_lane_sharded_requires_whole_lanes_per_device():
    from poisson_ellipse_tpu.parallel.batched_sharded import (
        build_batched_sharded_solver,
    )
    from poisson_ellipse_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices()[:2])
    with pytest.raises(ValueError, match="multiple of the mesh"):
        build_batched_sharded_solver(Problem(M=10, N=10), mesh, lanes=3)


# -- warm pool / bucketed AOT cache ------------------------------------------


def test_bucketed_cache_rerequest_is_a_hit_same_executable():
    from poisson_ellipse_tpu.runtime.compile_cache import WarmPool

    pool = WarmPool()
    first = pool.warmup("batched", (10, 10), jnp.float32, lanes=3)
    assert (pool.hits, pool.misses) == (0, 1)
    # a DIFFERENT request shape in the same bucket: hit, same executable
    second = pool.warmup("batched", (11, 12), jnp.float32, lanes=4)
    assert second.compiled is first.compiled
    assert (pool.hits, pool.misses) == (1, 1)
    # a different lane bucket is a different executable
    third = pool.warmup("batched", (10, 10), jnp.float32, lanes=5)
    assert third.compiled is not first.compiled
    assert pool.misses == 2


def test_bucketed_solve_serves_embedded_request():
    from poisson_ellipse_tpu.runtime.compile_cache import WarmPool
    from poisson_ellipse_tpu.solver.pcg import solve as single_solve

    p = Problem(M=10, N=10)
    clean = single_solve(p, jnp.float32)
    pool = WarmPool()
    res = pool.solve(p, 3, "batched", jnp.float32)
    assert res.w.shape == (3, 11, 11)
    assert bool(jnp.all(res.converged))
    # pad-and-mask embedding is value-equivalent (reduction-order ulps),
    # iteration counts within a step of the exact-shape solve
    assert all(abs(int(i) - int(clean.iters)) <= 2 for i in res.iters)
    np.testing.assert_allclose(
        np.asarray(res.w[0]), np.asarray(clean.w), rtol=0, atol=1e-5
    )
    # serving the request warmed the bucket: a second solve in the same
    # lane bucket (4 lanes -> bucket 4, same as 3) is a pure hit
    pool.solve(p, 4, "batched", jnp.float32)
    assert pool.hits >= 1


def test_cache_events_and_counters_emitted(tmp_path):
    from poisson_ellipse_tpu.obs import trace as obs_trace
    from poisson_ellipse_tpu.runtime.compile_cache import WarmPool

    path = tmp_path / "cache.jsonl"
    pool = WarmPool()
    obs_trace.start(str(path))
    try:
        pool.warmup("batched", (10, 10), jnp.float32, lanes=1)
        pool.warmup("batched", (10, 10), jnp.float32, lanes=1)
    finally:
        obs_trace.stop()
    names = [r["name"] for r in obs_trace.read_jsonl(str(path))]
    assert "cache:miss" in names and "cache:hit" in names


def test_bucket_ladder_shapes():
    from poisson_ellipse_tpu.runtime.compile_cache import (
        bucket_dim,
        grid_bucket,
        lane_bucket,
    )

    assert bucket_dim(8) == 8
    assert bucket_dim(9) == 12
    assert bucket_dim(400) == 512
    assert grid_bucket(400, 600) == (512, 768)
    assert lane_bucket(1) == 1
    assert lane_bucket(3) == 4
    assert lane_bucket(32) == 32


# -- batched Pallas kernels (lane dim on the kernel grid) --------------------


def test_batched_pallas_stencil_bitwise_per_lane(problem):
    from poisson_ellipse_tpu.ops.pallas_kernels import (
        apply_a_batched_pallas,
        apply_a_pallas,
    )

    a, b, rhs = assembly.assemble(problem, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(0), (3,) + rhs.shape,
                          jnp.float32)
    w = w.at[:, 0].set(0).at[:, -1].set(0)
    w = w.at[:, :, 0].set(0).at[:, :, -1].set(0)
    single = jnp.stack([
        apply_a_pallas(w[i], a, b, problem.h1, problem.h2, interpret=True)
        for i in range(3)
    ])
    out = apply_a_batched_pallas(w, a, b, problem.h1, problem.h2,
                                 interpret=True)
    assert bool(jnp.all(out == single))


def test_batched_pallas_fused_dots_match_lane_dots(problem):
    from poisson_ellipse_tpu.batch.batched_pcg import lane_dots
    from poisson_ellipse_tpu.ops.pallas_kernels import (
        apply_a_dots_batched_pallas,
    )

    a, b, rhs = assembly.assemble(problem, jnp.float32)
    key = jax.random.PRNGKey(1)
    w = jax.random.normal(key, (2,) + rhs.shape, jnp.float32)
    w = w.at[:, 0].set(0).at[:, -1].set(0)
    w = w.at[:, :, 0].set(0).at[:, :, -1].set(0)
    pairs = ((w, w), (w, -w))
    out, sums = apply_a_dots_batched_pallas(
        w, a, b, problem.h1, problem.h2, pairs, interpret=True
    )
    ref = lane_dots(*pairs)
    np.testing.assert_allclose(
        np.asarray(sums), np.asarray(ref), rtol=1e-5
    )
    assert out.shape == (2,) + rhs.shape


def test_batched_engines_accept_pallas_stencil(problem):
    a, b, rhs = batched_operands(problem, 2, jnp.float32)
    for fn in (pcg_batched, pcg_batched_pipelined):
        res = jax.jit(
            lambda a, b, r, fn=fn: fn(problem, a, b, r, stencil="pallas",
                                      interpret=True)
        )(a, b, rhs)
        assert bool(jnp.all(res.converged))
        assert all(abs(int(i) - 50) <= 2 for i in res.iters)


# -- harness / registry plumbing ---------------------------------------------


def test_cli_lanes_auto_resolves_to_batched(capsys):
    import json

    from poisson_ellipse_tpu.harness.__main__ import main

    rc = main(["10", "10", "--lanes", "2", "--json"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["engine"] == "batched"
    assert rec["lanes"] == 2
    assert rec["solves_per_sec"] > 0
    assert rec["quarantined"] == 0


def test_cli_warmup_subcommand(capsys):
    import json

    from poisson_ellipse_tpu.harness.__main__ import main

    rc = main([
        "warmup", "--grids", "10x10", "--lanes", "1", "--engine",
        "batched", "--json",
    ])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["warmed"][0]["bucket"] == [12, 12]


def test_lanes_reject_non_batched_engines(problem):
    from poisson_ellipse_tpu.harness.run import run_once

    with pytest.raises(ValueError, match="one solve per dispatch"):
        run_once(problem, mode="single", engine="xla", lanes=4)
    with pytest.raises(ValueError, match="one solve per dispatch"):
        build_solver(problem, "pipelined", jnp.float32, lanes=2)
    with pytest.raises(ValueError, match="native"):
        run_once(problem, mode="native", lanes=2)
    with pytest.raises(ValueError, match="checkpoint"):
        run_once(problem, lanes=2, checkpoint_dir="/tmp/nope")


def test_lanes_with_chained_timing_protocol(problem):
    # --lanes (real batching) composes with --batch (the chained timing
    # protocol): the marginal-cost measurement runs over the batched
    # solver without perturbing its per-lane results
    from poisson_ellipse_tpu.harness.run import run_once

    report = run_once(
        problem, mode="single", engine="batched", lanes=2, repeat=1,
        batch=2,
    )
    assert report.converged and report.iters == 50
    assert report.lanes == 2 and report.solves_per_sec > 0


def test_guarded_lanes_run(problem):
    from poisson_ellipse_tpu.harness.run import run_once

    report = run_once(problem, mode="single", engine="batched", lanes=2,
                      guard=True)
    assert report.converged
    assert report.recoveries == []
    assert report.lanes == 2


def test_guard_ladder_rejects_batched_with_pointer(problem):
    from poisson_ellipse_tpu.resilience.guard import guarded_solve

    with pytest.raises(ValueError, match="lane "):
        guarded_solve(problem, "batched", jnp.float32)


def test_sharded_mode_lanes_through_run_once(problem):
    from poisson_ellipse_tpu.harness.run import run_once

    report = run_once(
        problem, mode="sharded", mesh_shape=(1, 2), engine="batched",
        lanes=4,
    )
    assert report.converged and report.iters == 50
    assert report.lanes == 4 and report.solves_per_sec > 0
