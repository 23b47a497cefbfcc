"""``correct`` comes out false when the timed path is broken underneath,
and for the control: the reference kept in bfloat16 in the program's
place. Each cell's kind of fault, at test size on the CPU."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, reference
from conftest import edit_json, run


def test_sound_runs_are_correct(small_bench):
    for cell in ("solve-4096", "direct-400x600", "serve-400x600",
                 "solve-4096-2x2", "serve-400x600-4chip"):
        out = run(small_bench, cell)
        assert out["correct"] is True, (cell, out["checks"])


# -- the control ------------------------------------------------------------

@pytest.mark.parametrize("config", ["ellipse-4096", "ellipse-4096-2x2",
                                    "ellipse-400x600", "ellipse-400x600-2x2"])
def test_control_fails(small_bench, config):
    cfg = small_bench.config(config)
    device = jax.devices()[0]
    answers = []
    for eps in cfg["eps_choices"][:3]:
        spec = reference.problem_spec(cfg, eps)
        w, k, conv = reference.solve(spec, "bfloat16", max_iter=4 * 200,
                                     device=device)
        answers.append({"eps": eps, "w": w, "iters": k, "converged": conv})
    checks = compare.check(cfg, answers, device, log=open("/dev/null", "w"))
    assert not compare.passed(checks), checks


# -- faults in the one-chip solver (solve-4096, direct-400x600) -------------

def broken_build_solver(alter):
    from poisson_ellipse_tpu.solver import engine

    real = engine.build_solver

    def build(*a, **kw):
        solver, args, name = real(*a, **kw)
        return (lambda *xs: alter(solver(*xs), xs)), args, name
    return build


def unchanged(result, args):
    """The solve returns its starting state: w = 0, nothing converged."""
    return result._replace(w=jnp.zeros_like(result.w), iters=result.iters * 0,
                           converged=jnp.asarray(False))


def altered(result, args):
    """The answer altered where it is produced."""
    return result._replace(w=result.w * 1.01)


@pytest.mark.parametrize("cell", ["solve-4096", "direct-400x600"])
@pytest.mark.parametrize("fault", [unchanged, altered])
def test_one_chip_faults(small_bench, monkeypatch, cell, fault):
    from poisson_ellipse_tpu.solver import engine

    monkeypatch.setattr(engine, "build_solver", broken_build_solver(fault))
    out = run(small_bench, cell)
    assert out["correct"] is False, out["checks"]


def test_repeat_solve_checks_every_solve_beyond_its_sample(small_bench,
                                                          monkeypatch):
    """The answers compared are a sample of the window's solves; a solve
    that comes back unconverged makes ``correct`` false wherever it falls."""
    from poisson_ellipse_tpu.solver import engine

    calls = []

    def late_failure(result, args):
        calls.append(1)
        # the warm-up solve is the first call; the tenth is far past the
        # sample's first slots
        if len(calls) == 10:
            return result._replace(converged=jnp.asarray(False))
        return result
    monkeypatch.setattr(engine, "build_solver",
                        broken_build_solver(late_failure))
    out = run(small_bench, "solve-4096", seconds=2.0)
    assert out["attempted"] >= 10, out["attempted"]
    assert out["failed"] == 1
    assert out["correct"] is False, out["checks"]


# -- the 2x2 cell: the exchange between chips left out ----------------------

def test_sharded_without_exchange(small_bench, monkeypatch):
    from poisson_ellipse_tpu.parallel import halo

    monkeypatch.setattr(halo, "_shift_lo_to_hi",
                        lambda edge, axis, n: jnp.zeros_like(edge))
    monkeypatch.setattr(halo, "_shift_hi_to_lo",
                        lambda edge, axis, n: jnp.zeros_like(edge))
    out = run(small_bench, "solve-4096-2x2")
    assert out["correct"] is False, out["checks"]


def test_sharded_answer_altered(small_bench, monkeypatch):
    from poisson_ellipse_tpu.parallel import pcg_sharded

    real = pcg_sharded.build_sharded_solver

    def build(*a, **kw):
        solver, args = real(*a, **kw)
        return (lambda *xs: altered(solver(*xs), xs)), args
    monkeypatch.setattr(pcg_sharded, "build_sharded_solver", build)
    out = run(small_bench, "solve-4096-2x2")
    assert out["correct"] is False, out["checks"]


# -- the served cells -------------------------------------------------------
# serve-400x600 advances its lanes through ``scheduler._bucket_advance``;
# serve-400x600-4chip through ``batched_sharded.build_sharded_chunk_advance``
# (16 lanes, 4 on each of the 2x2 mesh's chips)

def broken_advance(real, keep_lanes):
    """The bucket's chunk advance that ``real`` builds, with the lanes
    ``keep_lanes`` picks left as they were."""

    def advance(*a, **kw):
        fn, proto = real(*a, **kw)

        def step(a3, b3, mask, h1, h2, delta, state, limit):
            new = fn(a3, b3, mask, h1, h2, delta, state, limit)
            lanes = state[1].shape[0]
            old = np.asarray(keep_lanes(lanes))
            return tuple(
                n if i == 0 else jnp.where(
                    old.reshape((lanes,) + (1,) * (n.ndim - 1)), o, n)
                for i, (n, o) in enumerate(zip(new, state)))
        return step, proto
    return advance


def patch_advance(monkeypatch, cell, keep_lanes):
    from poisson_ellipse_tpu.parallel import batched_sharded
    from poisson_ellipse_tpu.serve import scheduler

    module, name = ((batched_sharded, "build_sharded_chunk_advance")
                    if cell == "serve-400x600-4chip"
                    else (scheduler, "_bucket_advance"))
    monkeypatch.setattr(module, name,
                        broken_advance(getattr(module, name), keep_lanes))


SERVED_CELLS = {"serve-400x600": "served-poisson",
                "serve-400x600-4chip": "served-poisson-2x2"}


@pytest.mark.parametrize("cell", sorted(SERVED_CELLS))
@pytest.mark.parametrize("keep_lanes", [
    lambda lanes: np.ones(lanes, bool),                  # state unchanged
    lambda lanes: np.arange(lanes) >= lanes // 2,        # half the batch
], ids=["unchanged", "half-batch"])
def test_served_step_faults(small_bench, monkeypatch, keep_lanes, cell):
    # load enough that every lane takes requests
    edit_json(os.path.join(small_bench.dir, "traffic",
                           SERVED_CELLS[cell] + ".json"), rate_per_s=400)
    patch_advance(monkeypatch, cell, keep_lanes)
    out = run(small_bench, cell)
    assert out["correct"] is False, out["checks"]


def test_four_chip_served_one_chips_lanes_unchanged(small_bench, monkeypatch):
    """The lanes of the mesh's first chip (lanes 0-3 of 16) return their
    state unchanged; the other chips' lanes answer."""
    cell = "serve-400x600-4chip"
    edit_json(os.path.join(small_bench.dir, "traffic",
                           SERVED_CELLS[cell] + ".json"), rate_per_s=400)
    patch_advance(monkeypatch, cell, lambda lanes: np.arange(lanes) < 4)
    out = run(small_bench, cell)
    assert out["correct"] is False, out["checks"]


def built_schedulers(monkeypatch):
    """Every ``Scheduler`` built from here on, with its arguments."""
    from poisson_ellipse_tpu.serve import scheduler

    built = []
    real = scheduler.Scheduler.__init__

    def init(self, *a, **kw):
        built.append((self, a, kw))
        real(self, *a, **kw)
    monkeypatch.setattr(scheduler.Scheduler, "__init__", init)
    return built


def test_four_chip_served_run_spans_the_four_devices(small_bench,
                                                     monkeypatch):
    built = built_schedulers(monkeypatch)
    out = run(small_bench, "serve-400x600-4chip")
    assert out["correct"] is True, out["checks"]
    ((sched, _, _),) = built
    assert sched.lanes == 16
    assert sched.mesh.devices.shape == (2, 2)
    assert set(sched.mesh.devices.flat) == set(jax.devices()[:4])
    (ctx,) = sched._ctxs.values()
    for lanes in ctx.state[1:]:
        assert lanes.shape[0] == 16
        assert lanes.sharding.device_set == set(jax.devices()[:4])


def test_one_chip_served_run_builds_the_default_scheduler(small_bench,
                                                         monkeypatch):
    built = built_schedulers(monkeypatch)
    out = run(small_bench, "serve-400x600")
    assert out["correct"] is True, out["checks"]
    ((sched, args, kwargs),) = built
    assert (args, kwargs) == ((), {})
    assert sched.mesh is None and sched.lanes == 4


@pytest.mark.parametrize("cell", sorted(SERVED_CELLS))
def test_served_answer_altered(small_bench, monkeypatch, cell):
    from poisson_ellipse_tpu.serve import scheduler

    real = scheduler.Scheduler.collect

    def collect(self):
        out = real(self)
        for res in out.values():
            if res.w is not None:
                res.w = res.w * 1.01
        return out
    monkeypatch.setattr(scheduler.Scheduler, "collect", collect)
    out = run(small_bench, cell)
    assert out["correct"] is False, out["checks"]
