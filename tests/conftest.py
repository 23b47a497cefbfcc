"""Test harness configuration.

Forces the CPU backend with 8 virtual XLA devices before any backend
initialisation, so the distributed path (mesh / ppermute halos / psum
reductions) is unit-testable with no TPU — the strategy SURVEY.md §4
prescribes (the reference analogously tests small grids at 1/2/4 ranks
via mpirun on one host). Enables x64 because the reference is entirely
double precision and the iteration-count oracles are f64 facts.

The order-sensitive flag/platform ritual lives in
``parallel.mesh.virtual_cpu_devices`` — the same helper the driver's
multichip dryrun gate and the virtual-mesh benchmark use, so the test
suite exercises the production pinning path rather than a hand-rolled
copy that could drift.
"""

import os

import jax

from poisson_ellipse_tpu.parallel.mesh import virtual_cpu_devices

virtual_cpu_devices(8)
jax.config.update("jax_enable_x64", True)
# the persistent compile cache is switched on by the entry points (the
# harness CLI, chip_smoke.py, benchmark/run.py); tests stay hermetic and write
# nothing into the checkout — subprocesses inherit the variable
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
jax.config.update("jax_enable_compilation_cache", False)


# -- tier-1 per-test wall-clock budget ---------------------------------------
#
# The full suite sits near the 870 s tier-1 ceiling, so one test ballooning
# past a minute is a CI outage in the making. Any non-slow-marked test whose
# CALL phase exceeds the budget fails the session at exit with a named list —
# the fix is to shrink the test or mark it `slow` (excluded from tier-1).
# Enforcement carries a 1.25× host-noise grace: the 2-core CI box is
# load-sensitive (a test measured at 60.5 s under contention is not a
# regression of a test that runs in 45 s quiet), so 60–75 s is a printed
# warning and only > 75 s fails — a genuinely ballooned test blows far past
# the band, a noisy-neighbour blip does not. POISSON_TIER1_TEST_BUDGET_S
# overrides the nominal ceiling (0 disables both tiers).

TEST_BUDGET_S = float(os.environ.get("POISSON_TIER1_TEST_BUDGET_S", "60"))
_GRACE = 1.25

_over_budget: list[tuple[str, float]] = []
_near_budget: list[tuple[str, float]] = []


def pytest_runtest_logreport(report):
    if (
        TEST_BUDGET_S > 0
        and report.when == "call"
        and report.duration > TEST_BUDGET_S
        and "slow" not in getattr(report, "keywords", {})
    ):
        bucket = (
            _over_budget if report.duration > TEST_BUDGET_S * _GRACE
            else _near_budget
        )
        bucket.append((report.nodeid, report.duration))


def pytest_sessionfinish(session, exitstatus):
    if _near_budget:
        lines = "\n".join(
            f"  {nodeid}: {dur:.1f}s (budget {TEST_BUDGET_S:g}s)"
            for nodeid, dur in _near_budget
        )
        print(
            "\ntier-1 per-test budget WARNING (inside the host-noise "
            f"grace band, <= {TEST_BUDGET_S * _GRACE:g}s):\n{lines}"
        )
    if _over_budget:
        lines = "\n".join(
            f"  {nodeid}: {dur:.1f}s > {TEST_BUDGET_S * _GRACE:g}s"
            for nodeid, dur in _over_budget
        )
        session.exitstatus = 1
        print(
            "\ntier-1 per-test budget exceeded (mark these `slow` or "
            f"shrink them):\n{lines}"
        )
