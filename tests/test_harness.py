"""Harness layer: run_once reports, CLI contract, phase profiler.

The reference's manual oracle is its printed rank-0 summary (iteration
count + time, ``stage2-mpi/poisson_mpi_decomp.cpp:493-498``); these tests
pin the same facts programmatically: oracle iteration counts, convergence,
L2 error magnitude, and that the CLI accepts the reference's argv shape
(``argv[1]=M argv[2]=N``, ``poisson_mpi_cuda2.cu:995-999``).
"""

import json
import os
import re

import jax.numpy as jnp
import pytest

from poisson_ellipse_tpu.harness import run_once
from poisson_ellipse_tpu.harness.__main__ import main as cli_main
from poisson_ellipse_tpu.harness.profile import (
    format_phases,
    profile_single,
)
from poisson_ellipse_tpu.models.problem import Problem


def test_run_once_single_matches_oracle():
    report = run_once(Problem(M=40, N=40), mode="single", dtype="f64")
    assert report.iters == 50  # weighted-norm oracle @ 40x40
    assert report.converged and not report.breakdown
    assert report.l2_error == pytest.approx(3.68e-3, rel=0.05)
    assert report.t_solver > 0 and report.t_init > 0
    assert "Converged after 50 iterations" in report.summary()


def test_run_once_sharded_matches_single():
    single = run_once(Problem(M=40, N=40), mode="single", dtype="f64")
    sharded = run_once(Problem(M=40, N=40), mode="sharded", dtype="f64")
    assert sharded.mesh_shape == (2, 4)  # 8 virtual devices, near-square
    assert sharded.iters == single.iters
    assert sharded.l2_error == pytest.approx(single.l2_error, rel=1e-6)


def test_run_once_sharded_fused_engine():
    """mode=sharded engine=fused drives the two-kernel per-shard path
    end-to-end through the harness (oracle + report plumbing)."""
    report = run_once(
        Problem(M=40, N=40), mode="sharded", dtype="f32", engine="fused"
    )
    assert report.engine == "fused"
    assert report.iters == 50 and report.converged


def test_run_once_explicit_mesh_shape():
    report = run_once(
        Problem(M=20, N=20), mode="sharded", mesh_shape=(2, 2), dtype="f64"
    )
    assert report.mesh_shape == (2, 2)
    assert report.converged


def test_cli_positional_grid_and_json(capsys):
    rc = cli_main(["40", "40", "--mode", "single", "--dtype", "f64", "--json"])
    assert rc == 0
    line = capsys.readouterr().out.strip()
    rec = json.loads(line)
    assert rec["M"] == 40 and rec["N"] == 40
    assert rec["iters"] == 50 and rec["converged"] is True


def test_cli_grid_sweep_and_eps_sweep(capsys):
    rc = cli_main(
        [
            "--grids",
            "10x10,20x20",
            "--mode",
            "single",
            "--dtype",
            "f64",
            "--json",
        ]
    )
    assert rc == 0
    recs = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [r["iters"] for r in recs] == [15, 26]  # weighted oracles

    rc = cli_main(
        [
            "20",
            "20",
            "--mode",
            "single",
            "--dtype",
            "f64",
            "--eps-sweep",
            "1e-2,1e-4",
            "--json",
        ]
    )
    assert rc == 0
    recs = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [r["eps"] for r in recs] == [1e-2, 1e-4]
    # stiffer fictitious domain (smaller eps) must not take fewer iters
    assert recs[1]["iters"] >= recs[0]["iters"]


def test_cli_unconverged_exit_code():
    rc = cli_main(
        ["40", "40", "--mode", "single", "--dtype", "f64", "--max-iter", "3"]
    )
    assert rc == 1


def test_readme_python_surfaces_importable():
    """Every import the README's Python examples advertise must exist —
    the public API surface the docs promise is pinned here so it cannot
    silently drift from the documentation."""
    from poisson_ellipse_tpu import Problem as _P, solve as _s  # noqa: F401
    from poisson_ellipse_tpu.parallel import solve_sharded  # noqa: F401
    from poisson_ellipse_tpu.parallel.multihost import (  # noqa: F401
        global_mesh,
        initialize_multihost,
        process_info,
        shutdown_multihost,
    )
    from poisson_ellipse_tpu.runtime import solve_native  # noqa: F401
    from poisson_ellipse_tpu.solver import solve_with_checkpoints  # noqa: F401


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FENCE = re.compile(r"```[^\n]*\n(.*?)```", re.DOTALL)
_INLINE = re.compile(r"`([^`\n]+)`")
_SCRIPT = re.compile(r"\bpython3?\s+(?!-)(\S+\.py)\b")
_MODULE = re.compile(r"\bpython3?\s+-m\s+([A-Za-z_][\w.]*)")


@pytest.mark.parametrize("doc", ["README.md", ".claude/skills/verify/SKILL.md"])
def test_docs_name_only_runnable_commands(doc):
    """Every ``python <path>.py`` / ``python3 <path>.py`` and
    ``python -m <module>`` command in the document's code blocks and
    inline code names a file or a runnable module that exists: scripts
    by a path relative to the repo root, repo packages by their
    ``__main__.py`` or module file, anything else (pytest) by the
    installed interpreter."""
    import importlib.util

    with open(os.path.join(_REPO, doc)) as fh:
        prose = fh.read()
    blocks = _FENCE.findall(prose)
    assert blocks, f"{doc} has no code blocks"
    text = "\n".join(blocks + _INLINE.findall(_FENCE.sub("", prose)))
    scripts = _SCRIPT.findall(text)
    modules = _MODULE.findall(text)
    assert scripts or modules, f"{doc} names no python command"
    missing = [
        p for p in scripts
        if os.path.isabs(p) or not os.path.isfile(os.path.join(_REPO, p))
    ]
    for mod in modules:
        path = os.path.join(_REPO, *mod.split("."))
        if os.path.isdir(os.path.join(_REPO, mod.split(".")[0])):
            if not (os.path.isfile(path + ".py")
                    or os.path.isfile(os.path.join(path, "__main__.py"))):
                missing.append(f"-m {mod}")
        elif importlib.util.find_spec(mod) is None:
            missing.append(f"-m {mod}")
    assert not missing, f"{doc} names commands that do not exist: {missing}"


def test_phase_timer_decomposition_sums_to_total():
    """SURVEY §4's benchmark smoke: the named phase accumulators must
    decompose the wall clock — their sum matches an outer total timer
    (the stage4 init/solver/finalize split's defining invariant), and
    re-entering a phase accumulates rather than overwrites."""
    import time as _time

    from poisson_ellipse_tpu.utils.timing import PhaseTimer

    t = PhaseTimer()
    t0 = _time.perf_counter()
    with t.phase("init"):
        _time.sleep(0.02)
    with t.phase("solver"):
        _time.sleep(0.03)
    with t.phase("solver"):
        _time.sleep(0.01)
    total = _time.perf_counter() - t0
    assert set(t.totals) == {"init", "solver"}
    assert t.totals["solver"] > t.totals["init"]
    phase_sum = sum(t.totals.values())
    # phases cover everything but the negligible inter-phase gaps
    assert 0.9 * phase_sum <= total <= phase_sum + 0.05
    assert "T_solver" in t.report()


def test_profile_single_phases():
    phases = profile_single(Problem(M=32, N=32), jnp.float64, reps=5)
    assert set(phases) == {"stencil", "dot", "precond", "update", "halo"}
    assert phases["halo"] == 0.0
    assert all(v >= 0.0 for v in phases.values())
    text = format_phases(phases, iters=10)
    assert "t_stencil" in text and "x10 iters" in text


def test_profile_sharded_phases():
    """The sharded table covers every stage4 accumulator analog —
    including the update/axpy phase (``update_w_r_kernel``), which used
    to be single-device-only (``poisson_mpi_cuda2.cu:696-700``)."""
    from poisson_ellipse_tpu.harness.profile import profile_sharded

    phases = profile_sharded(Problem(M=32, N=32), reps=5)
    assert set(phases) == {
        "halo", "stencil", "stencil_pure", "precond", "dot", "update",
    }
    assert all(v >= 0.0 for v in phases.values())


def test_cli_native_backend(capsys):
    from poisson_ellipse_tpu.runtime import native_available

    if not native_available():
        pytest.skip("C++ runtime unavailable")
    rc = cli_main(["40", "40", "--mode", "native", "--threads", "1", "--json"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["iters"] == 50 and rec["dtype"] == "f64"


def test_cli_checkpointed_sharded_run(tmp_path, capsys):
    ck = str(tmp_path / "ck")
    argv = [
        "40", "40", "--mode", "sharded", "--dtype", "f64",
        "--checkpoint-dir", ck, "--chunk", "12", "--json",
    ]
    rc = cli_main(argv)
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["iters"] == 50 and rec["converged"] is True
    assert rec["mesh"] == [2, 4]
    # a second invocation resumes from the finished checkpoint: the carry
    # is already converged, so it completes without re-iterating
    rc = cli_main(argv)
    assert rc == 0
    rec2 = json.loads(capsys.readouterr().out.strip())
    assert rec2["iters"] == 50 and rec2["converged"] is True


def test_run_once_checkpointed_single(tmp_path):
    report = run_once(
        Problem(M=20, N=20),
        mode="single",
        dtype="f64",
        checkpoint_dir=str(tmp_path / "ck"),
        chunk=7,
    )
    assert report.iters == 26 and report.converged


@pytest.mark.parametrize("engine", ["resident", "streamed", "xl", "fused"])
def test_run_once_checkpoint_rejects_whole_kernel_engines(tmp_path, engine):
    """Checkpointing persists the XLA-loop PCG carry; the whole-solve
    kernel engines (whose state lives in VMEM scratch / kernel-private
    HBM) must be rejected with the xla-or-pallas pointer."""
    with pytest.raises(ValueError, match="xla or pallas"):
        run_once(
            Problem(M=20, N=20),
            mode="single",
            engine=engine,
            checkpoint_dir=str(tmp_path / "ck"),
        )


def test_cli_checkpoint_sweep_uses_per_run_subdirs(tmp_path):
    ck = str(tmp_path / "ck")
    rc = cli_main([
        "--grids", "10x10,20x20", "--mode", "single", "--dtype", "f64",
        "--checkpoint-dir", ck, "--chunk", "6", "--json",
    ])
    assert rc == 0
    import os

    assert os.path.isdir(os.path.join(ck, "10x10"))
    assert os.path.isdir(os.path.join(ck, "20x20"))


def test_run_once_checkpoint_rejects_repeat_batch(tmp_path):
    with pytest.raises(ValueError, match="repeat/batch"):
        run_once(
            Problem(M=10, N=10),
            mode="single",
            checkpoint_dir=str(tmp_path / "ck"),
            repeat=3,
        )


def test_run_once_unknown_mode_raises_with_checkpoint(tmp_path):
    with pytest.raises(ValueError, match="unknown mode"):
        run_once(
            Problem(M=10, N=10),
            mode="bogus",
            checkpoint_dir=str(tmp_path / "ck"),
        )


def test_cli_threads_sweep(capsys):
    from poisson_ellipse_tpu.runtime import native_available

    if not native_available():
        pytest.skip("C++ runtime unavailable")
    rc = cli_main(
        ["40", "40", "--mode", "native", "--threads-sweep", "1,2", "--json"]
    )
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2
    recs = [json.loads(l) for l in lines]
    # the stage1 invariant: iteration count is thread-invariant
    assert [r["iters"] for r in recs] == [50, 50]
    assert [r["threads"] for r in recs] == [1, 2]
    assert recs[0]["speedup_vs_first"] == 1.0


def test_cli_threads_sweep_requires_native_mode(capsys):
    rc = cli_main(["40", "40", "--mode", "single", "--threads-sweep", "1,2"])
    assert rc == 2
    assert "requires --mode native" in capsys.readouterr().err


def test_readme_bench_generator(tmp_path):
    """tools/update_readme_bench.py regenerates exactly the marker
    blocks from a bench artifact (driver format), leaves surrounding
    text untouched, and rejects artifacts predating the
    machine-readable rows."""
    import importlib.util
    import os
    import sys

    spec = importlib.util.spec_from_file_location(
        "urb",
        os.path.join(
            os.path.dirname(__file__), "..", "tools", "update_readme_bench.py"
        ),
    )
    urb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(urb)

    readme = tmp_path / "README.md"
    readme.write_text(
        "intro\n<!-- bench:headline -->\nOLD\n<!-- /bench:headline -->\n"
        "mid\n<!-- bench:table -->\nOLD\n<!-- /bench:table -->\noutro\n"
    )
    row = {
        "grid": [800, 1200], "t_solver_s": 0.008, "iters": 989,
        "converged": True, "engine": "resident", "l2_error": 2e-4,
        "ref_p100_s": 0.83, "vs_p100": 103.75,
    }
    artifact = tmp_path / "BENCH_r99.json"
    artifact.write_text(json.dumps({"parsed": {
        "metric": "m", "value": 0.008, "unit": "s", "vs_baseline": 103.75,
        "valid": True, "grids": [row],
        "config2": {**row, "grid": [1024, 1024]},
        "north_star": {**row, "grid": [4096, 4096], "engine": "xl"},
        "eps_sweep": [
            {"eps": 1e-2, "iters": 921, "converged": True,
             "t_solver_s": 0.01, "l2_error": 2e-4},
            {"eps": 1e-6, "iters": 921, "converged": True,
             "t_solver_s": 0.01, "l2_error": 2e-4},
        ],
        "f64": {**row},
    }}))
    summary = urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "OLD" not in text
    assert "103.75×" in text and "| 800×1200 |" in text
    assert text.startswith("intro\n") and text.rstrip().endswith("outro")
    assert "BENCH_r99.json" in summary
    # config4_1chip absent (older artifact shape): tolerated, no row
    assert "config-4" not in text
    # pre-machine-readable artifact is rejected with a pointer
    legacy = tmp_path / "legacy.json"
    legacy.write_text(json.dumps({"parsed": {"value": 1}}))
    with pytest.raises(SystemExit, match="machine-readable"):
        urb.regenerate(str(readme), str(legacy))


def test_bench_eps_sweep_solver_reuse_is_exact():
    """An eps sweep may reuse ONE jitted XLA solver across eps values
    (eps reaches the solve only through the assembled operands). Guard
    that assumption: a solver built for one eps, fed another eps's
    operands, must reproduce the fresh per-problem solve exactly."""
    from poisson_ellipse_tpu.ops import assembly as asm
    from poisson_ellipse_tpu.solver.engine import build_solver
    from poisson_ellipse_tpu.solver.pcg import solve as solve_xla

    p_a = Problem(M=24, N=24, eps=1e-2)
    p_b = Problem(M=24, N=24, eps=1e-5)
    reused, _, _ = build_solver(p_a, "xla", jnp.float32)
    fresh, _, _ = build_solver(p_b, "xla", jnp.float32)
    args_b = asm.assemble(p_b, jnp.float32)
    got = reused(*args_b)
    ref = fresh(*args_b)
    assert bool(got.converged)
    assert int(got.iters) == int(ref.iters)
    # also iteration-identical to the independent solve() entry point
    assert int(got.iters) == int(solve_xla(p_b, jnp.float32).iters)
    import numpy as np

    np.testing.assert_array_equal(np.asarray(got.w), np.asarray(ref.w))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64], ids=["f32", "f64"])
def test_iterations_stay_flat_across_eps(dtype):
    """BASELINE config 5's stiffness result at test scale: as eps falls
    from 1e-2 to 1e-6 the face coefficients outside the ellipse grow as
    1/eps, but the Jacobi preconditioner absorbs them, so every solve
    converges and the iteration counts stay inside a 25% band."""
    from poisson_ellipse_tpu.solver.pcg import solve as solve_xla

    iters = []
    for eps in (1e-2, 1e-3, 1e-4, 1e-5, 1e-6):
        result = solve_xla(Problem(M=20, N=20, eps=eps), dtype)
        assert bool(result.converged), eps
        iters.append(int(result.iters))
    assert max(iters) - min(iters) <= 0.25 * min(iters), iters


def test_cli_threads_sweep_conflicting_flags(capsys):
    rc = cli_main(
        ["40", "40", "--mode", "native", "--threads-sweep", "1,2",
         "--threads", "8"]
    )
    assert rc == 2 and "--threads conflicts" in capsys.readouterr().err
    rc = cli_main(
        ["40", "40", "--mode", "native", "--threads-sweep", "1,2",
         "--checkpoint-dir", "ck"]
    )
    assert rc == 2 and "not native" in capsys.readouterr().err


def test_resumed_checkpoint_report_suppresses_roofline(tmp_path):
    ck = str(tmp_path / "ck")
    first = run_once(
        Problem(M=20, N=20), mode="single", dtype="f64",
        checkpoint_dir=ck, chunk=7,
    )
    assert first.timed_iters == first.iters == 26
    assert first.roofline_line() != ""
    # resume of a finished run: zero iterations timed -> no roofline
    again = run_once(
        Problem(M=20, N=20), mode="single", dtype="f64",
        checkpoint_dir=ck, chunk=7,
    )
    assert again.iters == 26 and again.timed_iters == 0
    assert again.roofline_line() == ""
    assert again.hbm_gbps == 0.0 and again.passes_per_iter == 0.0


def test_roofline_line_vmem_resident_wording():
    from poisson_ellipse_tpu.harness.run import RunReport

    rep = RunReport(
        problem=Problem(M=40, N=40), mesh_shape=(1, 1), dtype="f32",
        engine="resident", iters=50, converged=True, breakdown=False,
        diff=1e-7, l2_error=1e-3, t_init=0.1, t_solver=0.001,
        passes_per_iter=0.0, hbm_gbps=0.0, hbm_peak_frac=0.0,
    )
    line = rep.roofline_line()
    assert "VMEM-resident" in line and "0 GB/s" not in line


# one grid per case (the sharded rows run at it too): the whole matrix
# in one test sat at the tier-1 per-test budget
@pytest.mark.parametrize("grid", [(10, 10), (20, 20), (40, 40)],
                         ids=lambda g: f"{g[0]}x{g[1]}")
def test_acceptance_gate_passes_on_cpu(grid):
    # on CPU the Pallas engines run in interpret mode; the oracle/contract
    # logic is identical, and the real-compile value comes from running
    # the same module on the chip (python -m ...harness.acceptance)
    from poisson_ellipse_tpu.harness.acceptance import run_acceptance
    import io

    buf = io.StringIO()
    assert run_acceptance(headline=False, out=buf, grids=(grid,)) is True, (
        buf.getvalue()
    )
    text = buf.getvalue()
    assert "ACCEPTANCE PASS" in text and "FAIL" not in text
