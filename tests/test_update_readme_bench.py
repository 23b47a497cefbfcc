"""tools/update_readme_bench.py: the README generator, under test.

The README's performance blocks are generated, so the generator is
load-bearing documentation infrastructure: a silent regression here
re-introduces exactly the hand-typed-numbers drift the tool exists to
prevent. Covered: artifact selection (round-number order, not
lexicographic), partial-artifact rejection with the curated message,
headline derivation from the artifact's own rows (no hardcoded grid/
chip/baseline), and marker-splice round-tripping (idempotence).
"""

from __future__ import annotations

import importlib.util
import json
import os
import time

import pytest

_TOOL = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools",
    "update_readme_bench.py",
)
_spec = importlib.util.spec_from_file_location("update_readme_bench", _TOOL)
urb = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(urb)


def make_artifact(**overrides) -> dict:
    rec = {
        "metric": "T_solver 100x200 (42 PCG iters to 1e-6), f32, 1 chip",
        "value": 0.5,
        "unit": "s",
        "vs_baseline": 4.0,
        "valid": True,
        "device": "TPU v6e",
        "grids": [
            {"grid": [100, 200], "t_solver_s": 0.5, "iters": 42,
             "converged": True, "engine": "resident",
             "ref_p100_s": 2.0, "vs_p100": 4.0},
            {"grid": [400, 600], "t_solver_s": 1.25, "iters": 99,
             "converged": True, "engine": "xl",
             "ref_p100_s": None, "vs_p100": None},
        ],
        "config2": {"grid": [64, 64], "t_solver_s": 0.01, "iters": 7,
                    "converged": True, "engine": "resident"},
        "eps_sweep": [
            {"eps": 1e-2, "iters": 7, "converged": True, "t_solver_s": 0.01},
            {"eps": 1e-6, "iters": 9, "converged": True, "t_solver_s": 0.01},
        ],
        "f64": {"grid": [100, 200], "t_solver_s": 3.0, "iters": 42,
                "converged": True, "engine": "xla"},
    }
    rec.update(overrides)
    return rec


def test_pipelined_row_rendered_when_present(workspace):
    _tmp, readme, artifact = workspace
    rec = make_artifact(
        pipelined={
            "grid": [100, 200], "t_solver_s": 0.45, "iters": 41,
            "converged": True, "engine": "pipelined", "l2_error": 1e-4,
            "t_xla_s": 0.5, "vs_xla": 1.111,
        }
    )
    artifact.write_text(json.dumps(rec))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "| 100×200 | 41 | pipelined | 0.4500 s |" in text
    assert "1 fused reduction/iter" in text
    assert "1.111× vs xla" in text
    # pre-pipelined artifacts still regenerate, without the row
    artifact.write_text(json.dumps(make_artifact()))
    urb.regenerate(str(readme), str(artifact))
    assert "pipelined" not in readme.read_text()


def test_observability_fields_rendered_when_present(workspace):
    _tmp, readme, artifact = workspace
    rec = make_artifact(
        convergence={
            "grid": [100, 200], "engine": "xla", "iters": 42,
            "converged": True, "diff_first": 0.02, "diff_final": 9.7e-7,
            "zr_first": 1e-3, "zr_final": 1e-14,
        },
        collectives={
            "available": True, "grid": [40, 40], "mesh": [1, 2],
            "engines": {
                "xla": {"psum_per_iter": 2, "ppermute_per_iter": 4},
                "pipelined": {"psum_per_iter": 1, "ppermute_per_iter": 12},
            },
        },
    )
    artifact.write_text(json.dumps(rec))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "42 iterations traced" in text
    assert "2.0e-02 → 9.7e-07" in text
    assert "**2** psum/iteration, pipelined **1**" in text
    assert "obs.static_cost" in text


def test_observability_fields_absent_is_supported(workspace):
    # pre-obs artifacts (no convergence/collectives keys) and skipped
    # accounting (available: false) both render without the lines
    _tmp, readme, artifact = workspace
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "iterations traced" not in text
    assert "psum/iteration" not in text
    artifact.write_text(
        json.dumps(make_artifact(collectives={"available": False}))
    )
    urb.regenerate(str(readme), str(artifact))
    assert "psum/iteration" not in readme.read_text()


def test_spectrum_table_rendered_when_present(workspace):
    _tmp, readme, artifact = workspace
    rec = make_artifact(
        spectrum=[
            {"grid": [100, 200], "engine": "xla", "iters": 42,
             "converged": True, "kappa": 5432.1, "cg_rate": 0.97325,
             "iters_bound": 80, "predicted_iters": 42,
             "predicted_err": 0.0, "stagnated": False},
        ]
    )
    artifact.write_text(json.dumps(rec))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Spectral diagnostics" in text
    assert "| 100×200 | 5432 | 0.97325 | 80 | 42 (+0.0%) | 42 |" in text
    assert "bench_compare" in text


def test_spectrum_absent_or_failed_is_supported(workspace):
    # pre-diagnostics artifacts lack the key; a failed row carries no
    # kappa — neither renders the table
    _tmp, readme, artifact = workspace
    urb.regenerate(str(readme), str(artifact))
    assert "Spectral diagnostics" not in readme.read_text()
    artifact.write_text(json.dumps(make_artifact(
        spectrum=[{"grid": [100, 200], "engine": "xla", "iters": 42,
                   "converged": False}]
    )))
    urb.regenerate(str(readme), str(artifact))
    assert "Spectral diagnostics" not in readme.read_text()


def test_precond_table_rendered_when_present(workspace):
    _tmp, readme, artifact = workspace
    rec = make_artifact(
        precond=[
            {"grid": [400, 600], "engine": "mg-pcg", "iters": 31,
             "t_solver_s": 0.0123, "converged": True, "l2_error": 1e-4,
             "diag_iters": 546, "diag_t_solver_s": 0.05,
             "iters_reduction": 17.6, "speedup_vs_diag": 4.07},
            {"grid": [800, 1200], "engine": "cheb-pcg", "iters": 90,
             "t_solver_s": 0.02, "converged": True, "l2_error": 2e-5},
        ]
    )
    artifact.write_text(json.dumps(rec))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Preconditioning" in text
    assert (
        "| 400×600 | mg-pcg | 31 (diag 546) | **17.6× fewer** | "
        "0.0123 s | 4.07× |" in text
    )
    # a row without the diag yardstick still renders, with dashes
    assert "| 800×1200 | cheb-pcg | 90 | — | 0.0200 s | — |" in text


def test_precond_absent_or_failed_is_supported(workspace):
    # pre-multigrid artifacts lack the key; a failed row (the run
    # aborted before an iteration count) is skipped, not a crash
    _tmp, readme, artifact = workspace
    urb.regenerate(str(readme), str(artifact))
    assert "Preconditioning (`mg/`" not in readme.read_text()
    artifact.write_text(json.dumps(make_artifact(
        precond=[{"grid": [400, 600], "engine": "mg-pcg",
                  "converged": False}]
    )))
    urb.regenerate(str(readme), str(artifact))
    assert "Preconditioning (`mg/`" not in readme.read_text()


def test_recovery_field_rendered_when_present(workspace):
    _tmp, readme, artifact = workspace
    rec = make_artifact(
        recovery={
            "grid": [100, 200], "engine": "xla", "fault": "nan", "at": 21,
            "iters": 42, "clean_iters": 42, "converged": True,
            "recoveries": ["residual-restart"],
        }
    )
    artifact.write_text(json.dumps(rec))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Resilience drill" in text
    assert "iteration 21" in text
    assert "residual-restart" in text
    assert "reconverges in 42 iterations" in text
    assert "oracle parity after recovery" in text


def test_recovery_field_absent_or_failed_is_supported(workspace):
    # pre-resilience artifacts lack the key entirely; an aborted drill
    # carries converged: false — neither renders the line
    _tmp, readme, artifact = workspace
    urb.regenerate(str(readme), str(artifact))
    assert "Resilience drill" not in readme.read_text()
    artifact.write_text(json.dumps(make_artifact(
        recovery={"grid": [100, 200], "engine": "xla", "fault": "nan",
                  "at": 21, "converged": False, "aborted": "diverged"}
    )))
    urb.regenerate(str(readme), str(artifact))
    assert "Resilience drill" not in readme.read_text()


def test_abft_field_rendered_when_present(workspace):
    _tmp, readme, artifact = workspace
    rec = make_artifact(
        abft={
            "available": True, "grid": [800, 1200], "mesh": [1, 2],
            "t_off_s": 1.0, "t_on_s": 1.012, "overhead_pct": 1.2,
            "gate_pct": 2.0, "iters_off": 99, "iters_on": 99,
            "psum_per_iter": 2, "ppermute_per_iter": 4,
            "collectives_identical": True, "ok": True,
        }
    )
    artifact.write_text(json.dumps(rec))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "ABFT silent-corruption checks" in text
    assert "+1.20%" in text
    assert "collective counts identical on/off" in text
    assert "2 psum/iteration" in text


def test_abft_field_absent_or_failed_is_supported(workspace):
    # pre-abft artifacts lack the key; a single-device bench box emits
    # available: false — neither renders the line
    _tmp, readme, artifact = workspace
    urb.regenerate(str(readme), str(artifact))
    assert "ABFT silent-corruption checks" not in readme.read_text()
    artifact.write_text(json.dumps(make_artifact(
        abft={"available": False}
    )))
    urb.regenerate(str(readme), str(artifact))
    assert "ABFT silent-corruption checks" not in readme.read_text()


def test_throughput_and_coldstart_rendered_when_present(workspace):
    _tmp, readme, artifact = workspace
    rec = make_artifact(
        throughput=[
            {"grid": [100, 200], "lanes": 1, "engine": "batched",
             "t_batch_s": 0.5, "solves_per_sec": 2.0,
             "speedup_vs_1lane": 1.0, "iters": 42, "converged": True},
            {"grid": [100, 200], "lanes": 8, "engine": "batched",
             "t_batch_s": 1.0, "solves_per_sec": 8.0,
             "speedup_vs_1lane": 4.0, "iters": 42, "converged": True},
        ],
        coldstart={
            "grid": [100, 200], "engine": "batched", "lanes": 8,
            "t_compile_s": 2.5, "t_solve_s": 0.5,
            "t_pool_cold_s": 2.4, "t_pool_warm_s": 0.0002,
            "pool_hit": True,
        },
    )
    artifact.write_text(json.dumps(rec))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Serving throughput" in text
    assert "| 100×200 | 8 | 1.00 s | 8 | **4×** |" in text
    assert "Cold-start split (100×200, lanes=8)" in text
    assert "compile 2.50 s vs solve 0.5000 s" in text
    assert "cache HIT returning the same executable (0.20 ms)" in text


def test_throughput_absent_or_failed_is_supported(workspace):
    # pre-batch artifacts lack the keys; a failed throughput row (no
    # solves_per_sec — the run aborted) is skipped, a missed warm pool
    # renders as the regression it is
    _tmp, readme, artifact = workspace
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Serving throughput" not in text
    assert "Cold-start split" not in text
    artifact.write_text(json.dumps(make_artifact(
        throughput=[{"grid": [100, 200], "lanes": 8, "engine": "batched",
                     "converged": False}],
        coldstart={"grid": [100, 200], "engine": "batched", "lanes": 8,
                   "t_compile_s": 2.5, "t_solve_s": 0.5,
                   "t_pool_cold_s": 2.4, "t_pool_warm_s": 2.3,
                   "pool_hit": False},
    )))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Serving throughput" not in text  # no renderable rows
    assert "MISSED the warm pool (regression)" in text


README_STUB = """# stub

<!-- bench:headline -->
stale headline
<!-- /bench:headline -->

prose between the blocks

<!-- bench:table -->
stale table
<!-- /bench:table -->
"""


@pytest.fixture
def workspace(tmp_path):
    readme = tmp_path / "README.md"
    readme.write_text(README_STUB)
    artifact = tmp_path / "BENCH_r02.json"
    artifact.write_text(json.dumps({"parsed": make_artifact()}))
    return tmp_path, readme, artifact


def test_fleet_table_rendered_when_present(workspace):
    _tmp, readme, artifact = workspace
    rec = make_artifact(fleet={
        "rows": [
            {"replicas": 1, "lanes": 2, "solves_per_sec": 100.0},
            {"replicas": 3, "lanes": 2, "solves_per_sec": 115.0},
        ],
        "non_decreasing": True,
        "handoff_p99_s": 0.0025,
        "rejoin_latency_s": 0.31,
        "handoffs": 1,
        "adopted": 3,
        "rejoins": 1,
        "kill_completed": 24,
    })
    artifact.write_text(json.dumps(rec))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Replicated fleet" in text
    assert "| 1 | 2 | 100 |" in text
    assert "| 3 | 2 | 115 |" in text
    assert "handoff latency p99 2.50 ms" in text
    assert "3 request(s) adopted" in text
    # the kill-drill sentence states only what the artifact carries
    assert "24 request(s) completed after the kill" in text
    assert "zero requests lost" not in text
    assert "kill→first-completed-solve p99 310.00 ms" in text
    assert "1 rejoin(s)" in text


def test_fleet_absent_or_failed_is_supported(workspace):
    # pre-fleet artifacts lack the key; a failed key (no usable rows)
    # renders nothing; rows without a kill drill render the table alone
    _tmp, readme, artifact = workspace
    urb.regenerate(str(readme), str(artifact))
    assert "Replicated fleet" not in readme.read_text()
    artifact.write_text(json.dumps(make_artifact(fleet={"rows": []})))
    urb.regenerate(str(readme), str(artifact))
    assert "Replicated fleet" not in readme.read_text()
    artifact.write_text(json.dumps(make_artifact(fleet={
        "rows": [{"replicas": 2, "lanes": 2, "solves_per_sec": 90.0}],
        "non_decreasing": True,
        "handoff_p99_s": None,
    })))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "| 2 | 2 | 90 |" in text
    assert "Kill drill" not in text
    assert "Rejoin drill" not in text
    # a pre-rejoin artifact (kill drill but no recovery number) renders
    # the kill line alone
    artifact.write_text(json.dumps(make_artifact(fleet={
        "rows": [{"replicas": 2, "lanes": 2, "solves_per_sec": 90.0}],
        "non_decreasing": True,
        "handoff_p99_s": 0.002,
        "handoffs": 1,
    })))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Kill drill" in text
    assert "Rejoin drill" not in text


def test_regenerate_derives_everything_from_artifact(workspace):
    tmp, readme, artifact = workspace
    summary = urb.regenerate(str(readme), str(artifact), root=str(tmp))
    text = readme.read_text()
    assert "BENCH_r02.json" in summary
    head = text.split("<!-- bench:headline -->")[1].split(
        "<!-- /bench:headline -->"
    )[0]
    # grid, iters, δ, chip and baseline all come from the artifact rows
    assert "**0.5000 s** for 100×200" in head
    assert "42 iterations to δ=1e-6" in head
    assert "TPU v6e" in head
    assert "single-P100 2.0 s" in head
    assert "**4×**" in head
    # the headline row is bolded in the table; non-reference rows dashed
    assert "| 100×200 | 42 | resident | **0.5000 s** | 2.0 s | **4×** |" in text
    assert "| 400×600 | 99 | xl | 1.25 s | — | — |" in text
    # prose outside the markers untouched
    assert "prose between the blocks" in text


def test_regenerate_is_idempotent(workspace):
    tmp, readme, artifact = workspace
    urb.regenerate(str(readme), str(artifact), root=str(tmp))
    once = readme.read_text()
    urb.regenerate(str(readme), str(artifact), root=str(tmp))
    assert readme.read_text() == once


def test_device_falls_back_to_measured_part(workspace):
    tmp, readme, artifact = workspace
    rec = make_artifact()
    del rec["device"]
    artifact.write_text(json.dumps(rec))  # raw line form (no driver wrapper)
    urb.regenerate(str(readme), str(artifact), root=str(tmp))
    assert urb.MEASURED_DEVICE in readme.read_text()


@pytest.mark.parametrize("missing", ["grids", "config2", "eps_sweep", "f64"])
def test_partial_artifact_gets_curated_error(workspace, missing):
    tmp, readme, artifact = workspace
    rec = make_artifact()
    del rec[missing]
    artifact.write_text(json.dumps(rec))
    with pytest.raises(SystemExit) as exc:
        urb.regenerate(str(readme), str(artifact), root=str(tmp))
    assert missing in str(exc.value)
    assert "re-run" in str(exc.value)


def test_empty_rows_get_the_curated_error_too(workspace):
    # an aborted driver run can serialize "grids": [] — as unusable as
    # an absent key, and it must not surface as a raw IndexError
    tmp, readme, artifact = workspace
    artifact.write_text(json.dumps(make_artifact(grids=[])))
    with pytest.raises(SystemExit) as exc:
        urb.regenerate(str(readme), str(artifact), root=str(tmp))
    assert "grids" in str(exc.value)


def test_newest_artifact_by_round_number_not_lexicographic(tmp_path, capsys):
    # lexicographic sort would pick r9 over r10 and r100; round-number
    # parse must not
    for name in ("BENCH_r9.json", "BENCH_r10.json", "BENCH_r100.json"):
        (tmp_path / name).write_text("{}")
        time.sleep(0.01)
    # make the lexicographic winner also the mtime winner, so only the
    # round-number key can produce the right answer
    os.utime(tmp_path / "BENCH_r9.json")
    picked = urb.newest_artifact(str(tmp_path))
    assert os.path.basename(picked) == "BENCH_r100.json"
    assert "BENCH_r100.json" in capsys.readouterr().out


def test_missing_marker_is_a_curated_error(workspace):
    tmp, readme, artifact = workspace
    readme.write_text("# no markers here\n")
    with pytest.raises(SystemExit) as exc:
        urb.regenerate(str(readme), str(artifact), root=str(tmp))
    assert "marker" in str(exc.value)


def test_geometry_field_rendered_when_present(workspace):
    _tmp, readme, artifact = workspace
    rec = make_artifact(
        geometry={
            "grid": [400, 600], "assembly_cf_s": 0.2,
            "assembly_quad_s": 1.0, "assembly_overhead_x": 5.0,
            "max_frac_err": 3.7e-15, "sdf_ellipse_iters": 99,
            "oracle_iters": 99,
            "composite": {"domain": "ellipse-minus-hole",
                          "t_solver_s": 0.75, "iters": 88,
                          "converged": True, "min_u": 0.0},
        }
    )
    artifact.write_text(json.dumps(rec))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Geometry (SDF quadrature" in text
    assert "3.7e-15" in text
    assert "Composite domain (ellipse-minus-hole)" in text
    assert "maximum principle held" in text


def test_geometry_field_absent_or_failed_is_supported(workspace):
    # pre-geometry artifacts lack the key; a failed composite half
    # (no t_solver_s) renders the parity line only
    _tmp, readme, artifact = workspace
    urb.regenerate(str(readme), str(artifact))
    assert "Geometry (SDF quadrature" not in readme.read_text()
    artifact.write_text(json.dumps(make_artifact(
        geometry={
            "grid": [400, 600], "max_frac_err": 1e-14,
            "sdf_ellipse_iters": 99, "oracle_iters": 99,
            "composite": {"domain": "ellipse-minus-hole",
                          "converged": False},
        }
    )))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Geometry (SDF quadrature" in text
    assert "Composite domain" not in text


def test_grad_field_rendered_when_present(workspace):
    _tmp, readme, artifact = workspace
    rec = make_artifact(
        grad={
            "grid": [400, 600], "lanes": 4, "n_requests": 8,
            "grad_solves_per_sec": 12.5, "wall_s": 0.64,
            "rows": [{"grid": [400, 600], "primal_iters": 546,
                      "adjoint_iters": 540, "ratio": 0.989}],
            "valid": True,
        }
    )
    artifact.write_text(json.dumps(rec))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Differentiable solving" in text
    assert "12.5 grad-solves/sec" in text
    assert "540/546" in text
    assert "grad-pct" in text


def test_grad_field_absent_or_failed_is_supported(workspace):
    # pre-diff artifacts lack the key; a failed throughput half (no
    # grad_solves_per_sec) still renders the ratio rows it carries
    _tmp, readme, artifact = workspace
    urb.regenerate(str(readme), str(artifact))
    assert "Differentiable solving" not in readme.read_text()
    artifact.write_text(json.dumps(make_artifact(
        grad={
            "grid": [400, 600], "grad_solves_per_sec": None,
            "rows": [{"grid": [400, 600], "primal_iters": 546,
                      "adjoint_iters": 560, "ratio": 1.026}],
            "valid": False,
        }
    )))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Differentiable solving" not in text
    assert "Adjoint-vs-primal iterations" in text
    assert "560/546" in text


def test_bandwidth_table_rendered_when_present(workspace):
    rec = make_artifact(bandwidth={
        "available": True,
        "grid": [2400, 3200],
        "byte_ratio_gate": 0.6,
        "cells": [
            {"engine": "sstep", "storage": "f32", "t_solver_s": 1.2,
             "hbm_gbps": 310.0, "l2_err": 9.9e-5},
            {"engine": "sstep", "storage": "bf16", "t_solver_s": 0.7,
             "hbm_gbps": 520.0, "l2_err": 1.01e-4,
             "byte_ratio_vs_f32": 0.5, "l2_parity": True},
        ],
        "ok": True,
    })
    lines = urb.bandwidth_lines(rec)
    text = "\n".join(lines)
    assert "Memory-bandwidth frontier at 2400×3200" in text
    assert "| sstep | bf16 | 0.7 s | 520 |" in text
    assert "0.50×" in text


def test_bandwidth_absent_or_failed_is_supported(workspace):
    assert urb.bandwidth_lines(make_artifact()) == []
    assert urb.bandwidth_lines(
        make_artifact(bandwidth={"available": False, "error": "x"})
    ) == []
    assert urb.bandwidth_lines(
        make_artifact(bandwidth={"available": True, "cells": []})
    ) == []


def test_fmg_table_rendered_when_present(workspace):
    rec = make_artifact(fmg={
        "work_units_constant": True,
        "rows": [
            {"grid": [400, 600], "t_solver_s": 0.012, "iters": 3,
             "work_units_per_point": 60.5, "speedup_vs_mg": 1.3},
            {"grid": [4096, 4096], "t_solver_s": 0.31, "iters": 3,
             "work_units_per_point": 62.1, "speedup_vs_mg": 2.4,
             "headline": True},
        ],
    })
    text = "\n".join(urb.fmg_lines(rec))
    assert "Full multigrid as the solver" in text
    assert "work units per grid point constant" in text
    assert "| 4096×4096 (headline) |" in text
    assert "**2.4×**" in text


def test_fmg_absent_or_failed_is_supported(workspace):
    assert urb.fmg_lines(make_artifact()) == []
    assert urb.fmg_lines(make_artifact(fmg={"rows": []})) == []
    # a failed row (no t_solver_s) is skipped, not a crash
    assert urb.fmg_lines(make_artifact(fmg={
        "work_units_constant": True,
        "rows": [{"grid": [400, 600], "error": "OOM"}],
    })) == []


def test_autotune_table_rendered_when_present(workspace):
    rec = make_artifact(autotune={
        "rows": [
            {"grid": [400, 600], "tuned_engine": "fmg",
             "static_engine": "xl", "tuned_t_s": 0.012,
             "static_t_s": 0.05, "tuned_loses": False,
             "roundtrip_ok": True},
            {"grid": [100, 200], "tuned_engine": "resident",
             "static_engine": "resident", "tuned_t_s": 0.004,
             "static_t_s": 0.004, "tuned_loses": False,
             "roundtrip_ok": True},
        ],
    })
    text = "\n".join(urb.autotune_lines(rec))
    assert "Telemetry-driven autotuning" in text
    assert "tuned wins" in text
    assert "static stands" in text


def test_autotune_absent_or_failed_is_supported(workspace):
    assert urb.autotune_lines(make_artifact()) == []
    assert urb.autotune_lines(make_artifact(autotune={"rows": []})) == []
    assert urb.autotune_lines(make_artifact(autotune={
        "rows": [{"grid": [400, 600], "error": "probe failed"}],
    })) == []


def test_fmg_and_autotune_ride_the_table_block(workspace):
    _tmp, readme, artifact = workspace
    rec = make_artifact(
        fmg={"work_units_constant": True, "rows": [
            {"grid": [400, 600], "t_solver_s": 0.012, "iters": 3,
             "work_units_per_point": 60.5, "speedup_vs_mg": 1.3},
        ]},
        autotune={"rows": [
            {"grid": [400, 600], "tuned_engine": "fmg",
             "static_engine": "xl", "tuned_t_s": 0.012,
             "static_t_s": 0.05, "tuned_loses": False,
             "roundtrip_ok": True},
        ]},
    )
    artifact.write_text(json.dumps(rec))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Full multigrid as the solver" in text
    assert "Telemetry-driven autotuning" in text


def _recycle_key(cut=4.13, valid=True, **overrides):
    row = {
        "grid": [128, 128], "stream": 5, "ring_cap": 64, "basis_rank": 8,
        "capture_iters": 150, "iters_cold_mean": 149.6,
        "iters_warm_mean": 36.2, "iter_cut": cut, "l2_rel_gap_max": 0.0501,
        "solves_per_s_cold": 2.77, "solves_per_s_warm": 3.15,
        "converged": True, "valid": valid,
    }
    row.update(overrides)
    return row


def test_recycle_table_rendered_when_present(workspace):
    _tmp, readme, artifact = workspace
    artifact.write_text(json.dumps(make_artifact(recycle=_recycle_key())))
    urb.regenerate(str(readme), str(artifact))
    text = readme.read_text()
    assert "Krylov recycling" in text
    assert "149.6 → 36.2 | **4.13× cut**" in text
    assert "2.77 → 3.15 | 5.0% |" in text
    # a round whose cut fell below the pin renders the broken verdict
    # loudly instead of a bold headline
    artifact.write_text(json.dumps(
        make_artifact(recycle=_recycle_key(cut=1.7, valid=False))
    ))
    urb.regenerate(str(readme), str(artifact))
    assert "1.7× (PIN BROKEN)" in readme.read_text()


def test_recycle_absent_or_failed_is_supported(workspace):
    # pre-recycling artifacts lack the key; a declined capture carries
    # no iter_cut — neither renders the block
    _tmp, readme, artifact = workspace
    urb.regenerate(str(readme), str(artifact))
    assert "Krylov recycling" not in readme.read_text()
    assert urb.recycle_lines(make_artifact()) == []
    assert urb.recycle_lines(
        make_artifact(recycle={"grid": [128, 128], "valid": False})
    ) == []
