"""tools/bench_compare.py: the perf regression gate, under test.

The gate's value is its exit-code contract — 0 = no regression,
nonzero naming the offending metric — so that contract is what the
tests pin, metric by metric, plus the tolerance-from-pyproject loading
and the skip-don't-fail stance on keys only one round carries (older
artifacts predate newer bench keys; that must never fail the gate).
"""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

_TOOL = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools",
    "bench_compare.py",
)
_spec = importlib.util.spec_from_file_location("bench_compare", _TOOL)
bc = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bc)

TOL = dict(bc.DEFAULT_TOLERANCES)


def make_round(**overrides) -> dict:
    rec = {
        "metric": "T_solver 100x200 (42 PCG iters to 1e-6), f32, 1 chip",
        "value": 0.5,
        "valid": True,
        "grids": [
            {"grid": [100, 200], "t_solver_s": 0.5, "iters": 42,
             "converged": True, "engine": "resident", "hbm_gbps": 100.0},
            {"grid": [400, 600], "t_solver_s": 1.0, "iters": 99,
             "converged": True, "engine": "xl", "hbm_gbps": 200.0},
        ],
        "config2": {"grid": [64, 64], "t_solver_s": 0.01, "iters": 7},
        "f64": {"grid": [100, 200], "t_solver_s": 3.0, "iters": 42},
        "spectrum": [
            {"grid": [100, 200], "kappa": 5000.0, "predicted_iters": 42},
        ],
        "throughput": [
            {"grid": [100, 200], "lanes": 8, "solves_per_sec": 50.0},
        ],
    }
    rec.update(overrides)
    return rec


def regressions_between(old, new):
    regs, _notes = bc.compare(old, new, TOL)
    return [(r.metric, r.where) for r in regs]


# ------------------------------------------------------- per-metric gates


def test_identical_rounds_have_no_regressions():
    rec = make_round()
    assert regressions_between(rec, rec) == []


def test_t_solver_regression_is_named_per_grid():
    new = make_round()
    new["grids"][0]["t_solver_s"] = 0.5 * (1 + TOL["t-solver-pct"]) * 1.01
    assert regressions_between(make_round(), new) == [
        ("t_solver_s", "100x200")
    ]
    # within tolerance: silent
    new["grids"][0]["t_solver_s"] = 0.5 * (1 + TOL["t-solver-pct"]) * 0.99
    assert regressions_between(make_round(), new) == []
    # getting FASTER is never a regression
    new["grids"][0]["t_solver_s"] = 0.1
    assert regressions_between(make_round(), new) == []


def test_iters_regression_is_absolute():
    new = make_round()
    new["grids"][1]["iters"] = 99 + int(TOL["iters-abs"]) + 1
    assert regressions_between(make_round(), new) == [("iters", "400x600")]
    new["grids"][1]["iters"] = 99 + int(TOL["iters-abs"])  # the ±2 reorder
    assert regressions_between(make_round(), new) == []


def test_scalar_row_keys_are_gated_too():
    new = make_round()
    new["f64"]["t_solver_s"] = 3.0 * 2
    assert regressions_between(make_round(), new) == [("t_solver_s", "f64")]


def test_gbps_drop_and_kappa_drift_are_regressions():
    new = make_round()
    new["grids"][0]["hbm_gbps"] = 100.0 * (1 - TOL["gbps-pct"]) * 0.9
    assert regressions_between(make_round(), new) == [
        ("hbm_gbps", "100x200")
    ]
    # kappa drifts BOTH ways: the operator didn't change, the estimator did
    for factor in (1 + TOL["kappa-pct"] * 1.5, 1 - TOL["kappa-pct"] * 1.5):
        new = make_round()
        new["spectrum"][0]["kappa"] = 5000.0 * factor
        assert regressions_between(make_round(), new) == [
            ("kappa", "100x200")
        ]
    new = make_round()
    new["spectrum"][0]["kappa"] = 5000.0 * (1 + TOL["kappa-pct"] * 0.5)
    assert regressions_between(make_round(), new) == []


def test_throughput_drop_is_a_regression():
    new = make_round()
    new["throughput"][0]["solves_per_sec"] = 50.0 * (1 - TOL["sps-pct"]) / 2
    assert regressions_between(make_round(), new) == [
        ("solves_per_sec", "100x200 lanes=8")
    ]


def _abft_row(**overrides):
    row = {
        "available": True, "grid": [800, 1200], "mesh": [1, 2],
        "t_off_s": 1.0, "t_on_s": 1.01, "overhead_pct": 1.0,
        "gate_pct": 2.0, "iters_off": 99, "iters_on": 99,
        "psum_per_iter": 2, "ppermute_per_iter": 4,
        "collectives_identical": True, "ok": True,
    }
    row.update(overrides)
    return row


def test_abft_overhead_creep_is_a_regression():
    old = make_round(abft=_abft_row())
    new = make_round(
        abft=_abft_row(overhead_pct=1.0 + TOL["abft-pp"] * 1.5)
    )
    assert regressions_between(old, new) == [("abft_overhead_pct", "abft")]
    # within the percentage-point band: silent
    new = make_round(
        abft=_abft_row(overhead_pct=1.0 + TOL["abft-pp"] * 0.5)
    )
    assert regressions_between(old, new) == []


def test_abft_broken_cadence_pin_is_a_regression():
    old = make_round(abft=_abft_row())
    new = make_round(abft=_abft_row(collectives_identical=False))
    assert regressions_between(old, new) == [("abft_collectives", "abft")]


def test_abft_only_in_one_round_is_noted_not_failed():
    old = make_round()  # pre-abft artifact
    new = make_round(abft=_abft_row())
    regs, notes = bc.compare(old, new, TOL)
    assert regs == []
    assert any("abft" in n for n in notes)
    # an unavailable row (single-device bench box) skips the same way
    regs, notes = bc.compare(
        make_round(abft={"available": False}), new, TOL
    )
    assert regs == []


def _fleet_key(**overrides):
    key = {
        "rows": [
            {"replicas": 1, "lanes": 2, "solves_per_sec": 100.0,
             "completed": 24, "wall_s": 0.24},
            {"replicas": 2, "lanes": 2, "solves_per_sec": 110.0,
             "completed": 24, "wall_s": 0.22},
            {"replicas": 3, "lanes": 2, "solves_per_sec": 115.0,
             "completed": 24, "wall_s": 0.21},
        ],
        "non_decreasing": True,
        "handoff_p99_s": 0.002,
        "rejoin_latency_s": 0.2,
        "kill_completed": 24,
        "handoffs": 1,
        "adopted": 3,
        "rejoins": 1,
    }
    key.update(overrides)
    return key


def test_fleet_aggregate_drop_is_a_regression():
    old = make_round(fleet=_fleet_key())
    new_key = _fleet_key()
    new_key["rows"][1]["solves_per_sec"] = (
        110.0 * (1 - TOL["fleet-agg-pct"]) / 2
    )
    new = make_round(fleet=new_key)
    assert regressions_between(old, new) == [
        ("fleet_solves_per_sec", "fleet replicas=2")
    ]


def test_fleet_broken_scaling_pin_is_a_regression():
    old = make_round(fleet=_fleet_key())
    new = make_round(fleet=_fleet_key(non_decreasing=False))
    assert ("fleet_non_decreasing", "fleet") in regressions_between(old, new)


def test_fleet_within_tolerance_is_clean():
    old = make_round(fleet=_fleet_key())
    new_key = _fleet_key()
    new_key["rows"][0]["solves_per_sec"] = (
        100.0 * (1 - TOL["fleet-agg-pct"] / 2)
    )
    assert regressions_between(old, new_round := make_round(fleet=new_key)) == []
    assert new_round["fleet"]["non_decreasing"]


def test_fleet_rejoin_latency_growth_is_a_regression():
    old = make_round(fleet=_fleet_key())
    slow = 0.2 * (1 + TOL["rejoin-p99-pct"]) * 1.1
    new = make_round(fleet=_fleet_key(rejoin_latency_s=slow))
    assert ("fleet_rejoin_latency_s", "fleet") in regressions_between(
        old, new
    )


def test_fleet_rejoin_absent_in_old_round_is_noted_not_failed():
    # a pre-rejoin artifact has no rejoin_latency_s: the new round's
    # number is noted one-sided, never failed against the absence
    old = make_round(fleet=_fleet_key(rejoin_latency_s=None, rejoins=0))
    new = make_round(fleet=_fleet_key())
    regs, notes = bc.compare(old, new, TOL)
    assert regs == []
    assert any("rejoin_latency_s" in n for n in notes)


def test_fleet_rejoin_drill_without_latency_is_a_regression():
    # the drill RAN (rejoins >= 1) but the recovery number went
    # missing — a broken emitter, not tolerable absence
    old = make_round(fleet=_fleet_key())
    new = make_round(fleet=_fleet_key(rejoin_latency_s=None, rejoins=1))
    assert ("fleet_rejoin_latency_s", "fleet") in regressions_between(
        old, new
    )


def test_fleet_only_in_one_round_is_noted_not_failed():
    old = make_round()  # pre-fleet artifact
    new = make_round(fleet=_fleet_key())
    regs, notes = bc.compare(old, new, TOL)
    assert regs == []
    assert any("fleet" in n for n in notes)
    # a failed fleet key with no rows skips the same way
    regs, _ = bc.compare(
        make_round(fleet={"rows": []}), new, TOL
    )
    assert regs == []


def _precond_rows():
    return [
        {"grid": [100, 200], "engine": "mg-pcg", "iters": 30,
         "t_solver_s": 0.2, "converged": True, "l2_error": 1e-4,
         "diag_iters": 420, "diag_t_solver_s": 0.5,
         "iters_reduction": 14.0, "speedup_vs_diag": 2.5},
        {"grid": [100, 200], "engine": "cheb-pcg", "iters": 60,
         "t_solver_s": 0.3, "converged": True, "l2_error": 1e-4},
    ]


def test_precond_regressions_are_named_per_grid_and_engine():
    base = make_round(precond=_precond_rows())
    assert regressions_between(base, base) == []
    # iters are operator-determined: growth past the fractional band
    # means the V-cycle/bounds broke, and the row names grid AND engine
    new = make_round(precond=_precond_rows())
    new["precond"][0]["iters"] = int(
        30 * (1 + TOL["precond-iters-pct"]) * 1.1
    )
    assert regressions_between(base, new) == [
        ("precond_iters", "100x200 mg-pcg")
    ]
    # the wall-clock win the key exists to defend
    new = make_round(precond=_precond_rows())
    new["precond"][1]["t_solver_s"] = 0.3 * (1 + TOL["precond-t-pct"]) * 1.05
    assert regressions_between(base, new) == [
        ("precond_t_solver_s", "100x200 cheb-pcg")
    ]
    # within tolerance / getting faster: silent
    new = make_round(precond=_precond_rows())
    new["precond"][0]["t_solver_s"] = 0.05
    new["precond"][1]["iters"] = 58
    assert regressions_between(base, new) == []


def test_precond_only_in_one_round_is_noted_not_failed():
    # pre-multigrid artifacts lack the key: skip with a note, never fail
    old = make_round()
    new = make_round(precond=_precond_rows())
    regs, notes = bc.compare(old, new, TOL)
    assert regs == []
    assert any("precond" in n for n in notes)


def test_null_kappa_in_a_matched_row_is_noted_not_silent():
    # bench_spectrum writes kappa=null when the trace was unusable —
    # exactly the broken-estimator case the gate exists to surface, so
    # it must land in the notes even though both rounds carry the key
    new = make_round()
    new["spectrum"][0]["kappa"] = None
    regs, notes = bc.compare(make_round(), new, TOL)
    assert regs == []
    assert any("kappa" in n and "100x200" in n for n in notes)


def test_one_sided_keys_are_skipped_with_a_note_not_failed():
    old = make_round()
    del old["spectrum"]
    del old["throughput"]
    old["grids"] = old["grids"][:1]
    for row in old["grids"]:
        row.pop("hbm_gbps")
    regs, notes = bc.compare(old, make_round(), TOL)
    assert regs == []
    text = " ".join(notes)
    assert "spectrum" in text and "throughput" in text
    assert "400x600" in text and "hbm_gbps" in text


# --------------------------------------------------------- CLI contract


def write_rounds(tmp_path, old, new):
    po, pn = tmp_path / "BENCH_r01.json", tmp_path / "BENCH_r02.json"
    po.write_text(json.dumps({"parsed": old}))  # driver artifact form
    pn.write_text(json.dumps(new))  # raw line form (no driver wrapper)
    return str(po), str(pn)


def test_cli_exit_0_on_clean_and_1_with_named_metric(tmp_path, capsys):
    po, pn = write_rounds(tmp_path, make_round(), make_round())
    assert bc.main([po, pn]) == 0
    assert "no regressions" in capsys.readouterr().out
    slow = make_round()
    slow["grids"][0]["t_solver_s"] = 5.0
    po, pn = write_rounds(tmp_path, make_round(), slow)
    assert bc.main([po, pn]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION t_solver_s @ 100x200" in out


def test_cli_json_mode_carries_the_regression_list(tmp_path, capsys):
    slow = make_round()
    slow["grids"][0]["t_solver_s"] = 5.0
    po, pn = write_rounds(tmp_path, make_round(), slow)
    assert bc.main(["--json", po, pn]) == 1
    rec = json.loads(capsys.readouterr().out)
    assert rec["regressions"][0]["metric"] == "t_solver_s"
    assert rec["tolerances"]["t-solver-pct"] == TOL["t-solver-pct"]


def test_cli_usage_and_unreadable_input_exit_2(tmp_path, capsys):
    assert bc.main(["one.json"]) == 2  # one path is not a comparison
    # unusable input is 2, NEVER 1: a gate reading 1 as "regression"
    # must not misclassify a corrupt artifact as a slowdown
    bad = tmp_path / "BENCH_r01.json"
    bad.write_text("{not json")
    assert bc.main([str(bad), str(bad)]) == 2
    assert "cannot read" in capsys.readouterr().err
    listy = tmp_path / "BENCH_r02.json"
    listy.write_text("[1, 2]")
    assert bc.main([str(listy), str(listy)]) == 2


def test_newest_rounds_orders_by_round_number(tmp_path):
    for name in ("BENCH_r9.json", "BENCH_r10.json", "BENCH_r2.json"):
        (tmp_path / name).write_text("{}")
    pair = [os.path.basename(p) for p in bc.newest_rounds(str(tmp_path))]
    assert pair == ["BENCH_r9.json", "BENCH_r10.json"]


def test_tolerances_load_from_pyproject_with_defaults(tmp_path):
    # the repo's own pyproject overrides nothing surprising
    repo_tol = bc.load_tolerances()
    assert set(repo_tol) == set(bc.DEFAULT_TOLERANCES)
    assert repo_tol["iters-abs"] == 2
    # an explicit table overrides; the fallback parser stores floats as
    # strings, so coercion is part of the contract under test
    (tmp_path / "pyproject.toml").write_text(
        "[tool.bench_compare]\nt-solver-pct = 0.5\niters-abs = 10\n"
    )
    tol = bc.load_tolerances(str(tmp_path))
    assert tol["t-solver-pct"] == 0.5
    assert tol["iters-abs"] == 10
    assert tol["kappa-pct"] == bc.DEFAULT_TOLERANCES["kappa-pct"]
    (tmp_path / "pyproject.toml").write_text(
        "[tool.bench_compare]\nt-solver-pct = banana\n"
    )
    with pytest.raises(SystemExit, match="t-solver-pct"):
        bc.load_tolerances(str(tmp_path))


# ------------------------------------------------------- geometry rows


def _geometry_row(**overrides):
    row = {
        "grid": [400, 600], "assembly_cf_s": 0.2, "assembly_quad_s": 1.0,
        "assembly_overhead_x": 5.0, "max_frac_err": 1e-14,
        "sdf_ellipse_iters": 42, "oracle_iters": 42,
        "composite": {"domain": "ellipse-minus-hole", "t_solver_s": 0.5,
                      "iters": 40, "converged": True, "min_u": 0.0},
    }
    row.update(overrides)
    return row


def test_geometry_composite_slowdown_is_a_regression():
    old = make_round(geometry=_geometry_row())
    comp = dict(_geometry_row()["composite"])
    comp["t_solver_s"] = 0.5 * (1 + TOL["geometry-t-pct"]) * 1.01
    new = make_round(geometry=_geometry_row(composite=comp))
    assert regressions_between(old, new) == [
        ("geometry_t_solver_s", "composite")
    ]
    comp["t_solver_s"] = 0.5 * (1 + TOL["geometry-t-pct"]) * 0.99
    new = make_round(geometry=_geometry_row(composite=comp))
    assert regressions_between(old, new) == []


def test_geometry_assembly_slowdown_and_frac_err_are_regressions():
    old = make_round(geometry=_geometry_row())
    new = make_round(geometry=_geometry_row(
        assembly_quad_s=1.0 * (1 + TOL["geometry-assembly-pct"]) * 1.01
    ))
    assert regressions_between(old, new) == [
        ("geometry_assembly_quad_s", "geometry")
    ]
    # the parity bound is a hard pin, not a relative drift band
    new = make_round(geometry=_geometry_row(max_frac_err=1e-11))
    assert regressions_between(old, new) == [
        ("geometry_max_frac_err", "geometry")
    ]


def test_geometry_only_in_one_round_is_noted_not_failed():
    old = make_round()
    new = make_round(geometry=_geometry_row())
    regs, notes = bc.compare(old, new, TOL)
    assert not regs
    assert any("geometry" in n for n in notes)


def _grad_row(**overrides) -> dict:
    row = {
        "grid": [400, 600], "lanes": 4, "n_requests": 8,
        "grad_solves_per_sec": 10.0, "wall_s": 0.8,
        "rows": [{"grid": [400, 600], "primal_iters": 546,
                  "adjoint_iters": 540, "ratio": 0.989}],
        "valid": True,
    }
    row.update(overrides)
    return row


def test_grad_throughput_drop_is_a_regression():
    old = make_round(grad=_grad_row())
    new = make_round(grad=_grad_row(
        grad_solves_per_sec=10.0 * (1 - TOL["grad-pct"]) * 0.99
    ))
    assert regressions_between(old, new) == [
        ("grad_solves_per_sec", "grad")
    ]
    new = make_round(grad=_grad_row(
        grad_solves_per_sec=10.0 * (1 - TOL["grad-pct"]) * 1.01
    ))
    assert regressions_between(old, new) == []


def test_grad_adjoint_ratio_growth_is_a_regression():
    old = make_round(grad=_grad_row())
    grown = [{"grid": [400, 600], "primal_iters": 546,
              "adjoint_iters": 1100, "ratio": 2.015}]
    new = make_round(grad=_grad_row(rows=grown))
    assert regressions_between(old, new) == [
        ("grad_adjoint_ratio", "grad 400x600")
    ]
    near = [{"grid": [400, 600], "primal_iters": 546,
             "adjoint_iters": 560, "ratio": 1.026}]
    assert regressions_between(old, make_round(grad=_grad_row(rows=near))) == []


def test_grad_only_in_one_round_is_noted_not_failed():
    old = make_round()
    new = make_round(grad=_grad_row())
    regs, notes = bc.compare(old, new, TOL)
    assert not regs
    assert any("grad" in n for n in notes)


# ------------------------------------------------ the bandwidth key's gates


def _bw(t_pipe_bf16=0.6, gbps_bf16=400.0, ratio=0.5, parity=True):
    return {
        "available": True,
        "grid": [2400, 3200],
        "byte_ratio_gate": 0.6,
        "l2_band": 1.10,
        "cells": [
            {"engine": "pipelined", "storage": "f32", "t_solver_s": 1.0,
             "hbm_gbps": 300.0, "l2_err": 1e-4},
            {"engine": "pipelined", "storage": "bf16",
             "t_solver_s": t_pipe_bf16, "hbm_gbps": gbps_bf16,
             "l2_err": 1.05e-4, "byte_ratio_vs_f32": ratio,
             "l2_parity": parity},
        ],
        "ok": True,
    }


def test_bandwidth_identical_rounds_pass_and_absence_is_noted():
    old = make_round(bandwidth=_bw())
    assert regressions_between(old, old) == []
    regs, notes = bc.compare(make_round(), make_round(bandwidth=_bw()), TOL)
    assert not [r for r in regs if "bandwidth_t" in r.metric]
    assert any("bandwidth" in n for n in notes)


def test_bandwidth_cell_slowdown_and_gbps_drop_are_regressions():
    old = make_round(bandwidth=_bw())
    slow = make_round(bandwidth=_bw(t_pipe_bf16=0.9))
    regs = regressions_between(old, slow)
    assert ("bandwidth_t_solver_s", "bandwidth pipelined/bf16") in regs
    dropped = make_round(bandwidth=_bw(gbps_bf16=200.0))
    regs = regressions_between(old, dropped)
    assert ("bandwidth_hbm_gbps", "bandwidth pipelined/bf16") in regs


def test_bandwidth_hard_pins_fire_on_the_new_round_alone():
    old = make_round(bandwidth=_bw())
    fat = make_round(bandwidth=_bw(ratio=0.75))
    regs = regressions_between(old, fat)
    assert ("bandwidth_byte_ratio", "bandwidth pipelined/bf16") in regs
    off = make_round(bandwidth=_bw(parity=False))
    regs = regressions_between(old, off)
    assert ("bandwidth_l2_parity", "bandwidth pipelined/bf16") in regs


# ------------------------------------------------------- fmg / autotune


def _fmg_round(t1=0.05, t2=0.4, wu=True, headline_speedup=1.4):
    return make_round(fmg={
        "work_units_constant": wu,
        "rows": [
            {"grid": [400, 600], "t_solver_s": t1, "iters": 3,
             "work_units_per_point": 60.0, "headline": False},
            {"grid": [4096, 4096], "t_solver_s": t2, "iters": 3,
             "work_units_per_point": 62.0, "headline": True,
             "speedup_vs_mg": headline_speedup},
        ],
    })


def test_fmg_slowdown_is_a_regression_per_grid():
    old, new = _fmg_round(), _fmg_round(t1=0.05 * 1.5)
    assert ("fmg_t_solver_s", "fmg 400x600") in regressions_between(old, new)
    assert regressions_between(old, _fmg_round(t1=0.05 * 1.1)) == []


def test_fmg_hard_pins_fire_on_the_new_round_alone():
    old = _fmg_round()
    regs = regressions_between(old, _fmg_round(wu=False))
    assert ("fmg_work_units", "fmg") in regs
    regs = regressions_between(old, _fmg_round(headline_speedup=0.8))
    assert ("fmg_headline_speedup", "fmg 4096x4096") in regs


def test_fmg_only_in_one_round_is_noted_not_failed():
    old, new = make_round(), _fmg_round()
    regs, notes = bc.compare(old, new, TOL)
    assert not regs
    assert any("fmg" in n for n in notes)


def _autotune_round(t=0.02, loses=False, roundtrip=True):
    return make_round(autotune={
        "rows": [
            {"grid": [400, 600], "tuned_engine": "fmg",
             "static_engine": "xl", "tuned_t_s": t, "static_t_s": 0.05,
             "tuned_loses": loses, "roundtrip_ok": roundtrip},
        ],
    })


def test_autotune_tuned_slowdown_is_a_regression():
    old, new = _autotune_round(), _autotune_round(t=0.02 * 1.5)
    assert ("autotune_tuned_t_s", "autotune 400x600") in \
        regressions_between(old, new)
    assert regressions_between(old, _autotune_round(t=0.02 * 1.1)) == []


def test_autotune_never_loses_pin_fires_on_the_new_round_alone():
    # a new round whose tuned config lost to the static default fails
    # even against an old round that also carried the key cleanly
    regs = regressions_between(_autotune_round(), _autotune_round(loses=True))
    assert ("autotune_tuned_loses", "autotune 400x600") in regs
    regs = regressions_between(
        _autotune_round(), _autotune_round(roundtrip=False)
    )
    assert ("autotune_roundtrip", "autotune 400x600") in regs


def test_autotune_only_in_one_round_is_noted_not_failed():
    old, new = make_round(), _autotune_round()
    regs, notes = bc.compare(old, new, TOL)
    assert not regs
    assert any("autotune" in n for n in notes)


# ------------------------------------------------ engine-contract stamping


def test_contracts_violated_new_round_is_a_regression():
    old = make_round(contracts={"hash": "a" * 64, "clean": True})
    new = make_round(contracts={"hash": "a" * 64, "clean": False})
    assert ("contracts_clean", "contracts") in regressions_between(old, new)


def test_contracts_hash_change_is_noted_not_failed():
    old = make_round(contracts={"hash": "a" * 64, "clean": True})
    new = make_round(contracts={"hash": "b" * 64, "clean": True})
    regs, notes = bc.compare(old, new, TOL)
    assert not regs
    assert any("contracts" in n and "hash changed" in n for n in notes)


def test_contracts_identical_state_is_silent():
    old = make_round(contracts={"hash": "a" * 64, "clean": True})
    new = make_round(contracts={"hash": "a" * 64, "clean": True})
    regs, notes = bc.compare(old, new, TOL)
    assert not regs
    assert not any("contracts" in n for n in notes)


def test_contracts_only_in_one_round_is_noted_not_failed():
    old = make_round()
    new = make_round(contracts={"hash": "a" * 64, "clean": True})
    regs, notes = bc.compare(old, new, TOL)
    assert not regs
    assert any("contracts: only in one round" in n for n in notes)


def test_stamp_embeds_contract_state(tmp_path, monkeypatch, capsys):
    from poisson_ellipse_tpu.analysis import matrix

    monkeypatch.setattr(
        matrix, "run_matrix", lambda *a, **k: {"clean": True, "cells": []}
    )
    p = tmp_path / "BENCH_r01.json"
    p.write_text(json.dumps({"parsed": make_round()}))
    assert bc.stamp(str(p)) == 0
    out = capsys.readouterr().out
    assert "contracts clean" in out
    stamped = json.loads(p.read_text())["parsed"]["contracts"]
    assert stamped["clean"] is True and len(stamped["hash"]) == 64
    # the stamped round now compares against an unstamped one as a note
    regs, notes = bc.compare(
        make_round(), json.loads(p.read_text())["parsed"], TOL
    )
    assert not regs
    assert any("contracts: only in one round" in n for n in notes)


def test_stamp_not_clean_exits_1_but_still_writes(tmp_path, monkeypatch):
    from poisson_ellipse_tpu.analysis import matrix

    monkeypatch.setattr(
        matrix, "run_matrix", lambda *a, **k: {"clean": False, "cells": []}
    )
    p = tmp_path / "BENCH_r02.json"
    p.write_text(json.dumps(make_round()))  # raw bench line, no "parsed"
    assert bc.stamp(str(p)) == 1
    assert json.loads(p.read_text())["contracts"]["clean"] is False


def test_stamp_unreadable_input_exits_2(tmp_path, capsys):
    bad = tmp_path / "BENCH_r03.json"
    bad.write_text("{not json")
    assert bc.main(["--stamp", str(bad)]) == 2
    assert bc.main(["--stamp"]) == 2  # missing operand is usage, not crash


# ------------------------------------------------------ recycle stream


def _recycle_round(cut=4.1, sps_warm=3.15, gap=0.05, converged=True):
    return make_round(recycle={
        "grid": [128, 128], "stream": 5, "ring_cap": 64, "basis_rank": 8,
        "capture_iters": 150, "iters_cold_mean": 149.6,
        "iters_warm_mean": 36.2, "iter_cut": cut, "l2_rel_gap_max": gap,
        "solves_per_s_cold": 2.77, "solves_per_s_warm": sps_warm,
        "converged": converged, "valid": True,
    })


def test_recycle_iter_cut_and_warm_throughput_are_gated():
    old = _recycle_round()
    limit = TOL["recycle-pct"]
    new = _recycle_round(cut=4.1 * (1 - limit) * 0.99)
    assert ("recycle_iter_cut", "recycle 128x128") in \
        regressions_between(old, new)
    new = _recycle_round(sps_warm=3.15 * (1 - limit) * 0.99)
    assert ("recycle_solves_per_s_warm", "recycle 128x128") in \
        regressions_between(old, new)
    # within tolerance (and identical rounds): silent
    assert regressions_between(old, _recycle_round(cut=4.1 * 0.9)) == []
    assert regressions_between(old, old) == []


def test_recycle_hard_pins_fire_on_the_new_round_alone():
    # the acceptance pins hold even against an old round that also
    # carried the key cleanly: >= 2x cut, <= 10% analytic-l2 gap,
    # every solve in the stream converged
    regs = regressions_between(_recycle_round(), _recycle_round(cut=1.7))
    assert ("recycle_cut_pin", "recycle 128x128") in regs
    regs = regressions_between(_recycle_round(), _recycle_round(gap=0.2))
    assert ("recycle_l2_gap", "recycle 128x128") in regs
    regs = regressions_between(
        _recycle_round(), _recycle_round(converged=False)
    )
    assert ("recycle_converged", "recycle 128x128") in regs
    # ...and fire on a brand-new key with no old counterpart at all
    regs = regressions_between(make_round(), _recycle_round(cut=1.7))
    assert ("recycle_cut_pin", "recycle 128x128") in regs


def test_recycle_only_in_one_round_is_noted_not_failed():
    regs, notes = bc.compare(make_round(), _recycle_round(), TOL)
    assert not regs
    assert any("recycle" in n for n in notes)
