"""tools/diag_precision.py: the l2 split into discretisation and
algebraic error, at a CPU-sized grid (the Pallas engines interpret)."""

from __future__ import annotations

import importlib.util
import json
import os

_TOOL = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools",
    "diag_precision.py",
)
_spec = importlib.util.spec_from_file_location("diag_precision", _TOOL)
dp = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dp)


def _rows(capsys):
    return {r["engine"]: r for r in map(json.loads,
                                        capsys.readouterr().out.splitlines())}


def test_f32_engines_sit_at_the_f64_algebraic_error(tmp_path, capsys):
    ref = str(tmp_path / "ref.npy")
    engines = ("xla", "fused", "resident", "sharded/fused")
    assert dp.main(["30", "45", "--engines", ",".join(engines),
                    "--ref", ref]) == 0
    rows = _rows(capsys)
    assert rows["xla-f64-ref"]["alg"] == 0.0
    same = rows["xla-f64"]
    for engine in engines:
        row = rows[engine]
        assert row["converged"] and row["iters"] == same["iters"]
        # f32 rounding moves this tiny grid's stop error by ~2.5%
        assert abs(row["alg"] - same["alg"]) <= 0.05 * same["alg"]
    # the saved reference is reused: no f64 solve the second time
    assert dp.main(["30", "45", "--engines", "fused", "--ref", ref]) == 0
    again = _rows(capsys)
    assert set(again) == {"fused"}
    assert again["fused"]["alg"] == rows["fused"]["alg"]
