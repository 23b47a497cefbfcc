"""The engine × axis contract matrix: enumerate, check, report.

``ENGINE_CAPS`` declares each engine's structural contract
(``analysis.contracts``); this module sweeps the full cross-product —
engine × {single, sharded, batched, guarded, abft, storage, history} —
on a tiny grid, entirely by abstract tracing (no solver compiles), and
emits a deterministic machine-readable report: JSON, SARIF, and a
classified exit code mirroring tpulint's (0 clean, 1 violations,
2 a cell errored out).

Cells are suppressible with a reason, tpulint-style, via
``[tool.engine_contracts] suppress`` in ``pyproject.toml``::

    suppress = ["pipelined:sharded:collective-cadence: known drift, #123"]

A suppressed failing cell reads as suppressed (exit stays 0); a
suppression that no longer matches a failing cell is reported unused —
the same accept-then-ratchet hygiene the linter applies to its
``disable`` comments.
"""

from __future__ import annotations

import hashlib
import json
import re
from typing import Optional

from poisson_ellipse_tpu.analysis import contracts

TOOL_NAME = "engine-contracts"
REPORT_VERSION = 1

# axis -> the contract kinds that can run there (applicability per
# engine is the capability row's business — contracts.contract_applies)
AXIS_CONTRACTS = {
    "single": ("single-collective-free",),
    "sharded": ("collective-cadence", "fcycle-budget"),
    "batched": ("batched-cadence",),
    "guarded": ("guard-overhead",),
    "abft": ("abft-identity",),
    "storage": ("storage-identity", "storage-narrow"),
    "history": ("history-free", "history-resident"),
    "fleet": ("fleet-chaos",),
    "recycle": ("recycle-deflation",),
}
AXES = tuple(AXIS_CONTRACTS)

_SUPPRESS_RE = re.compile(
    r"^\s*([^:\s]+)\s*:\s*([^:\s]+)\s*:\s*([^:\s]+)\s*(?::\s*(.*))?$"
)


def cell_id(engine: str, axis: str, kind: str) -> str:
    return f"{engine}:{axis}:{kind}"


def enumerate_cells(
    engines: Optional[tuple[str, ...]] = None,
    axes: Optional[tuple[str, ...]] = None,
) -> list[tuple[str, str, str]]:
    """Every applicable (engine, axis, kind) cell, sorted — the
    deterministic sweep order every report uses."""
    from poisson_ellipse_tpu.solver.engine import ENGINE_CAPS

    engines = tuple(engines) if engines else tuple(ENGINE_CAPS)
    axes = tuple(axes) if axes else AXES
    cells = []
    for engine in engines:
        for axis in axes:
            for kind in AXIS_CONTRACTS[axis]:
                try:
                    applies = contracts.contract_applies(kind, engine)
                except ValueError:
                    # missing/malformed metadata: the engine-metadata
                    # check below names it; no per-axis cells to run
                    applies = False
                if applies:
                    cells.append((engine, axis, kind))
    return sorted(cells)


def load_suppressions(root: Optional[str] = None) -> dict[str, str]:
    """``[tool.engine_contracts] suppress`` entries -> {cell id: reason}.

    Reuses the tpulint pyproject reader, so the knob parses identically
    everywhere.
    """
    import os

    from poisson_ellipse_tpu.lint import _read_pyproject

    if root is None:
        root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    pyproject = os.path.join(root, "pyproject.toml")
    if not os.path.exists(pyproject):
        return {}
    table = _read_pyproject(pyproject).get("tool", {}).get(
        "engine_contracts", {}
    )
    out: dict[str, str] = {}
    for entry in table.get("suppress", []):
        m = _SUPPRESS_RE.match(str(entry))
        if not m:
            raise SystemExit(
                f"[tool.engine_contracts] suppress entry {entry!r} is not "
                "'engine:axis:kind: reason'"
            )
        engine, axis, kind, reason = m.groups()
        out[cell_id(engine, axis, kind)] = reason or "(no reason given)"
    return out


def _jsonable(value):
    if isinstance(value, dict):
        return {str(k): _jsonable(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_jsonable(v) for v in value]
    if isinstance(value, (str, int, float, bool)) or value is None:
        return value
    return str(value)


def run_matrix(
    engines: Optional[tuple[str, ...]] = None,
    axes: Optional[tuple[str, ...]] = None,
    *,
    problem=None,
    mesh_shape: tuple[int, int] = (1, 2),
    suppressions: Optional[dict[str, str]] = None,
) -> dict:
    """Sweep the matrix; return the deterministic report dict.

    ``suppressions`` defaults to the pyproject table; pass ``{}`` to run
    unsuppressed (the pytest gate does, so a suppression can never hide
    a regression from tier-1 silently).
    """
    if suppressions is None:
        suppressions = load_suppressions()
    cells = enumerate_cells(engines, axes)
    rows: list[dict] = []
    n_pass = n_fail = n_suppressed = n_error = 0
    violations: list[str] = []
    used: set[str] = set()

    # the registration gate runs once, ahead of the per-cell sweep
    meta = contracts.check_engine_metadata()
    meta_row = {
        "engine": "*",
        "axis": "registry",
        "kind": "engine-metadata",
        "status": "fail" if meta else "pass",
        "expected": {"declared": True},
        "actual": {"missing": [v.engine for v in meta]},
        "messages": [v.message for v in meta],
    }
    if meta:
        n_fail += 1
        violations.extend(v.render() for v in meta)
    else:
        n_pass += 1
    rows.append(meta_row)

    for engine, axis, kind in cells:
        cid = cell_id(engine, axis, kind)
        try:
            result = contracts.check_contract(
                kind, engine, problem=problem, mesh_shape=mesh_shape
            )
            row = {
                "engine": engine,
                "axis": axis,
                "kind": kind,
                "status": result.status,
                "expected": _jsonable(result.expected),
                "actual": _jsonable(result.actual),
                "messages": [v.message for v in result.violations],
            }
        # a crashed cell is CLASSIFIED, not swallowed: status "error"
        # carries the exception name in messages and trumps the exit
        # code (2) — the deliberate-swallow shape TPU009 fences allows
        # tpulint: disable=TPU009
        except Exception as e:  # a cell that cannot run is exit 2, not 0
            row = {
                "engine": engine,
                "axis": axis,
                "kind": kind,
                "status": "error",
                "expected": None,
                "actual": None,
                "messages": [f"{type(e).__name__}: {e}"],
            }
        if row["status"] == "fail" and cid in suppressions:
            row["status"] = "suppressed"
            row["suppressed_reason"] = suppressions[cid]
            used.add(cid)
            n_suppressed += 1
        elif row["status"] == "fail":
            n_fail += 1
            violations.extend(
                f"{cid}: {m}" for m in row["messages"]
            )
        elif row["status"] == "error":
            n_error += 1
            violations.extend(f"{cid}: {m}" for m in row["messages"])
        else:
            n_pass += 1
        rows.append(row)

    unused = sorted(set(suppressions) - used)
    report = {
        "tool": TOOL_NAME,
        "version": REPORT_VERSION,
        "grid": (
            [problem.M, problem.N] if problem is not None else [16, 16]
        ),
        "mesh": list(mesh_shape),
        "cells": rows,
        "summary": {
            "checked": len(rows),
            "pass": n_pass,
            "fail": n_fail,
            "error": n_error,
            "suppressed": n_suppressed,
        },
        "violations": violations,
        "unused_suppressions": unused,
        "clean": n_fail == 0 and n_error == 0,
    }
    return report


def report_hash(report: dict) -> str:
    """The canonical-JSON sha256 of a matrix report — what a bench round
    embeds so two perf numbers are only compared under the same (clean)
    contract state."""
    return hashlib.sha256(
        json.dumps(report, sort_keys=True, separators=(",", ":")).encode()
    ).hexdigest()


def exit_code(report: dict) -> int:
    """0 clean (incl. suppressed), 1 contract violations, 2 a cell
    errored (unusable sweep trumps findings — mirror tpulint)."""
    if report["summary"]["error"]:
        return 2
    return 1 if report["summary"]["fail"] else 0


def render_report(report: dict) -> str:
    """Human-readable matrix summary: one line per non-pass cell plus
    the tally (the CLI's default text form)."""
    lines = [
        f"{TOOL_NAME}: grid {report['grid'][0]}x{report['grid'][1]}, "
        f"mesh {report['mesh'][0]}x{report['mesh'][1]}, "
        f"{report['summary']['checked']} contract cells"
    ]
    for row in report["cells"]:
        if row["status"] == "pass":
            continue
        cid = cell_id(row["engine"], row["axis"], row["kind"])
        if row["status"] == "suppressed":
            lines.append(
                f"  suppressed {cid}: {row['suppressed_reason']}"
            )
        else:
            for msg in row["messages"]:
                lines.append(f"  {row['status'].upper()} {cid}: {msg}")
    for cid in report["unused_suppressions"]:
        lines.append(f"  unused suppression: {cid}")
    s = report["summary"]
    lines.append(
        f"  {s['pass']} pass, {s['fail']} fail, {s['error']} error, "
        f"{s['suppressed']} suppressed — "
        + ("clean" if report["clean"] else "NOT clean")
    )
    return "\n".join(lines)


def report_to_sarif(report: dict) -> dict:
    """Matrix report -> SARIF (the shared writer; one result per
    non-pass cell, ruleId = the contract kind)."""
    from poisson_ellipse_tpu.analysis.sarif import sarif_report, sarif_result

    results = []
    for row in report["cells"]:
        if row["status"] == "pass":
            continue
        cid = cell_id(row["engine"], row["axis"], row["kind"])
        level = {
            "fail": "error", "error": "error", "suppressed": "note"
        }[row["status"]]
        for msg in row["messages"] or [row.get("suppressed_reason", "")]:
            results.append(
                sarif_result(row["kind"], f"{cid}: {msg}", level=level)
            )
    return sarif_report(
        TOOL_NAME,
        results,
        rules=dict(contracts.CONTRACT_KINDS),
    )
