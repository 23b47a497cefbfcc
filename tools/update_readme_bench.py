"""Regenerate README.md's measured-performance blocks from a BENCH artifact.

The README's headline speedup and measured table are GENERATED — not
hand-edited — from the machine-readable JSON line `bench.py` prints
(which the round driver archives as `BENCH_r{N}.json`). One number, one
source:

    python bench.py > /tmp/bench.json   # or use the driver's BENCH_r*.json
    python tools/update_readme_bench.py [/tmp/bench.json]

With no argument the newest `BENCH_r*.json` in the repo root is used —
"newest" by parsed round number (mtime breaks ties), not filename sort,
so r100 beats r99 — and the chosen file is echoed. Both formats are
accepted: the driver artifact (``{"parsed": {...}}``) and bench.py's raw
stdout line. Every number in the generated text (headline grid,
iteration count, reference baseline, chip name) is derived from the
artifact's own rows; nothing is hardcoded here. The tool rewrites the
text between the ``<!-- bench:... -->`` marker pairs in README.md and
leaves everything else untouched; artifacts missing any of the
machine-readable keys are rejected with a pointer to re-run the bench.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
README = os.path.join(ROOT, "README.md")

# every key table_block/headline_block reads; a partial artifact gets the
# curated error below, never a bare KeyError
REQUIRED_KEYS = ("value", "vs_baseline", "grids", "config2", "eps_sweep", "f64")

# chip the committed budgets/artifacts were measured on: the honest
# fallback for artifacts that predate bench.py's "device" field
MEASURED_DEVICE = "TPU v5e"

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def _round_key(path: str) -> tuple[int, float]:
    m = _ROUND_RE.search(os.path.basename(path))
    n = int(m.group(1)) if m else -1
    return n, os.path.getmtime(path)


def newest_artifact(root: str = ROOT) -> str:
    """The highest-round (mtime tie-broken) BENCH_r*.json under root."""
    rounds = glob.glob(os.path.join(root, "BENCH_r*.json"))
    if not rounds:
        raise SystemExit(f"no BENCH_r*.json found in {root}; pass a path")
    picked = max(rounds, key=_round_key)
    print(
        f"using {os.path.basename(picked)} "
        f"(round {_round_key(picked)[0]}, newest of {len(rounds)} artifacts)"
    )
    return picked


def load_artifact(path: str | None, root: str = ROOT) -> tuple[dict, str]:
    """(parsed bench record, source label)."""
    if path is None:
        path = newest_artifact(root)
    with open(path) as f:
        data = json.load(f)
    rec = data.get("parsed", data)  # driver artifact vs raw bench line
    # empty rows are as unusable as absent ones (an aborted driver run
    # can serialize "grids": []) — same curated error, not an IndexError
    missing = [
        k
        for k in REQUIRED_KEYS
        if k not in rec or (isinstance(rec[k], list) and not rec[k])
    ]
    if missing:
        raise SystemExit(
            f"{path} predates the machine-readable bench rows "
            f"(missing: {', '.join(missing)}); re-run "
            "`python bench.py > out.json` and pass that file"
        )
    return rec, os.path.basename(path)


def fmt_t(t: float) -> str:
    return f"{t:.4f} s" if t < 1 else f"{t:.2f} s"


def headline_row(rec: dict) -> dict:
    """The grids row the headline `value` was measured on.

    Matched by the timing itself (both come from the same bench run);
    falls back to the first row carrying a reference baseline, so a
    hand-rounded artifact still resolves to the comparable row.
    """
    for row in rec["grids"]:
        if row["t_solver_s"] == rec["value"]:
            return row
    for row in rec["grids"]:
        if row.get("ref_p100_s"):
            return row
    return rec["grids"][0]


def _delta_of(rec: dict) -> str | None:
    m = re.search(r"to\s+(?:δ=)?([0-9.eE+-]+)\)", rec.get("metric", ""))
    return m.group(1) if m else None


def headline_block(rec: dict, src: str) -> str:
    row = headline_row(rec)
    M, N = row["grid"]
    delta = _delta_of(rec)
    iters = f"{row['iters']} iterations" + (f" to δ={delta}" if delta else "")
    device = rec.get("device", MEASURED_DEVICE)
    ref = row.get("ref_p100_s")
    vs = (
        f"**{rec['vs_baseline']:g}×** the reference's stage4 single-P100 "
        f"{ref} s" if ref else f"**{rec['vs_baseline']:g}×** the "
        "reference baseline"
    )
    return (
        f"Measured headline: **{fmt_t(rec['value'])}** for {M}×{N} "
        f"({iters}) on one {device} chip — {vs}. "
        f"(Generated from `{src}` by "
        f"`tools/update_readme_bench.py` — the same artifact as the "
        f"table below.)"
    )


def table_block(rec: dict, src: str) -> str:
    lines = [
        "`T_solver`, median, fenced, marginal-cost protocol (host↔device "
        "RTT cancelled); reference numbers from `BASELINE.md` (P100). "
        f"Generated from `{src}` by `tools/update_readme_bench.py`:",
        "",
        "| Grid | iters | engine | this framework | stage4 1×P100 | speedup |",
        "|---|---|---|---|---|---|",
    ]
    bold_grid = headline_row(rec)["grid"]
    for row in rec["grids"]:
        M, N = row["grid"]
        ref = f"{row['ref_p100_s']} s" if row.get("ref_p100_s") else "—"
        vs = f"**{row['vs_p100']:g}×**" if row.get("vs_p100") else "—"
        bold = "**" if row["grid"] == bold_grid else ""
        lines.append(
            f"| {M}×{N} | {row['iters']} | {row['engine']} | "
            f"{bold}{fmt_t(row['t_solver_s'])}{bold} | {ref} | {vs} |"
        )
    for key, note in (("config2", "BASELINE config 2"),
                      ("north_star", "north-star config"),
                      ("config4_1chip", "config-4 grid on ONE chip")):
        row = rec.get(key)
        if row is None:
            continue
        M, N = row["grid"]
        lines.append(
            f"| {M}×{N} | {row['iters']} | {row['engine']} | "
            f"{fmt_t(row['t_solver_s'])} | — ({note}) | — |"
        )
    pipe = rec.get("pipelined")  # absent in pre-pipelined artifacts
    if pipe is not None:
        M, N = pipe["grid"]
        vs = (
            f"{pipe['vs_xla']:g}× vs xla ({fmt_t(pipe['t_xla_s'])})"
            if pipe.get("vs_xla")
            else "—"
        )
        lines.append(
            f"| {M}×{N} | {pipe['iters']} | pipelined | "
            f"{fmt_t(pipe['t_solver_s'])} | — (1 fused reduction/iter) | "
            f"{vs} |"
        )
    f64 = rec["f64"]
    eps = rec["eps_sweep"]
    eps_iters = sorted({r["iters"] for r in eps})
    eps_span = (
        f"{eps_iters[0]}" if len(eps_iters) == 1
        else f"{eps_iters[0]}–{eps_iters[-1]}"
    )
    M, N = rec["config2"]["grid"]
    lines += [
        "",
        f"The f64 fidelity row (emulated f64 on TPU): "
        f"{f64['grid'][0]}×{f64['grid'][1]} converges in exactly the "
        f"published {f64['iters']} iterations at {fmt_t(f64['t_solver_s'])} "
        "— still faster than the reference's single-P100 f32 time. The "
        f"ε-stiffness sweep at {M}×{N} (BASELINE config 5) is flat: "
        f"{eps_span} iterations across ε ∈ {{1e-2 … 1e-6}} — the Jacobi "
        "preconditioner absorbs the 1/ε stiffness, so the solver does "
        "not degrade as the fictitious domain hardens.",
    ]
    obs = observability_lines(rec)
    if obs:
        lines += [""] + obs
    precond = precond_lines(rec)
    if precond:
        lines += [""] + precond
    spectrum = spectrum_lines(rec)
    if spectrum:
        lines += [""] + spectrum
    serving = serving_lines(rec)
    if serving:
        lines += [""] + serving
    fleet = fleet_lines(rec)
    if fleet:
        lines += [""] + fleet
    geometry = geometry_lines(rec)
    if geometry:
        lines += [""] + geometry
    grad = grad_lines(rec)
    if grad:
        lines += [""] + grad
    bandwidth = bandwidth_lines(rec)
    if bandwidth:
        lines += [""] + bandwidth
    fmg = fmg_lines(rec)
    if fmg:
        lines += [""] + fmg
    autotune = autotune_lines(rec)
    if autotune:
        lines += [""] + autotune
    recycle = recycle_lines(rec)
    if recycle:
        lines += [""] + recycle
    return "\n".join(lines)


def fmg_lines(rec: dict) -> list[str]:
    """Markdown for the artifact's ``fmg`` key (full multigrid as the
    solver, emitted since mg/fmg landed): T_solver + work units per
    grid point vs mg-pcg per grid. Pre-FMG artifacts lack the key and
    render without the table; a failed row (no t_solver_s) is skipped,
    not a crash."""
    fmg = rec.get("fmg")
    if not isinstance(fmg, dict):
        return []
    rows = [
        r for r in (fmg.get("rows") or [])
        if r.get("t_solver_s") and r.get("grid")
    ]
    if not rows:
        return []
    wu_pin = (
        "work units per grid point constant across grids (the O(N) pin)"
        if fmg.get("work_units_constant")
        else "WORK-UNIT PIN BROKEN"
    )
    lines = [
        "Full multigrid as the solver (`mg/fmg`: one O(N) F-cycle + a "
        "VERIFIED mg-pcg handoff against δ — accuracy measured, never "
        f"assumed; {wu_pin}; `fmg-pct` regression-gated by "
        "`tools/bench_compare.py`):",
        "",
        "| Grid | T_solver | handoff iters | work units/pt | vs mg-pcg |",
        "|---|---|---|---|---|",
    ]
    for r in rows:
        M, N = r["grid"]
        vs = (
            f"**{r['speedup_vs_mg']:g}×**"
            if r.get("speedup_vs_mg") else "—"
        )
        head = " (headline)" if r.get("headline") else ""
        lines.append(
            f"| {M}×{N}{head} | {fmt_t(r['t_solver_s'])} | "
            f"{r.get('iters', '—')} | "
            f"{r.get('work_units_per_point', '—')} | {vs} |"
        )
    return lines


def autotune_lines(rec: dict) -> list[str]:
    """Markdown for the artifact's ``autotune`` key (the closed-loop
    tuner, emitted since runtime/autotune landed): tuned-vs-static wall
    clock per shape. Pre-tuner artifacts lack the key and render
    without the table; a failed row (no tuned_t_s) is skipped."""
    at = rec.get("autotune")
    if not isinstance(at, dict):
        return []
    rows = [
        r for r in (at.get("rows") or [])
        if r.get("tuned_t_s") and r.get("grid")
    ]
    if not rows:
        return []
    lines = [
        "Telemetry-driven autotuning (`runtime.autotune`: per-shape "
        "configs scored from measured κ/Ritz predictions and GB/s, "
        "persisted in the checkout; a tuned config that loses to "
        "the static default fails the `autotune-pct` gate):",
        "",
        "| Grid | tuned engine | tuned | static default | verdict |",
        "|---|---|---|---|---|",
    ]
    for r in rows:
        M, N = r["grid"]
        verdict = (
            "TUNED LOSES (gate fails)" if r.get("tuned_loses")
            else ("static stands" if r.get("tuned_engine")
                  == r.get("static_engine") else "tuned wins")
        )
        lines.append(
            f"| {M}×{N} | {r.get('tuned_engine', '—')} | "
            f"{fmt_t(r['tuned_t_s'])} | "
            f"{fmt_t(r['static_t_s'])} ({r.get('static_engine', '?')}) | "
            f"{verdict} |"
        )
    return lines


def recycle_lines(rec: dict) -> list[str]:
    """Markdown for the artifact's ``recycle`` key (Krylov recycling on
    the correlated stream, emitted since solver/recycle landed):
    cold-vs-warm iterations, the measured cut against the ≥2× pin, and
    solves/sec both ways. Pre-recycling artifacts lack the key and
    render without the block; a failed row (no iter_cut — the capture
    solve or harvest declined) is skipped, not a crash."""
    rc = rec.get("recycle")
    if not isinstance(rc, dict):
        return []
    if not rc.get("grid") or rc.get("iter_cut") is None:
        return []
    M, N = rc["grid"]
    verdict = (
        f"**{rc['iter_cut']:g}× cut**" if rc.get("valid")
        else f"{rc['iter_cut']:g}× (PIN BROKEN)"
    )
    gap = rc.get("l2_rel_gap_max")
    return [
        "Krylov recycling (`solver.recycle` + `runtime.solvecache`: one "
        "ring-carrying capture solve harvests a "
        f"{rc.get('basis_rank', '?')}-mode deflation basis, then each "
        "correlated request warm-starts from the previous solution "
        "deflated against its true residual; `recycle-pct` gated with "
        "the ≥2× cut hard-pinned by `tools/bench_compare.py`):",
        "",
        "| Grid | stream | iters cold → warm | cut | solves/s cold → "
        "warm | analytic-l2 gap |",
        "|---|---|---|---|---|---|",
        f"| {M}×{N} | {rc.get('stream', '—')} related requests | "
        f"{rc.get('iters_cold_mean', '—')} → "
        f"{rc.get('iters_warm_mean', '—')} | {verdict} | "
        f"{rc.get('solves_per_s_cold', '—')} → "
        f"{rc.get('solves_per_s_warm', '—')} | "
        + (f"{gap:.1%} |" if gap is not None else "— |"),
    ]


def bandwidth_lines(rec: dict) -> list[str]:
    """Markdown for the artifact's ``bandwidth`` key ({f32, bf16-
    storage} × {pipelined, sstep} at the HBM-bound grid, emitted since
    the precision/s-step axes landed). Pre-bandwidth artifacts lack the
    key and render without the table; a failed study
    (``available: false``) or empty cell list renders nothing — absence
    and failure are supported inputs, not errors."""
    bw = rec.get("bandwidth")
    if not isinstance(bw, dict) or not bw.get("available"):
        return []
    cells = [c for c in (bw.get("cells") or []) if c.get("t_solver_s")]
    if not cells:
        return []
    g = bw.get("grid", ["?", "?"])
    lines = [
        f"Memory-bandwidth frontier at {g[0]}×{g[1]} (bf16 storage / "
        "f32 compute + s-step CG; the bf16 cells run the guard's "
        "storage-promotion ladder, so their l2 is recovered at full "
        "width — regression-gated by `tools/bench_compare.py` "
        "`bandwidth-pct` with the ≤0.6× byte ratio and l2 parity as "
        "hard pins):",
        "",
        "| engine | storage | T_solver | GB/s | l2 err | bytes/iter vs f32 |",
        "|---|---|---|---|---|---|",
    ]
    for c in cells:
        ratio = c.get("byte_ratio_vs_f32")
        lines.append(
            f"| {c.get('engine', '?')} | {c.get('storage', '?')} | "
            f"{c['t_solver_s']:g} s | {c.get('hbm_gbps', 0):g} | "
            f"{c.get('l2_err', float('nan')):.3e} | "
            + (f"{ratio:.2f}×" if ratio is not None else "—")
            + " |"
        )
    return lines


def grad_lines(rec: dict) -> list[str]:
    """Prose for the artifact's ``grad`` key (differentiable serving,
    emitted since diff/ landed): grad-solves/sec through the scheduler
    plus the adjoint-vs-primal iteration ratio per grid. Pre-diff
    artifacts lack the key and render without the lines; a failed run
    (no grad_solves_per_sec) still renders any iteration-ratio rows it
    carries — absence and partial are supported inputs, not errors."""
    grad = rec.get("grad")
    if not isinstance(grad, dict):
        return []
    lines = []
    gps = grad.get("grad_solves_per_sec")
    if gps is not None and grad.get("grid"):
        g = grad["grid"]
        lines.append(
            f"Differentiable solving (`diff/`, IFT adjoints through the "
            f"converged solve): {gps:g} grad-solves/sec at "
            f"{g[0]}×{g[1]} through the scheduler "
            f"({grad.get('lanes', '?')} candidate lanes, each gradient "
            f"= primal + adjoint lane solve; regression-gated by "
            f"`tools/bench_compare.py` `grad-pct`)."
        )
    rows = [
        r for r in (grad.get("rows") or [])
        if r.get("ratio") is not None and r.get("grid")
    ]
    if rows:
        ratios = ", ".join(
            f"{r['grid'][0]}×{r['grid'][1]} "
            f"{r['adjoint_iters']}/{r['primal_iters']} "
            f"({r['ratio']:g})"
            for r in rows
        )
        lines.append(
            f"Adjoint-vs-primal iterations (same operator, same "
            f"preconditioner): {ratios}."
        )
    return lines


def fleet_lines(rec: dict) -> list[str]:
    """Markdown for the artifact's ``fleet`` key (emitted by bench.py
    since the replicated-serving layer landed): aggregate solves/sec
    per replica count plus the kill-drill handoff p99 and (since the
    survivability layer) the kill→rejoin recovery p99. Pre-fleet
    artifacts lack the key and render without the table; a failed row
    (no solves_per_sec) is skipped, a missing kill drill renders the
    table alone, and a pre-rejoin artifact renders the kill line
    without the recovery clause — absence and partial are supported
    inputs, not errors."""
    fleet = rec.get("fleet")
    if not isinstance(fleet, dict):
        return []
    rows = [
        r for r in (fleet.get("rows") or [])
        if r.get("solves_per_sec") is not None
        and r.get("replicas") is not None
    ]
    if not rows:
        return []
    lines = [
        "Replicated fleet (`fleet/`: lease-fenced scheduler replicas "
        "behind a shape-affinity router, journal-backed handoff on "
        "replica death; aggregate throughput regression-gated by "
        "`tools/bench_compare.py` `fleet-agg-pct`):",
        "",
        "| replicas | lanes each | aggregate solves/sec |",
        "|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['replicas']} | {r.get('lanes', '—')} | "
            f"{r['solves_per_sec']:g} |"
        )
    if fleet.get("handoff_p99_s") is not None:
        adopted = fleet.get("adopted")
        completed = fleet.get("kill_completed")
        lines.append(
            f"Kill drill: replica 0 SIGKILLed mid-stream — "
            f"{fleet.get('handoffs', '?')} journal handoff(s)"
            + (f", {adopted} request(s) adopted" if adopted is not None
               else "")
            + f", handoff latency p99 {fleet['handoff_p99_s'] * 1e3:.2f} ms"
            + (f"; {completed} request(s) completed after the kill"
               if completed is not None else "")
            + "."
        )
    if fleet.get("rejoin_latency_s") is not None:
        lines.append(
            f"Rejoin drill: the victim re-entered as a fresh "
            f"incarnation ({fleet.get('rejoins', '?')} rejoin(s)) — "
            f"kill→first-completed-solve p99 "
            f"{fleet['rejoin_latency_s'] * 1e3:.2f} ms, regression-gated "
            f"by `rejoin-p99-pct`."
        )
    return lines


def geometry_lines(rec: dict) -> list[str]:
    """Prose for the artifact's ``geometry`` key (SDF quadrature
    assembly, emitted by bench.py since the geom layer landed).
    Pre-geometry artifacts lack the key and render without the lines; a
    failed row (no composite t_solver_s) renders the parity half only —
    absence and partial are both supported inputs, not errors."""
    geo = rec.get("geometry")
    if not isinstance(geo, dict):
        return []
    lines: list[str] = []
    if geo.get("max_frac_err") is not None:
        M, N = geo.get("grid", ("?", "?"))
        over = (
            f" (assembly {geo['assembly_overhead_x']:g}× the closed "
            f"form, {fmt_t(geo['assembly_quad_s'])} host-f64 one-time)"
            if geo.get("assembly_overhead_x") else ""
        )
        lines.append(
            f"Geometry (SDF quadrature, `geom.*`): the ellipse through "
            f"the bisection quadrature matches the closed form to "
            f"{geo['max_frac_err']:.1e} relative face fraction at "
            f"{M}×{N}, solving in {geo.get('sdf_ellipse_iters', '?')} "
            f"iterations (closed-form oracle "
            f"{geo.get('oracle_iters', '?')}){over}."
        )
    comp = geo.get("composite") or {}
    if comp.get("t_solver_s") is not None:
        lines.append(
            f"Composite domain ({comp.get('domain', 'composite')}): "
            f"{fmt_t(comp['t_solver_s'])} / {comp.get('iters', '?')} "
            "iterations through the validated arbitrary-SDF path "
            "(admissibility gate + degenerate-cut clamp), discrete "
            "maximum principle held."
        )
    return lines


def precond_lines(rec: dict) -> list[str]:
    """Markdown for the artifact's ``precond`` key (emitted by bench.py
    since the multigrid layer landed): mg-pcg/cheb-pcg vs diag-PCG per
    grid. Pre-multigrid artifacts lack the key and render without the
    table; a failed row (no iters) is skipped, not a crash."""
    rows = [
        r for r in (rec.get("precond") or [])
        if r.get("iters") and r.get("grid") and r.get("engine")
    ]
    if not rows:
        return []
    lines = [
        "Preconditioning (`mg/`: geometric-multigrid V-cycle and "
        "Chebyshev polynomial engines vs the reference's diagonal "
        "preconditioner — the iteration-count wall, killed; "
        "iters/T_solver regression-gated by `tools/bench_compare.py`):",
        "",
        "| Grid | engine | iters | vs diag iters | T_solver | vs diag |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        M, N = r["grid"]
        red = (
            f"**{r['iters_reduction']:g}× fewer**"
            if r.get("iters_reduction") else "—"
        )
        diag_i = f" (diag {r['diag_iters']})" if r.get("diag_iters") else ""
        vs = (
            f"{r['speedup_vs_diag']:g}×"
            if r.get("speedup_vs_diag") else "—"
        )
        lines.append(
            f"| {M}×{N} | {r['engine']} | {r['iters']}{diag_i} | {red} | "
            f"{fmt_t(r['t_solver_s'])} | {vs} |"
        )
    return lines


def spectrum_lines(rec: dict) -> list[str]:
    """Markdown for the artifact's ``spectrum`` key (emitted by bench.py
    since the diagnostics layer landed): the κ-per-grid table with
    predicted-vs-actual iterations. Pre-diagnostics artifacts lack the
    key and render without the table; a failed row (no kappa — the
    trace was unusable) is skipped, not a crash."""
    rows = [
        r for r in (rec.get("spectrum") or [])
        if r.get("kappa") is not None and r.get("grid")
    ]
    if not rows:
        return []
    lines = [
        "Spectral diagnostics (`obs.spectrum`: the Lanczos tridiagonal "
        "hiding in the recorded CG α/β — κ(M⁻¹A) is what the iteration "
        "counts *are*, and the yardstick preconditioner work is measured "
        "against; κ drift between rounds is regression-gated by "
        "`tools/bench_compare.py`):",
        "",
        "| Grid | κ(M⁻¹A) | CG rate | κ-bound iters | predicted | actual |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        M, N = r["grid"]
        rate = f"{r['cg_rate']:.5f}" if r.get("cg_rate") is not None else "—"
        bound = r.get("iters_bound")
        pred = r.get("predicted_iters")
        err = r.get("predicted_err")
        pred_cell = (
            f"{pred} ({err:+.1%})" if pred is not None and err is not None
            else (str(pred) if pred is not None else "—")
        )
        lines.append(
            f"| {M}×{N} | {r['kappa']:.4g} | {rate} | "
            f"{bound if bound is not None else '—'} | {pred_cell} | "
            f"{r.get('iters', '—')} |"
        )
    return lines


def serving_lines(rec: dict) -> list[str]:
    """Markdown for the artifact's serving keys (``throughput`` /
    ``coldstart``, emitted by bench.py since the batch layer landed).
    Pre-batch artifacts lack the keys and render without these lines;
    a failed/partial row (no solves_per_sec) is skipped, not a crash."""
    lines: list[str] = []
    thr = rec.get("throughput")
    rows = [
        r for r in (thr or [])
        if r.get("solves_per_sec") is not None and r.get("grid")
    ]
    if rows:
        lines += [
            "Serving throughput (`--lanes`, batched engine, marginal-cost "
            "protocol — aggregate solves/sec per dispatch):",
            "",
            "| Grid | lanes | T_batch | solves/sec | vs 1 lane |",
            "|---|---|---|---|---|",
        ]
        for r in rows:
            M, N = r["grid"]
            t = (
                fmt_t(r["t_batch_s"]) if r.get("t_batch_s") is not None
                else "—"
            )
            vs = (
                f"**{r['speedup_vs_1lane']:g}×**"
                if r.get("speedup_vs_1lane") else "—"
            )
            lines.append(
                f"| {M}×{N} | {r['lanes']} | {t} | "
                f"{r['solves_per_sec']:g} | {vs} |"
            )
    cold = rec.get("coldstart")
    if cold and cold.get("t_compile_s") is not None:
        M, N = cold["grid"]
        hit = (
            "the re-request was a cache HIT returning the same executable"
            f" ({cold['t_pool_warm_s'] * 1e3:.2f} ms)"
            if cold.get("pool_hit")
            else "the re-request MISSED the warm pool (regression)"
        )
        lines.append(
            f"Cold-start split ({M}×{N}, lanes={cold.get('lanes', '?')}): "
            f"compile {fmt_t(cold['t_compile_s'])} vs solve "
            f"{fmt_t(cold['t_solve_s'])}; with the AOT warm pool "
            f"(`runtime.compile_cache`), {hit}."
        )
    return lines


def observability_lines(rec: dict) -> list[str]:
    """Prose for the artifact's observability keys (``convergence`` /
    ``collectives``, emitted by bench.py since the obs layer landed).
    Pre-obs artifacts simply lack the keys and render without these
    lines — absence is a supported input, not an error."""
    lines: list[str] = []
    conv = rec.get("convergence")
    if conv and conv.get("iters"):
        M, N = conv["grid"]
        span = (
            f", step-norm {conv['diff_first']:.1e} → {conv['diff_final']:.1e}"
            if conv.get("diff_first") is not None
            and conv.get("diff_final") is not None
            else ""
        )
        lines.append(
            f"Convergence telemetry: the {M}×{N} {conv['engine']} solve's "
            f"per-iteration curve is captured on device "
            f"(`solve(..., history=True)`, zero host syncs in the loop) — "
            f"{conv['iters']} iterations traced{span}."
        )
    recov = rec.get("recovery")
    if recov and recov.get("converged") and recov.get("iters") is not None:
        M, N = recov["grid"]
        kinds = ", ".join(recov.get("recoveries", [])) or "none"
        clean = recov.get("clean_iters")
        parity = (
            f" (clean run: {clean} — oracle parity after recovery)"
            if clean is not None else ""
        )
        lines.append(
            f"Resilience drill (`resilience.guard`): a NaN injected into "
            f"the {M}×{N} solve's residual at iteration {recov['at']} is "
            f"detected from the per-chunk health word and recovered via "
            f"{kinds}; the guarded solve reconverges in {recov['iters']} "
            f"iterations{parity} — regression-checked in every artifact."
        )
    coll = rec.get("collectives")
    if coll and coll.get("available"):
        engines = coll.get("engines", {})
        classical = engines.get("xla", {}).get("psum_per_iter")
        pipelined = engines.get("pipelined", {}).get("psum_per_iter")
        if classical is not None and pipelined is not None:
            mesh = coll.get("mesh", ["?", "?"])
            lines.append(
                f"Static collective accounting (`obs.static_cost`, "
                f"{mesh[0]}×{mesh[1]} mesh, jaxpr-derived): classical "
                f"sharded loop **{classical}** psum/iteration, pipelined "
                f"**{pipelined}** — the halved-collectives property, "
                "regression-checked in every bench artifact."
            )
    abft = rec.get("abft")
    if abft and abft.get("available") and abft.get("overhead_pct") is not None:
        M, N = abft.get("grid", ["?", "?"])
        pin = (
            "collective counts identical on/off"
            if abft.get("collectives_identical")
            else "COLLECTIVE-CADENCE PIN BROKEN"
        )
        lines.append(
            f"ABFT silent-corruption checks (`resilience.abft`): "
            f"checks-on overhead **{abft['overhead_pct']:+.2f}%** of "
            f"T_solver at {M}×{N} (gate ≤{abft.get('gate_pct', 2):g}%), "
            f"{pin} at {abft.get('psum_per_iter', '?')} psum/iteration — "
            "every checksum partial rides the existing stacked "
            "convergence psum."
        )
    return lines


def splice(text: str, marker: str, replacement: str) -> str:
    begin, end = f"<!-- bench:{marker} -->", f"<!-- /bench:{marker} -->"
    pattern = re.compile(
        re.escape(begin) + r".*?" + re.escape(end), flags=re.DOTALL
    )
    if not pattern.search(text):
        raise SystemExit(f"README.md is missing the {begin} marker pair")
    return pattern.sub(f"{begin}\n{replacement}\n{end}", text)


def regenerate(readme_path: str, artifact_path: str | None,
               root: str = ROOT) -> str:
    """Rewrite the marker blocks in ``readme_path``; returns a summary."""
    rec, src = load_artifact(artifact_path, root=root)
    with open(readme_path) as f:
        text = f.read()
    text = splice(text, "headline", headline_block(rec, src))
    text = splice(text, "table", table_block(rec, src))
    with open(readme_path, "w") as f:
        f.write(text)
    return (
        f"{os.path.basename(readme_path)} regenerated from {src}: headline "
        f"{rec['value']} s / {rec['vs_baseline']}x, "
        f"{len(rec['grids'])} grid rows"
    )


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    print(regenerate(README, argv[0] if argv else None))
    return 0


if __name__ == "__main__":
    sys.exit(main())
