"""Threshold-based regression gate between two BENCH_r*.json rounds.

The archived pre-chip rounds (`BENCH_r{N}.json`, one JSON line each,
from a bench script since retired for `benchmark/`) are diffed here,
any two rounds metric by metric against named tolerances, exiting
nonzero with the offending metric spelled out — a perf regression
becomes a failing check, not an archaeology project:

    python tools/bench_compare.py OLD.json NEW.json
    python tools/bench_compare.py              # newest two rounds

Compared, where both rounds carry them (absence is skipped and noted —
older artifacts predate newer keys, which must never fail the gate):

- per-grid `t_solver_s` (grids / config2 / north_star / config4_1chip /
  pipelined / f64 rows): slower than `t-solver-pct` is a regression
- per-grid `iters`: growth beyond `iters-abs` (the oracle counts are
  exact, so the default allows only the pipelined-style ±2 reordering)
- per-grid `hbm_gbps` (grids rows, emitted since the diagnostics PR):
  achieved bandwidth dropping more than `gbps-pct`
- `spectrum` rows: `kappa` drifting more than `kappa-pct` in either
  direction (same grid + same operator ⇒ same κ; a drift means the
  trace or the estimator broke, not the hardware)
- `throughput` rows (keyed grid × lanes): `solves_per_sec` dropping
  more than `sps-pct`
- `precond` rows (keyed grid × engine): `iters` growing more than
  `precond-iters-pct` (operator-determined, like κ) or `t_solver_s`
  more than `precond-t-pct` slower
- the `abft` row: checks-on overhead creeping more than `abft-pp`
  percentage points between rounds, or the collective-cadence pin
  (`collectives_identical`) breaking — the producing round bounded
  the absolute at ≤2%; this catches the trend
- `fleet` rows (keyed by replica count): aggregate `solves_per_sec`
  through the replicated fleet dropping more than `fleet-agg-pct`, the
  `non_decreasing` scaling pin breaking in the new round, and the
  kill→rejoin recovery p99 (`rejoin_latency_s`) growing more than
  `rejoin-p99-pct` (a drill that ran but lost the number is a broken
  emitter, gated unconditionally)
- the `grad` row: grad-solves/sec through the scheduler dropping more
  than `grad-pct`, and the per-grid adjoint/primal iteration ratio
  growing past the same band (the adjoint must stay "one extra solve
  with the same operator", not drift into its own convergence story)
- `fmg` rows (keyed by grid): F-cycle `t_solver_s` slower than
  `fmg-pct`, the constant-work-units-per-point pin breaking in the new
  round (the O(N) claim), or a headline row's wall-clock-vs-mg-pcg
  acceptance breaking
- `autotune` rows (keyed by grid): `tuned_t_s` slower than
  `autotune-pct` between rounds; hard pins in the new round — a tuned
  config that measures slower than the static default (`tuned_loses`)
  or a broken registry round-trip is a regression outright
- the `recycle` row (Krylov recycling on the correlated stream):
  `iter_cut` shrinking or warm `solves_per_s_warm` dropping more than
  `recycle-pct` between rounds; hard pins in the new round — a cut
  below 2× or an analytic-l2 gap beyond 10% (the equal-accuracy
  contract of the warm start) is a regression outright

- the `contracts` key (written by `--stamp`): a new round measured
  under a violated engine-contract state is a regression outright, and
  a report-hash change between rounds is noted — two perf numbers are
  only comparable under the same, clean contract state

`python tools/bench_compare.py --stamp BENCH_rN.json` runs the
engine-contract matrix (`poisson_ellipse_tpu.analysis`) and embeds
`{"contracts": {"hash", "clean"}}` into the round, so the next compare
can tell structural drift from noise.

Tolerances live in `pyproject.toml [tool.bench_compare]` (shared by the
CLI and the driver-dryrun smoke gate); built-in defaults apply when the
table or a key is absent. Exit codes: 0 = no regression, 1 = regression
(each named on stdout as `REGRESSION <metric> @ <where>: old -> new`),
2 = unusable input.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
import tomllib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fractional unless -abs; overridable via [tool.bench_compare]
DEFAULT_TOLERANCES = {
    "t-solver-pct": 0.25,
    "iters-abs": 2,
    "gbps-pct": 0.25,
    "kappa-pct": 0.20,
    "sps-pct": 0.25,
    # precond rows (mg-pcg/cheb-pcg): iteration counts are operator-
    # determined like kappa but sit at O(10) where ±2 would be 20%, so
    # they get a fractional band; time shares the wall-clock noise floor
    "precond-iters-pct": 0.15,
    "precond-t-pct": 0.25,
    # abft overhead drift between rounds, in absolute percentage POINTS
    # (the quantity is already a percent — a fractional band of a small
    # percent would be noise-tight)
    "abft-pp": 1.0,
    # geometry rows: the composite-domain solve shares the wall-clock
    # noise floor; quadrature assembly is host work (noisier on a
    # shared CI box), so its band is wider
    "geometry-t-pct": 0.25,
    "geometry-assembly-pct": 0.50,
    # fleet aggregate solves/sec per replica count: the replicated
    # serving layer's throughput shares the serving noise floor
    "fleet-agg-pct": 0.25,
    # fleet kill→rejoin recovery-time-to-capacity p99: dominated by the
    # rejoiner's replay + pre-warm compile, so it gets a wide band
    "rejoin-p99-pct": 0.50,
    # grad key: grad-solves/sec through the scheduler shares the
    # serving noise floor; the adjoint/primal iteration ratio gets the
    # same band (same-operator adjoints must keep tracking the primal)
    "grad-pct": 0.25,
    # bandwidth key ({f32, bf16-storage} × {pipelined, sstep} cells):
    # per-cell T_solver/GB/s share the wall-clock noise floor; the
    # ≤0.6× byte ratio and the l2 parity flag are hard pins per round
    "bandwidth-pct": 0.25,
    # fmg rows (full multigrid as the solver): per-grid T_solver shares
    # the wall-clock noise floor; the work-units-constant pin and the
    # headline wall-clock-vs-mg-pcg acceptance are hard pins per round
    "fmg-pct": 0.25,
    # autotune rows: tuned wall clock per shape shares the noise floor;
    # `tuned_loses` (a tuned config measuring slower than the static
    # default) and a broken registry round-trip are hard pins per round
    "autotune-pct": 0.25,
    # recycle key (Krylov recycling, solver.recycle): the correlated-
    # stream iteration cut and warm solves/sec between rounds; the ≥2×
    # cut and the ≤10% analytic-l2 gap are hard pins per round
    "recycle-pct": 0.25,
}

# scalar-row artifact keys carrying {grid, t_solver_s, iters}
ROW_KEYS = (
    "config2", "north_star", "config4_1chip", "pipelined", "f64",
)

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def load_tolerances(root: str = ROOT) -> dict:
    """DEFAULT_TOLERANCES overlaid with `[tool.bench_compare]`.

    A pyproject that does not parse is an error naming the file and the
    offending key: gating on the defaults instead would hide a typo'd
    bound.
    """
    tol = dict(DEFAULT_TOLERANCES)
    pyproject = os.path.join(root, "pyproject.toml")
    if not os.path.exists(pyproject):
        return tol
    from poisson_ellipse_tpu.lint import _read_pyproject

    try:
        doc = _read_pyproject(pyproject)
    except tomllib.TOMLDecodeError as e:
        # tomllib names only the position: "... (at line 2, column 16)"
        m = re.search(r"at line (\d+)", str(e))
        line = ""
        if m:
            with open(pyproject, encoding="utf-8") as f:
                line = f.read().splitlines()[int(m.group(1)) - 1]
        key = line.partition("=")[0].strip() or "?"
        raise SystemExit(f"{pyproject}: key {key}: {e}") from e
    table = doc.get("tool", {}).get("bench_compare", {})
    for key in tol:
        if key in table:
            try:
                tol[key] = float(table[key])
            except (TypeError, ValueError):
                raise SystemExit(
                    f"[tool.bench_compare] {key} = {table[key]!r} is not "
                    "a number"
                )
    return tol


def _round_key(path: str) -> tuple[int, float]:
    m = _ROUND_RE.search(os.path.basename(path))
    n = int(m.group(1)) if m else -1
    return n, os.path.getmtime(path)


def newest_rounds(root: str = ROOT, n: int = 2) -> list[str]:
    """The n highest-round BENCH_r*.json paths, oldest first."""
    rounds = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")),
                    key=_round_key)
    return rounds[-n:]


def load_round(path: str) -> dict:
    """One bench record (driver `{"parsed": ...}` or raw bench line)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SystemExit(f"cannot read bench round {path}: {e}")
    rec = data.get("parsed", data) if isinstance(data, dict) else data
    if not isinstance(rec, dict):
        raise SystemExit(f"{path}: not a bench record")
    return rec


class Regression:
    """One named threshold violation."""

    def __init__(self, metric: str, where: str, old, new, limit: str):
        self.metric = metric
        self.where = where
        self.old = old
        self.new = new
        self.limit = limit

    def __str__(self) -> str:
        return (
            f"REGRESSION {self.metric} @ {self.where}: "
            f"{self.old:g} -> {self.new:g} ({self.limit})"
        )


def _by_grid(rows) -> dict:
    out = {}
    for row in rows or []:
        grid = row.get("grid")
        if grid:
            out[tuple(grid)] = row
    return out


def _grid_label(key) -> str:
    return "x".join(str(k) for k in key) if isinstance(key, tuple) else str(key)


def compare(old: dict, new: dict, tol: dict) -> tuple[list[Regression], list[str]]:
    """(regressions, notes) between two bench records.

    Only metrics present on BOTH sides are judged; one-sided metrics
    land in notes — a new bench key must not fail its first gated round,
    and an old artifact must not fail for predating one.
    """
    regressions: list[Regression] = []
    notes: list[str] = []

    def one_sided(metric, where, o, n) -> bool:
        """Note-and-skip when a metric exists on exactly one side of a
        matched row — the 'absence is skipped and NOTED' half of the
        contract (silent per-row absence would let a broken emitter
        read as a clean gate)."""
        if (o is None) != (n is None):
            notes.append(f"{metric} @ {where}: only in one round, skipped")
            return True
        return False

    def check_time(where, o, n):
        if one_sided("t_solver_s", where, o, n):
            return
        limit = tol["t-solver-pct"]
        if o and n is not None and n > o * (1.0 + limit):
            regressions.append(Regression(
                "t_solver_s", where, o, n,
                f"+{(n / o - 1):.0%} > {limit:.0%} slower",
            ))

    def check_iters(where, o, n):
        if one_sided("iters", where, o, n):
            return
        limit = tol["iters-abs"]
        if o is not None and n is not None and n > o + limit:
            regressions.append(Regression(
                "iters", where, o, n, f"+{n - o} > +{limit:g} iterations",
            ))

    def scalar_rows(rec, key):
        row = rec.get(key)
        return row if isinstance(row, dict) and row.get("grid") else None

    # the reference-grid table, matched per grid
    old_grids = _by_grid(old.get("grids"))
    new_grids = _by_grid(new.get("grids"))
    for key in sorted(old_grids.keys() & new_grids.keys()):
        o, n = old_grids[key], new_grids[key]
        where = _grid_label(key)
        check_time(where, o.get("t_solver_s"), n.get("t_solver_s"))
        check_iters(where, o.get("iters"), n.get("iters"))
        og, ng = o.get("hbm_gbps"), n.get("hbm_gbps")
        if not one_sided("hbm_gbps", where, og, ng) and og and ng is not None:
            limit = tol["gbps-pct"]
            if ng < og * (1.0 - limit):
                regressions.append(Regression(
                    "hbm_gbps", where, og, ng,
                    f"{(ng / og - 1):.0%} > {limit:.0%} bandwidth drop",
                ))
    for key in sorted(set(old_grids) ^ set(new_grids)):
        notes.append(f"grid {_grid_label(key)}: only in one round, skipped")

    # single-config rows
    for key in ROW_KEYS:
        o, n = scalar_rows(old, key), scalar_rows(new, key)
        if o is None or n is None:
            if (o is None) != (n is None):
                notes.append(f"{key}: only in one round, skipped")
            continue
        check_time(key, o.get("t_solver_s"), n.get("t_solver_s"))
        check_iters(key, o.get("iters"), n.get("iters"))

    # spectral diagnostics: κ is a property of grid + operator, not of
    # the hardware — drift EITHER way is a broken estimator/trace
    old_spec = _by_grid(old.get("spectrum"))
    new_spec = _by_grid(new.get("spectrum"))
    for key in sorted(old_spec.keys() & new_spec.keys()):
        ok, nk = old_spec[key].get("kappa"), new_spec[key].get("kappa")
        if one_sided("kappa", _grid_label(key), ok, nk):
            continue  # a null kappa IS the broken-estimator case: noted
        if ok and nk is not None:
            limit = tol["kappa-pct"]
            if abs(nk - ok) > ok * limit:
                regressions.append(Regression(
                    "kappa", _grid_label(key), ok, nk,
                    f"{(nk / ok - 1):+.0%} drift > ±{limit:.0%}",
                ))
    if bool(old.get("spectrum")) != bool(new.get("spectrum")):
        notes.append("spectrum: only in one round, skipped")

    # preconditioner rows, keyed grid × engine: iteration counts are
    # operator-determined (growth means the V-cycle/bounds broke, not
    # the hardware — fractional band at their O(10) scale), t_solver is
    # the wall-clock win the key exists to defend
    def by_grid_engine(rows):
        out = {}
        for row in rows or []:
            if row.get("grid") and row.get("engine"):
                out[(tuple(row["grid"]), row["engine"])] = row
        return out

    old_pre = by_grid_engine(old.get("precond"))
    new_pre = by_grid_engine(new.get("precond"))
    for key in sorted(old_pre.keys() & new_pre.keys()):
        o_row, n_row = old_pre[key], new_pre[key]
        where_pre = f"{_grid_label(key[0])} {key[1]}"
        o, n = o_row.get("iters"), n_row.get("iters")
        if not one_sided("precond iters", where_pre, o, n) and o and \
                n is not None:
            limit = tol["precond-iters-pct"]
            if n > o * (1.0 + limit):
                regressions.append(Regression(
                    "precond_iters", where_pre, o, n,
                    f"+{(n / o - 1):.0%} > {limit:.0%} more iterations",
                ))
        o, n = o_row.get("t_solver_s"), n_row.get("t_solver_s")
        if not one_sided("precond t_solver_s", where_pre, o, n) and o and \
                n is not None:
            limit = tol["precond-t-pct"]
            if n > o * (1.0 + limit):
                regressions.append(Regression(
                    "precond_t_solver_s", where_pre, o, n,
                    f"+{(n / o - 1):.0%} > {limit:.0%} slower",
                ))
    if bool(old.get("precond")) != bool(new.get("precond")):
        notes.append("precond: only in one round, skipped")

    # serving throughput, keyed grid × lanes
    def by_grid_lanes(rows):
        out = {}
        for row in rows or []:
            if row.get("grid") and row.get("lanes") is not None:
                out[(tuple(row["grid"]), row["lanes"])] = row
        return out

    old_thr = by_grid_lanes(old.get("throughput"))
    new_thr = by_grid_lanes(new.get("throughput"))
    for key in sorted(old_thr.keys() & new_thr.keys()):
        o = old_thr[key].get("solves_per_sec")
        n = new_thr[key].get("solves_per_sec")
        where_thr = f"{_grid_label(key[0])} lanes={key[1]}"
        if one_sided("solves_per_sec", where_thr, o, n):
            continue
        if o and n is not None:
            limit = tol["sps-pct"]
            if n < o * (1.0 - limit):
                regressions.append(Regression(
                    "solves_per_sec", where_thr, o, n,
                    f"{(n / o - 1):.0%} > {limit:.0%} throughput drop",
                ))
    if bool(old.get("throughput")) != bool(new.get("throughput")):
        notes.append("throughput: only in one round, skipped")

    # the ABFT overhead row: the producing round bounded the absolute at
    # ≤2%; this catches creep between rounds (percentage POINTS —
    # the quantity is already a percent) and the cadence pin breaking
    def live_abft(rec):
        row = rec.get("abft")
        return row if isinstance(row, dict) and row.get("available") else None

    o_row, n_row = live_abft(old), live_abft(new)
    if o_row is not None and n_row is not None:
        o, n = o_row.get("overhead_pct"), n_row.get("overhead_pct")
        if not one_sided("abft overhead_pct", "abft", o, n) and \
                o is not None and n is not None:
            limit = tol["abft-pp"]
            if n > o + limit:
                regressions.append(Regression(
                    "abft_overhead_pct", "abft", o, n,
                    f"+{n - o:.2f}pp > +{limit:g}pp overhead creep",
                ))
        if n_row.get("collectives_identical") is False:
            regressions.append(Regression(
                "abft_collectives", "abft", 1, 0,
                "checks-on added collectives (the identical-cadence pin "
                "broke)",
            ))
    elif (o_row is None) != (n_row is None):
        notes.append("abft: only in one round, skipped")

    # the fleet key: aggregate solves/sec per replica count (the
    # replicated layer's throughput story) and the non-decreasing
    # scaling pin — a new round whose own pin broke is a regression
    # even if every per-width number stayed inside the band
    def fleet_rows(rec):
        fleet = rec.get("fleet")
        if not isinstance(fleet, dict):
            return {}
        return {
            row["replicas"]: row
            for row in fleet.get("rows") or []
            if row.get("replicas") is not None
        }

    old_fleet, new_fleet = fleet_rows(old), fleet_rows(new)
    for key in sorted(old_fleet.keys() & new_fleet.keys()):
        o = old_fleet[key].get("solves_per_sec")
        n = new_fleet[key].get("solves_per_sec")
        where_fleet = f"fleet replicas={key}"
        if one_sided("fleet solves_per_sec", where_fleet, o, n):
            continue
        if o and n is not None:
            limit = tol["fleet-agg-pct"]
            if n < o * (1.0 - limit):
                regressions.append(Regression(
                    "fleet_solves_per_sec", where_fleet, o, n,
                    f"{(n / o - 1):.0%} > {limit:.0%} aggregate drop",
                ))
    if old_fleet and new_fleet:
        if new.get("fleet", {}).get("non_decreasing") is False:
            regressions.append(Regression(
                "fleet_non_decreasing", "fleet", 1, 0,
                "aggregate solves/sec now DECREASES with replica count "
                "(the scaling pin broke)",
            ))
        # the kill→rejoin recovery number: p99 of kill→first-completed-
        # solve on the rejoined incarnation. One-sided absence is noted
        # (pre-rejoin artifacts must keep comparing), but a new round
        # that DID run the drill and lost the number (rejoins executed,
        # no latency observed) is a broken emitter, not noise.
        o_rj = old.get("fleet", {}).get("rejoin_latency_s")
        n_rj = new.get("fleet", {}).get("rejoin_latency_s")
        if not one_sided("fleet rejoin_latency_s", "fleet", o_rj, n_rj):
            if o_rj and n_rj is not None:
                limit = tol["rejoin-p99-pct"]
                if n_rj > o_rj * (1.0 + limit):
                    regressions.append(Regression(
                        "fleet_rejoin_latency_s", "fleet", o_rj, n_rj,
                        f"+{(n_rj / o_rj - 1):.0%} > {limit:.0%} slower "
                        "recovery to capacity",
                    ))
        if new.get("fleet", {}).get("rejoins", 0) >= 1 and n_rj is None:
            regressions.append(Regression(
                "fleet_rejoin_latency_s", "fleet", 1, 0,
                "rejoin drill ran but observed no recovery latency "
                "(the emitter broke)",
            ))
    elif bool(old_fleet) != bool(new_fleet):
        notes.append("fleet: only in one round, skipped")

    # the geometry key: the composite-domain solve time and the
    # quadrature assembly cost, plus the parity fields as hard pins —
    # face-fraction error growing past the acceptance bound is a
    # regression even within a round that still said valid
    o_geo, n_geo = old.get("geometry"), new.get("geometry")
    if isinstance(o_geo, dict) and isinstance(n_geo, dict):
        o_c = (o_geo.get("composite") or {}).get("t_solver_s")
        n_c = (n_geo.get("composite") or {}).get("t_solver_s")
        if not one_sided("geometry composite t_solver_s", "geometry",
                         o_c, n_c) and o_c and n_c is not None:
            limit = tol["geometry-t-pct"]
            if n_c > o_c * (1.0 + limit):
                regressions.append(Regression(
                    "geometry_t_solver_s", "composite", o_c, n_c,
                    f"+{(n_c / o_c - 1):.0%} > +{limit:.0%}",
                ))
        o_a, n_a = o_geo.get("assembly_quad_s"), n_geo.get("assembly_quad_s")
        if not one_sided("geometry assembly_quad_s", "geometry",
                         o_a, n_a) and o_a and n_a is not None:
            limit = tol["geometry-assembly-pct"]
            if n_a > o_a * (1.0 + limit):
                regressions.append(Regression(
                    "geometry_assembly_quad_s", "geometry", o_a, n_a,
                    f"+{(n_a / o_a - 1):.0%} > +{limit:.0%}",
                ))
        o_e, n_e = o_geo.get("max_frac_err"), n_geo.get("max_frac_err")
        if o_e is not None and n_e is not None and n_e > 1e-12:
            regressions.append(Regression(
                "geometry_max_frac_err", "geometry", o_e, n_e,
                "> 1e-12 acceptance bound",
            ))
    elif (o_geo is None) != (n_geo is None):
        notes.append("geometry: only in one round, skipped")

    # the grad key: grad-solves/sec through the scheduler (the served
    # differentiable-solving throughput) under `grad-pct`, plus the
    # per-grid adjoint/primal iteration ratio as a hard pin — the
    # adjoint reuses the same operator and preconditioner, so its
    # iteration count drifting far past the primal's means the adjoint
    # path stopped being "one extra solve"
    o_grad, n_grad = old.get("grad"), new.get("grad")
    if isinstance(o_grad, dict) and isinstance(n_grad, dict):
        o_g = o_grad.get("grad_solves_per_sec")
        n_g = n_grad.get("grad_solves_per_sec")
        if not one_sided("grad grad_solves_per_sec", "grad", o_g, n_g) \
                and o_g and n_g is not None:
            limit = tol["grad-pct"]
            if n_g < o_g * (1.0 - limit):
                regressions.append(Regression(
                    "grad_solves_per_sec", "grad", o_g, n_g,
                    f"{(n_g / o_g - 1):.0%} > {limit:.0%} drop",
                ))
        o_rows = {tuple(r["grid"]): r for r in o_grad.get("rows") or []}
        n_rows = {tuple(r["grid"]): r for r in n_grad.get("rows") or []}
        for key in sorted(o_rows.keys() & n_rows.keys()):
            o_r, n_r = o_rows[key].get("ratio"), n_rows[key].get("ratio")
            if o_r is None or n_r is None:
                continue
            where_grad = f"grad {_grid_label(key)}"
            limit = tol["grad-pct"]
            if n_r > max(o_r * (1.0 + limit), o_r + 0.1):
                regressions.append(Regression(
                    "grad_adjoint_ratio", where_grad, o_r, n_r,
                    f"adjoint/primal ratio +{(n_r / o_r - 1):.0%} > "
                    f"+{limit:.0%}",
                ))
    elif (o_grad is None) != (n_grad is None):
        notes.append("grad: only in one round, skipped")

    # the bandwidth key: per-cell T_solver/GB/s drift between rounds
    # under `bandwidth-pct`, plus two hard pins carried by the new
    # round itself — the ≤0.6× modeled byte ratio and the bf16 l2
    # parity flag — which are acceptance facts, not noise-band numbers
    def bw_cells(rec):
        row = rec.get("bandwidth")
        if not isinstance(row, dict) or not row.get("available"):
            return {}
        return {
            (c.get("engine"), c.get("storage")): c
            for c in row.get("cells") or []
        }

    o_bw, n_bw = bw_cells(old), bw_cells(new)
    for key in sorted(o_bw.keys() & n_bw.keys()):
        where_bw = f"bandwidth {key[0]}/{key[1]}"
        o_t = o_bw[key].get("t_solver_s")
        n_t = n_bw[key].get("t_solver_s")
        if not one_sided("bandwidth t_solver_s", where_bw, o_t, n_t) and \
                o_t and n_t is not None:
            limit = tol["bandwidth-pct"]
            if n_t > o_t * (1.0 + limit):
                regressions.append(Regression(
                    "bandwidth_t_solver_s", where_bw, o_t, n_t,
                    f"+{(n_t / o_t - 1):.0%} > +{limit:.0%}",
                ))
        o_g = o_bw[key].get("hbm_gbps")
        n_g = n_bw[key].get("hbm_gbps")
        if not one_sided("bandwidth hbm_gbps", where_bw, o_g, n_g) and \
                o_g and n_g is not None:
            limit = tol["bandwidth-pct"]
            if n_g < o_g * (1.0 - limit):
                regressions.append(Regression(
                    "bandwidth_hbm_gbps", where_bw, o_g, n_g,
                    f"{(n_g / o_g - 1):.0%} > {limit:.0%} bandwidth drop",
                ))
    if n_bw:
        for key, cell in sorted(n_bw.items()):
            ratio = cell.get("byte_ratio_vs_f32")
            gate = new.get("bandwidth", {}).get("byte_ratio_gate", 0.6)
            if ratio is not None and ratio > gate:
                regressions.append(Regression(
                    "bandwidth_byte_ratio",
                    f"bandwidth {key[0]}/{key[1]}", gate, ratio,
                    f"modeled byte ratio {ratio:.2f}x > {gate:g}x gate",
                ))
            if cell.get("l2_parity") is False:
                regressions.append(Regression(
                    "bandwidth_l2_parity",
                    f"bandwidth {key[0]}/{key[1]}", 1, 0,
                    "bf16 l2 left the f32 parity band",
                ))
    if bool(o_bw) != bool(n_bw):
        notes.append("bandwidth: only in one round, skipped")

    # the fmg key: per-grid T_solver drift between rounds under
    # `fmg-pct`, plus two hard pins carried by the new round itself —
    # the constant-work-units pin (the O(N) claim) and every headline
    # row's wall-clock-vs-mg-pcg acceptance
    def fmg_rows(rec):
        row = rec.get("fmg")
        if not isinstance(row, dict):
            return {}
        return {
            tuple(r["grid"]): r for r in row.get("rows") or []
            if r.get("grid")
        }

    o_fmg, n_fmg = fmg_rows(old), fmg_rows(new)
    for key in sorted(o_fmg.keys() & n_fmg.keys()):
        where_fmg = f"fmg {_grid_label(key)}"
        o_t, n_t = o_fmg[key].get("t_solver_s"), n_fmg[key].get("t_solver_s")
        if not one_sided("fmg t_solver_s", where_fmg, o_t, n_t) and \
                o_t and n_t is not None:
            limit = tol["fmg-pct"]
            if n_t > o_t * (1.0 + limit):
                regressions.append(Regression(
                    "fmg_t_solver_s", where_fmg, o_t, n_t,
                    f"+{(n_t / o_t - 1):.0%} > +{limit:.0%}",
                ))
    if n_fmg:
        if new.get("fmg", {}).get("work_units_constant") is False:
            regressions.append(Regression(
                "fmg_work_units", "fmg", 1, 0,
                "work units per grid point left the ±20% constant band "
                "(the O(N) pin broke)",
            ))
        for key, row in sorted(n_fmg.items()):
            sp = row.get("speedup_vs_mg")
            if row.get("headline") and sp is not None and sp < 1.0:
                regressions.append(Regression(
                    "fmg_headline_speedup", f"fmg {_grid_label(key)}",
                    1.0, sp,
                    "headline F-cycle slower than mg-pcg at equal "
                    "accuracy (the wall-clock acceptance broke)",
                ))
    if bool(o_fmg) != bool(n_fmg):
        notes.append("fmg: only in one round, skipped")

    # the autotune key: tuned wall clock per shape under `autotune-pct`
    # between rounds, plus the hard pins in the new round — a tuned
    # config must never lose to the static default, and the persisted
    # registry must round-trip
    def tune_rows(rec):
        row = rec.get("autotune")
        if not isinstance(row, dict):
            return {}
        return {
            tuple(r["grid"]): r for r in row.get("rows") or []
            if r.get("grid")
        }

    o_at, n_at = tune_rows(old), tune_rows(new)
    for key in sorted(o_at.keys() & n_at.keys()):
        where_at = f"autotune {_grid_label(key)}"
        o_t, n_t = o_at[key].get("tuned_t_s"), n_at[key].get("tuned_t_s")
        if not one_sided("autotune tuned_t_s", where_at, o_t, n_t) and \
                o_t and n_t is not None:
            limit = tol["autotune-pct"]
            if n_t > o_t * (1.0 + limit):
                regressions.append(Regression(
                    "autotune_tuned_t_s", where_at, o_t, n_t,
                    f"+{(n_t / o_t - 1):.0%} > +{limit:.0%}",
                ))
    for key, row in sorted(n_at.items()):
        if row.get("tuned_loses"):
            regressions.append(Regression(
                "autotune_tuned_loses", f"autotune {_grid_label(key)}",
                row.get("static_t_s"), row.get("tuned_t_s"),
                "tuned config loses to the static default (the "
                "never-loses contract broke)",
            ))
        if row.get("roundtrip_ok") is False:
            regressions.append(Regression(
                "autotune_roundtrip", f"autotune {_grid_label(key)}",
                1, 0, "tuned-config registry round-trip broke",
            ))
    if bool(o_at) != bool(n_at):
        notes.append("autotune: only in one round, skipped")

    # the recycle key: the correlated-stream iteration cut and warm
    # solves/sec under `recycle-pct` between rounds, plus the hard pins
    # in the new round — the ≥2× cut (the ISSUE's acceptance number)
    # and the ≤10% analytic-l2 gap (a warm start must buy iterations,
    # never accuracy) are regressions outright
    def recycle_row(rec):
        row = rec.get("recycle")
        return row if isinstance(row, dict) and row.get("grid") else None

    o_rc, n_rc = recycle_row(old), recycle_row(new)
    if o_rc is not None and n_rc is not None:
        where_rc = f"recycle {_grid_label(tuple(n_rc['grid']))}"
        limit = tol["recycle-pct"]
        o_cut, n_cut = o_rc.get("iter_cut"), n_rc.get("iter_cut")
        if not one_sided("recycle iter_cut", where_rc, o_cut, n_cut) and \
                o_cut and n_cut is not None:
            if n_cut < o_cut * (1.0 - limit):
                regressions.append(Regression(
                    "recycle_iter_cut", where_rc, o_cut, n_cut,
                    f"-{(1 - n_cut / o_cut):.0%} > -{limit:.0%}",
                ))
        o_s = o_rc.get("solves_per_s_warm")
        n_s = n_rc.get("solves_per_s_warm")
        if not one_sided("recycle solves_per_s_warm", where_rc, o_s, n_s) \
                and o_s and n_s is not None:
            if n_s < o_s * (1.0 - limit):
                regressions.append(Regression(
                    "recycle_solves_per_s_warm", where_rc, o_s, n_s,
                    f"-{(1 - n_s / o_s):.0%} > -{limit:.0%}",
                ))
    if n_rc is not None:
        where_rc = f"recycle {_grid_label(tuple(n_rc['grid']))}"
        n_cut = n_rc.get("iter_cut")
        if n_cut is not None and n_cut < 2.0:
            regressions.append(Regression(
                "recycle_cut_pin", where_rc, 2.0, n_cut,
                "correlated-stream iteration cut below the 2x "
                "acceptance pin",
            ))
        gap = n_rc.get("l2_rel_gap_max")
        if gap is not None and gap > 0.10:
            regressions.append(Regression(
                "recycle_l2_gap", where_rc, 0.10, gap,
                "warm-stream analytic l2 left the 10% equal-accuracy "
                "band",
            ))
        if n_rc.get("converged") is False:
            regressions.append(Regression(
                "recycle_converged", where_rc, 1, 0,
                "a solve in the recycle stream failed to converge",
            ))
    if (o_rc is None) != (n_rc is None):
        notes.append("recycle: only in one round, skipped")

    # the contracts key (--stamp): two perf numbers are only comparable
    # under the same, clean engine-contract state — a new round measured
    # under violated contracts is a regression outright, and a changed
    # report hash means the deltas may be structural, not noise
    o_ct, n_ct = old.get("contracts"), new.get("contracts")
    if isinstance(o_ct, dict) and isinstance(n_ct, dict):
        if n_ct.get("clean") is False:
            regressions.append(Regression(
                "contracts_clean", "contracts", 1, 0,
                "new round measured under a violated engine-contract "
                "state",
            ))
        if o_ct.get("hash") != n_ct.get("hash"):
            notes.append(
                "contracts: report hash changed between rounds — the "
                "engine-contract state differs; perf deltas may be "
                "structural, not noise"
            )
    elif (o_ct is None) != (n_ct is None):
        notes.append("contracts: only in one round, skipped")

    return regressions, notes


def stamp(path: str) -> int:
    """Embed the current engine-contract state into a bench round.

    Runs the full contract matrix (abstract tracing only — cheap) and
    writes ``{"contracts": {"hash", "clean"}}`` into the record, so a
    later compare can refuse to read perf deltas across a contract
    change. Exit 0 when the matrix is clean, 1 when not (the stamp is
    still written — the compare gate is what fails the round).
    """
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"error: cannot read bench round {path}: {e}",
              file=sys.stderr)
        return 2
    rec = data.get("parsed", data) if isinstance(data, dict) else data
    if not isinstance(rec, dict):
        print(f"error: {path}: not a bench record", file=sys.stderr)
        return 2
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if ROOT not in sys.path:  # script invocation: tools/ is sys.path[0]
        sys.path.insert(0, ROOT)
    try:
        from poisson_ellipse_tpu.analysis import matrix
        from poisson_ellipse_tpu.parallel.mesh import virtual_cpu_devices

        # same virtual-mesh ritual as the analysis CLI: the matrix's
        # sharded cells trace against a (1, 2) mesh, which needs more
        # than the single default CPU device
        virtual_cpu_devices(8)
        report = matrix.run_matrix()
    except Exception as e:
        # the exit-code contract: 1 is "contracts not clean", never a
        # crash — an unimportable/unrunnable matrix is unusable input
        print(f"error: cannot run the contract matrix: {e}",
              file=sys.stderr)
        return 2
    rec["contracts"] = {
        "hash": matrix.report_hash(report),
        "clean": report["clean"],
    }
    with open(path, "w") as f:
        json.dump(data, f)
        f.write("\n")
    state = "clean" if report["clean"] else "NOT clean"
    print(
        f"stamped {os.path.basename(path)}: contracts {state} "
        f"({rec['contracts']['hash'][:12]})"
    )
    return 0 if report["clean"] else 1


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    if "--stamp" in argv:
        argv.remove("--stamp")
        if len(argv) != 1:
            print(
                "usage: python tools/bench_compare.py --stamp "
                "BENCH_rN.json",
                file=sys.stderr,
            )
            return 2
        return stamp(argv[0])
    if len(argv) not in (0, 2):
        print(
            "usage: python tools/bench_compare.py [--json] "
            "[OLD.json NEW.json | --stamp BENCH_rN.json]\n(no paths: the "
            "newest two BENCH_r*.json rounds in the repo root)",
            file=sys.stderr,
        )
        return 2
    if argv:
        old_path, new_path = argv
    else:
        rounds = newest_rounds()
        if len(rounds) < 2:
            print(
                f"need two BENCH_r*.json rounds in {ROOT} to compare, "
                f"found {len(rounds)}",
                file=sys.stderr,
            )
            return 2
        old_path, new_path = rounds
    try:
        tol = load_tolerances()
        old, new = load_round(old_path), load_round(new_path)
    except SystemExit as e:
        # the exit-code contract: unusable input is 2, NEVER 1 — a CI
        # gate reading 1 as "perf regression" must not misclassify a
        # corrupt artifact or a typo'd tolerance as a slowdown
        print(f"error: {e}", file=sys.stderr)
        return 2
    regressions, notes = compare(old, new, tol)
    if as_json:
        print(json.dumps({
            "old": os.path.basename(old_path),
            "new": os.path.basename(new_path),
            "tolerances": tol,
            "regressions": [
                {
                    "metric": r.metric, "where": r.where,
                    "old": r.old, "new": r.new, "limit": r.limit,
                }
                for r in regressions
            ],
            "notes": notes,
        }))
    else:
        print(
            f"bench_compare: {os.path.basename(old_path)} -> "
            f"{os.path.basename(new_path)}"
        )
        for note in notes:
            print(f"  note: {note}")
        for r in regressions:
            print(f"  {r}")
        if not regressions:
            print("  no regressions (within tolerances)")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
