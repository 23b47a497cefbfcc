"""Telemetry-driven engine autotuning: measured configs, not folklore.

The repo already measures everything a tuner needs — κ(M⁻¹A) and
Ritz-replay iteration prediction from the Lanczos-of-CG reconstruction
(``obs.spectrum``, exact on the published grids), measured streaming
bandwidth (``obs.profile``), and the per-engine traffic models
(``harness.roofline`` / ``mg.engine.modeled_extra_passes``). This
module closes the loop: score every candidate engine configuration for
a shape from that telemetry, pick a winner that provably does not lose
to the static default, persist it at a fixed path in the checkout, and
let ``solver.engine.build_solver(engine="auto")`` and the serve
scheduler's batch contexts (``Scheduler._ctx_for``, the per-bucket
tuned chunk) consult the persisted registry at admission.

Three invariants, enforced in code rather than hoped for:

- **The static default is always a candidate** and the winner must beat
  it by a margin (:data:`SELECT_MARGIN`) on the predicted-cost model —
  a coin-flip prediction keeps the default. With ``measure=True`` the
  winner is additionally wall-clocked against the default and demoted
  on a loss (and ``tools/bench_compare.py``'s ``autotune-pct`` gate
  fails any published round where a tuned config loses anyway).
- **Determinism**: :func:`select` is a pure function of the telemetry
  dict — the same telemetry always yields the same config (pinned in
  ``tests/test_fmg.py``), so a persisted registry is reproducible from
  its recorded telemetry.
- **Keys are complete**: (grid bucket, geometry fingerprint, dtype,
  storage dtype, norm) — the same components that make a warm-pool
  executable reusable. A tuned config is never consulted for a shape
  it was not tuned for.

The candidate knob space comes from ``solver.engine.ENGINE_CAPS`` — the
one engine-capability table — so a newly registered engine exposes its
tunables to the tuner in the same row that registers everything else.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import tempfile
import time
from typing import Optional

import jax.numpy as jnp

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs import trace as obs_trace

SCHEMA_VERSION = 1
ENV_DISABLE = "POISSON_AUTOTUNE"

# a candidate must beat the static default's predicted cost by this
# fraction to displace it — the model's noise floor; anything closer is
# a coin flip and the default (known-good, oracle-checked) keeps the slot
SELECT_MARGIN = 0.10

# modeled HBM passes per stencil application / per diagonal-PCG
# iteration — the same constants mg.engine.modeled_extra_passes and
# harness.roofline quote, kept here as named facts of the cost model
PASSES_PER_APPLY = 7.0
PASSES_PER_DIAG_ITER = 13.0
# of those, the fine-array passes the classical recurrence spends on
# its separate reduction/dot reads; the s-step block fuses them into
# ONE Gram round over its (2s+1)-vector basis per s iterations (PR
# 14's communication-avoiding trade), i.e. (2s+1)/s passes/iteration
PASSES_PER_DIAG_REDUCE = 4.0

# V-cycle-preconditioned CG contracts the error by a grid-independent
# factor per iteration (the whole point of PR 8); ρ = 0.3 is the
# conservative end of the measured band on the published grids
MG_RATE = 0.3
# verification/polish iterations the FMG handoff budget assumes
FMG_HANDOFF_ITERS = 2.0
# telemetry probe budget (iterations of the capped history solve)
PROBE_ITERS = 48
# fallback streaming bandwidth when no profile measurement is available
# (CPU test runs); only relative candidate ranking survives it anyway
FALLBACK_GBPS = 100.0


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """One shape's tuned engine configuration (the registry's value)."""

    engine: str
    knobs: dict = dataclasses.field(default_factory=dict)
    predicted_iters: float | None = None
    predicted_t_s: float | None = None
    static_engine: str | None = None
    static_predicted_t_s: float | None = None
    measured_t_s: float | None = None
    static_measured_t_s: float | None = None
    # Krylov-recycling verdict for the serve lanes (``solver.recycle``):
    # True when the deflated Ritz replay predicts a warm start cuts the
    # diagonal iteration count by at least SELECT_MARGIN for this shape.
    # Advisory — the scheduler's ``warm_start`` stays an explicit opt-in
    # because warm-started solution bits legitimately differ from cold.
    recycle: bool = False
    predicted_iters_recycled: float | None = None

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, rec: dict) -> "TunedConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in rec.items() if k in fields})


# -- keys --------------------------------------------------------------------


def geometry_fingerprint(geometry) -> str:
    """A stable content fingerprint of the domain: "ellipse" for the
    closed-form default, else the sha1 of the canonical JSON spec —
    byte-stable across processes, which is what lets a persisted config
    be consulted by a different worker than the one that tuned it."""
    if geometry is None:
        return "ellipse"
    if not isinstance(geometry, dict):
        from poisson_ellipse_tpu.geom import sdf as geom_sdf

        geometry = geom_sdf.to_spec(geometry)
    canon = json.dumps(geometry, sort_keys=True, separators=(",", ":"))
    return hashlib.sha1(canon.encode()).hexdigest()[:16]


def tune_key(problem: Problem, dtype=jnp.float32, storage_dtype=None,
             geometry=None) -> str:
    """The registry key: (grid bucket, geometry fingerprint, dtype,
    storage dtype, norm) — the compile-cache bucketing reused, so one
    tuned config covers exactly the shapes one warm executable covers."""
    from poisson_ellipse_tpu.ops.precision import resolve_storage_dtype
    from poisson_ellipse_tpu.runtime.compile_cache import grid_bucket

    Mb, Nb = grid_bucket(problem.M, problem.N)
    st = resolve_storage_dtype(storage_dtype, dtype)
    storage = "" if st is None else jnp.dtype(st).name
    return "|".join((
        f"{Mb}x{Nb}", geometry_fingerprint(geometry),
        jnp.dtype(dtype).name, storage, problem.norm,
    ))


# -- persistence -------------------------------------------------------------


def registry_path() -> str:
    """``<repo>/.autotune/registry.json``: a fixed path in the checkout.

    It deliberately does NOT follow ``$JAX_COMPILATION_CACHE_DIR``: the
    registry steers which engine ``engine="auto"`` resolves to, so a
    compile cache shared between checkouts must never carry one
    checkout's tuning into another."""
    from poisson_ellipse_tpu.runtime.compile_cache import REPO_ROOT

    return os.path.join(REPO_ROOT, ".autotune", "registry.json")


class TuneRegistry:
    """The persisted key → :class:`TunedConfig` map.

    Writes are atomic (tempfile + rename) so a crashed tuner never
    leaves a torn registry for ``build_solver`` to trip over; loads
    tolerate a missing file (empty registry) and refuse a wrong schema
    version (forward-compatibility: better untuned than mistuned).
    """

    def __init__(self, path: str | None = None):
        self.path = path or registry_path()
        self.entries: dict[str, TunedConfig] = {}
        self._loaded = False

    def load(self) -> "TuneRegistry":
        self._loaded = True
        try:
            with open(self.path, "r", encoding="utf-8") as fh:
                rec = json.load(fh)
        except (OSError, json.JSONDecodeError):
            return self
        if rec.get("version") != SCHEMA_VERSION:
            return self
        for key, val in (rec.get("entries") or {}).items():
            try:
                self.entries[key] = TunedConfig.from_json(val)
            except (TypeError, ValueError):
                continue  # one bad entry must not poison the registry
        return self

    def save(self) -> str:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        rec = {
            "version": SCHEMA_VERSION,
            "entries": {k: v.to_json() for k, v in self.entries.items()},
        }
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(self.path) or ".", suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as fh:
                json.dump(rec, fh, sort_keys=True)
            os.replace(tmp, self.path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return self.path

    def get(self, key: str) -> Optional[TunedConfig]:
        if not self._loaded:
            self.load()
        return self.entries.get(key)

    def put(self, key: str, cfg: TunedConfig) -> None:
        self.entries[key] = cfg


_REGISTRY: Optional[TuneRegistry] = None


def default_registry() -> TuneRegistry:
    """The process-wide registry (loaded lazily from the default path)."""
    global _REGISTRY
    if _REGISTRY is None:
        _REGISTRY = TuneRegistry().load()
    return _REGISTRY


def lookup(problem: Problem, dtype=jnp.float32, storage_dtype=None,
           geometry=None, registry: TuneRegistry | None = None,
           ) -> Optional[TunedConfig]:
    """The admission-time consult: the persisted tuned config for this
    shape, or None (which leaves every caller on its static default).

    Cheap by construction — one dict lookup against the lazily loaded
    registry; a missing file, a disabled tuner
    (``POISSON_AUTOTUNE=off``) or an unknown key all answer None, so
    untuned processes behave byte-identically to the pre-tuner release.
    """
    if os.environ.get(ENV_DISABLE, "").lower() in ("0", "off", "false"):
        return None
    reg = registry if registry is not None else default_registry()
    if registry is None and not os.path.exists(reg.path):
        return None
    return reg.get(tune_key(problem, dtype, storage_dtype=storage_dtype,
                            geometry=geometry))


# -- telemetry ---------------------------------------------------------------


def collect_telemetry(problem: Problem, dtype=jnp.float32, geometry=None,
                      theta=None, probe_iters: int = PROBE_ITERS,
                      measure_gbps: bool = True) -> dict:
    """The measured facts the scoring model consumes, in one dict.

    One capped history-enabled diagonal solve feeds ``obs.spectrum``
    (κ, eigenvalue bounds, Ritz-replay predicted iterations — the same
    single Lanczos path ``harness diagnose`` and ``mg.engine`` use);
    ``measure_gbps=True`` adds one ``obs.profile`` phase profile for the
    achieved streaming bandwidth. Everything downstream
    (:func:`select`) is a pure function of this dict — record it, and
    the tuning decision replays exactly.
    """
    import dataclasses as _dc

    from poisson_ellipse_tpu.obs import spectrum as obs_spectrum
    from poisson_ellipse_tpu.solver.engine import build_solver
    from poisson_ellipse_tpu.solver.recycle import RECYCLE_K

    probe = _dc.replace(
        problem, max_iter=min(probe_iters, problem.max_iterations)
    )
    solver, args, _ = build_solver(probe, "xla", dtype, history=True,
                                   geometry=geometry, theta=theta)
    result, trace = solver(*args)
    # deflated_k marks the report as ALSO predicting the k-mode
    # recycled warm start — predicted_iters_cold/-_recycled arrive as a
    # pair, and scoring below keeps the cold figure as predicted_iters
    # so the per-solve cost model's semantics are unchanged
    spec = obs_spectrum.spectrum_report(
        trace, delta=problem.delta, actual_iters=int(result.iters),
        deflated_k=RECYCLE_K,
    )
    gbps = None
    if measure_gbps:
        from poisson_ellipse_tpu.obs import profile as obs_profile

        try:
            # the profile runs the ellipse form of the grid — bandwidth
            # is a shape fact, not a geometry fact
            prof = obs_profile.profile_engine(
                probe, "xla", dtype, repeat=1, with_xla_cost=False,
            )
            gbps = prof.get("hbm_gbps")
        except (TypeError, ValueError):
            gbps = None
    return {
        "grid": [problem.M, problem.N],
        "delta": problem.delta,
        "kappa": spec.get("kappa") if spec.get("available") else None,
        "predicted_iters": (
            spec.get("predicted_iters_cold")
            if spec.get("available") else None
        ),
        "predicted_iters_recycled": (
            spec.get("predicted_iters_recycled")
            if spec.get("available") else None
        ),
        "probe_iters": int(result.iters),
        "probe_converged": bool(result.converged),
        "gbps": gbps,
    }


# -- the scoring model -------------------------------------------------------


def _diag_iters(problem: Problem, telemetry: dict) -> float:
    """Ritz-predicted diagonal-PCG iterations, with the κ-model and the
    probe's own count as graceful fallbacks (in that order)."""
    pred = telemetry.get("predicted_iters")
    if pred:
        return float(pred)
    kappa = telemetry.get("kappa")
    if kappa and kappa > 1.0:
        # the CG error bound: iters ≈ ½√κ ln(2/δ)
        return 0.5 * math.sqrt(kappa) * math.log(2.0 / problem.delta)
    return float(max(telemetry.get("probe_iters") or 1, 1))


def _recycled_iters(problem: Problem,
                    telemetry: dict) -> Optional[float]:
    """Ritz-predicted diagonal iterations AFTER the k-mode deflated warm
    start (``solver.recycle``), or None when the probe's trace could not
    support the deflated replay — there is deliberately no κ fallback
    here: a recycling win must be predicted from the measured spectrum
    or not claimed at all."""
    pred = telemetry.get("predicted_iters_recycled")
    return float(pred) if pred else None


def _mg_iters(problem: Problem) -> float:
    """V-cycle-preconditioned iteration budget: the grid-independent
    contraction ρ = MG_RATE gives iters ≈ ln(1/δ)/ln(1/ρ)."""
    return max(
        math.log(1.0 / problem.delta) / math.log(1.0 / MG_RATE), 4.0
    )


def candidates(problem: Problem, dtype=jnp.float32,
               storage_dtype=None) -> list[TunedConfig]:
    """The candidate set for one shape: the static default first (the
    anchor every winner must beat), then the iteration-count engines
    with their ENGINE_CAPS tunables swept over a small static menu."""
    from poisson_ellipse_tpu.mg import coarsen
    from poisson_ellipse_tpu.solver.engine import (
        ENGINE_CAPS,
        select_engine,
    )

    default = select_engine(problem, dtype)
    out = [TunedConfig(engine=default)]
    if storage_dtype is not None:
        # narrow-storage shapes: only storage-capable engines may enter
        return out + [
            TunedConfig(engine="sstep", knobs={"sstep_s": s})
            for s in (2, 4)
            if ENGINE_CAPS["sstep"]["storage"]
        ]
    levels = coarsen.num_levels(problem.M, problem.N)
    mg_tun = dict(ENGINE_CAPS["mg-pcg"]["tunables"], levels=levels)
    fmg_tun = dict(ENGINE_CAPS["fmg"]["tunables"], levels=levels)
    out.append(TunedConfig(engine="mg-pcg", knobs=mg_tun))
    for k in (8, 12, 16):
        out.append(TunedConfig(engine="cheb-pcg",
                               knobs={"cheb_degree": k}))
    for nv in (1, 2):
        out.append(TunedConfig(
            engine="fmg", knobs=dict(fmg_tun, n_vcycles=nv)
        ))
    return out


def predicted_cost(problem: Problem, cand: TunedConfig, telemetry: dict,
                   dtype=jnp.float32) -> tuple[float, float]:
    """(predicted fine-array HBM passes, predicted iterations) for one
    candidate — a pure function of (candidate, telemetry), which is what
    makes :func:`select` deterministic and replayable."""
    from poisson_ellipse_tpu.mg.engine import modeled_extra_passes
    from poisson_ellipse_tpu.mg.fmg import work_units_per_point

    if cand.engine == "mg-pcg":
        iters = _mg_iters(problem)
        passes = iters * (
            PASSES_PER_DIAG_ITER
            + modeled_extra_passes(problem, "mg-pcg", dtype)
        )
    elif cand.engine == "cheb-pcg":
        k = int(cand.knobs.get("cheb_degree", 12))
        # each iteration's polynomial buys ~k× fewer iterations (the
        # measured first-rung trade; bench `precond` validates it)
        iters = max(_diag_iters(problem, telemetry) / max(k, 1), 4.0)
        passes = iters * (
            PASSES_PER_DIAG_ITER + PASSES_PER_APPLY * (k - 1) + 2.0
        )
    elif cand.engine == "fmg":
        levels = int(cand.knobs.get("levels") or 1)
        iters = FMG_HANDOFF_ITERS
        passes = PASSES_PER_APPLY * work_units_per_point(
            levels,
            nu=int(cand.knobs.get("nu", 2)),
            coarse_degree=int(cand.knobs.get("coarse_degree", 24)),
            n_vcycles=int(cand.knobs.get("n_vcycles", 2)),
        ) + iters * (
            PASSES_PER_DIAG_ITER
            + modeled_extra_passes(problem, "mg-pcg", dtype)
        )
    elif cand.engine in ("sstep", "sstep-pallas"):
        # same iteration count as the diagonal recurrence, but the
        # separate reduction reads collapse into one Gram round over
        # the (2s+1)-vector basis per s iterations — without this the
        # storage-dtype sweep scores sstep identical to the default
        # and can never select it
        iters = _diag_iters(problem, telemetry)
        s = max(int(cand.knobs.get("sstep_s", 4)), 1)
        passes = iters * (
            PASSES_PER_DIAG_ITER - PASSES_PER_DIAG_REDUCE
            + (2.0 * s + 1.0) / s
        )
    else:
        # the diagonal-recurrence engines (the static-default family):
        # same iteration count, per-iteration byte bills differing only
        # in residency — modeled at the loop figure, which ranks them
        # conservatively AGAINST the iteration-count engines
        iters = _diag_iters(problem, telemetry)
        passes = iters * PASSES_PER_DIAG_ITER
    return passes, iters


def select(problem: Problem, telemetry: dict, dtype=jnp.float32,
           storage_dtype=None) -> tuple[TunedConfig, list[dict]]:
    """Score every candidate from the telemetry and pick the winner.

    Pure in the telemetry (determinism pin: same dict in, same config
    out). The static default anchors the comparison: a candidate must
    beat its predicted cost by :data:`SELECT_MARGIN`, so the tuner can
    only ever *match or improve* the static policy by construction —
    the in-model half of the never-loses acceptance (the measured half
    is ``measure=True`` below and the bench ``autotune`` gate).
    """
    g1, g2 = problem.node_shape
    array_gb = g1 * g2 * jnp.dtype(dtype).itemsize / 1e9
    gbps = telemetry.get("gbps") or FALLBACK_GBPS
    scored = []
    for cand in candidates(problem, dtype, storage_dtype):
        passes, iters = predicted_cost(problem, cand, telemetry, dtype)
        t_pred = passes * array_gb / gbps
        scored.append({
            "engine": cand.engine, "knobs": dict(cand.knobs),
            "predicted_iters": round(iters, 2),
            "predicted_passes": round(passes, 2),
            "predicted_t_s": t_pred,
        })
    default_row = scored[0]
    best = min(scored, key=lambda row: row["predicted_t_s"])
    if best["predicted_t_s"] > default_row["predicted_t_s"] * (
            1.0 - SELECT_MARGIN):
        best = default_row
    # the serve-layer knob rides the same entry: chunk sized to ~4
    # retire-and-refill boundaries per solve (granularity for
    # deadlines/refill vs per-chunk dispatch overhead), clamped to the
    # scheduler's sane band — consulted by Scheduler._ctx_for at
    # warm-pool admission. Sized from the DIAGONAL prediction, not the
    # winner's: the scheduler's lanes run the batched diag engine
    # regardless of the single-solve winner, and an fmg winner's ~2
    # handoff iterations would floor the chunk at 8 and double the
    # lanes' per-chunk host round-trips on a 546-iteration solve
    serve_chunk = int(min(128, max(
        8, round(_diag_iters(problem, telemetry) / 4)
    )))
    # the recycling verdict rides the DIAGONAL prediction pair, same
    # reasoning as the chunk: the scheduler's lanes run the batched diag
    # engine regardless of the single-solve winner, so the warm-start
    # payoff is the cold-vs-deflated gap of that engine, not the
    # winner's. Recycling must clear the same noise-floor margin a
    # candidate engine must — a marginal predicted cut keeps cold.
    cold_iters = _diag_iters(problem, telemetry)
    rec_iters = _recycled_iters(problem, telemetry)
    recycle = bool(
        rec_iters is not None
        and rec_iters < cold_iters * (1.0 - SELECT_MARGIN)
    )
    chosen = TunedConfig(
        engine=best["engine"], knobs=dict(best["knobs"], chunk=serve_chunk),
        predicted_iters=best["predicted_iters"],
        predicted_t_s=best["predicted_t_s"],
        static_engine=default_row["engine"],
        static_predicted_t_s=default_row["predicted_t_s"],
        recycle=recycle,
        predicted_iters_recycled=(
            None if rec_iters is None else round(rec_iters, 2)
        ),
    )
    return chosen, scored


# -- the closed loop ---------------------------------------------------------


def _measure_once(problem: Problem, engine: str, dtype, geometry=None,
                  theta=None, knobs: dict | None = None) -> float:
    """One warmed, fenced dispatch's wall clock (the tune-time check,
    not a benchmark: ``benchmark/`` owns the measured numbers).
    ``knobs`` is the candidate's knob dict: the measured configuration
    must BE the scored configuration (levels/ν/degrees/n_vcycles via
    ``tuned_knobs``, s via ``sstep_s``), or the persisted record would
    attest a wall clock the selected config never produced."""
    from poisson_ellipse_tpu.solver.engine import build_solver
    from poisson_ellipse_tpu.utils.timing import fence

    knobs = knobs or {}
    sstep_kwargs = (
        {"sstep_s": int(knobs["sstep_s"])} if "sstep_s" in knobs else {}
    )
    solver, args, _ = build_solver(problem, engine, dtype,
                                   geometry=geometry, theta=theta,
                                   tuned_knobs=knobs, **sstep_kwargs)
    fence(solver(*args))  # compile + warm-up, untimed
    t0 = time.perf_counter()
    # the sync IS the measurement — the bracket closes on device work
    fence(solver(*args))
    return time.perf_counter() - t0


def tune(problem: Problem, dtype=jnp.float32, storage_dtype=None,
         geometry=None, theta=None, registry: TuneRegistry | None = None,
         persist: bool = True, measure: bool = False,
         telemetry: dict | None = None) -> dict:
    """Run the closed loop for one shape: telemetry → score → select →
    (optionally measure) → persist. Returns the full report (the
    ``harness tune`` subcommand prints it; the measured columns are
    None unless ``measure=True``).

    ``telemetry`` overrides collection (replay/testing); ``registry``
    overrides the default persisted registry (tests use throwaways).
    With ``measure=True`` the chosen config and the static default are
    each wall-clocked once and a losing winner is DEMOTED to the
    default before persisting — a tuned registry can then only contain
    configs that beat (or are) the static default as measured on the
    tuning machine.
    """
    tel = telemetry if telemetry is not None else collect_telemetry(
        problem, dtype, geometry=geometry, theta=theta
    )
    chosen, scored = select(problem, tel, dtype, storage_dtype)
    key = tune_key(problem, dtype, storage_dtype=storage_dtype,
                   geometry=geometry)
    demoted = False
    if measure and chosen.engine != chosen.static_engine:
        t_tuned = _measure_once(problem, chosen.engine, dtype,
                                geometry=geometry, theta=theta,
                                knobs=chosen.knobs)
        t_static = _measure_once(problem, chosen.static_engine, dtype,
                                 geometry=geometry, theta=theta)
        if t_tuned > t_static:
            demoted = True
            chosen = dataclasses.replace(
                chosen, engine=chosen.static_engine, knobs={},
                measured_t_s=t_static, static_measured_t_s=t_static,
            )
        else:
            chosen = dataclasses.replace(
                chosen, measured_t_s=t_tuned, static_measured_t_s=t_static,
            )
    reg = registry if registry is not None else default_registry()
    if persist:
        reg.put(key, chosen)
        reg.save()
    obs_trace.event(
        "autotune:select", key=key, engine=chosen.engine,
        static_engine=chosen.static_engine, demoted=demoted,
        predicted_t_s=chosen.predicted_t_s,
        static_predicted_t_s=chosen.static_predicted_t_s,
        recycle=chosen.recycle,
        predicted_iters_recycled=chosen.predicted_iters_recycled,
    )
    return {
        "key": key,
        "telemetry": tel,
        "candidates": scored,
        "chosen": chosen.to_json(),
        "demoted_to_static": demoted,
        "registry_path": reg.path if persist else None,
    }
