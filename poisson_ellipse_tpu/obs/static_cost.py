"""Static cost accounting: collectives, FLOPs and HBM bytes from the jaxpr.

The perf properties this framework advertises are *structural* — the
pipelined sharded iteration issues ONE stacked ``psum`` where the
classical loop issues two; the halo exchange is four ``ppermute``s; an
iteration's HBM traffic is so-many array passes. Structural claims rot
silently unless they are read back from the compiled artifact itself.
This module does that reading, with no hardware in the loop:

- :func:`loop_primitive_counts` walks a function's jaxpr and counts the
  named primitives inside every ``while_loop`` body — the
  per-iteration count, by construction (branch arms of a ``lax.cond``
  inside the body count too: a static budget is an upper bound, and the
  residual-replacement branches deliberately add no collectives).
- :func:`xla_cost` asks XLA's HLO cost analysis for estimated FLOPs and
  bytes accessed. XLA analyses a ``while`` body once (the trip count is
  dynamic), so the computation total ≈ prologue + one iteration — the
  honest per-iteration estimate, labelled as such.
- :func:`engine_report` builds any engine through its real product
  entry point (``solver.engine.build_solver`` /
  ``parallel.pcg_sharded.build_sharded_solver``) and emits one record:
  psum/ppermute per iteration, estimated FLOPs/bytes, and the roofline
  traffic *model*'s passes/bytes side by side — the measured-vs-modeled
  columns ``harness inspect`` prints and BENCH artifacts carry.

The "pipelined = 1 psum/iter vs classical = 2" regression check lives on
top of this module (``tests/test_obs.py``, ``tests/test_pipelined.py``,
``bench.py``'s artifact) — one metric, asserted everywhere it matters,
instead of test-local jaxpr walks.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from poisson_ellipse_tpu.models.problem import Problem

# the jaxpr walk lives in analysis.jaxpr_scan (the contract matrix and
# this report read the SAME traversal); re-exported here because every
# cadence pin historically imports them from obs.static_cost
from poisson_ellipse_tpu.analysis.jaxpr_scan import (  # noqa: F401
    COLLECTIVE_PRIMS,
    count_primitives,
    loop_collectives,
    loop_primitive_counts,
    while_body_primitive_counts,
)

# derived from the ENGINE_CAPS contract metadata — an engine declares a
# sharded collective cadence iff it has a sharded form
from poisson_ellipse_tpu.solver.engine import SHARDED_ENGINES  # noqa: F401

# iterations advanced per while-loop body: the s-step engines run s
# iterations per body (matrix-powers block), every other engine runs 1.
# Collective counts read from a while body divide by this to become
# per-ITERATION figures — the denominator every cadence claim uses.
def iters_per_loop_body(engine: str, sstep_s: int = 4) -> int:
    return sstep_s if engine in ("sstep", "sstep-pallas") else 1


# -- XLA cost analysis -------------------------------------------------------


def xla_cost(fn, args) -> dict | None:
    """{"flops", "bytes_accessed"} from XLA's HLO cost analysis, or None
    when the backend does not expose one. A ``while`` body is analysed
    once (dynamic trip count), so these totals read as prologue + one
    iteration — the per-iteration estimate, not a whole-solve total."""
    try:
        # single-shot construction is the point: this jit exists only to
        # be lowered for its cost analysis, never dispatched
        compiled = jax.jit(fn).lower(*args).compile()  # tpulint: disable=TPU006
        analysis = compiled.cost_analysis()
    except Exception:  # tpulint: disable=TPU009 — introspection must never break a run
        return None
    if not isinstance(analysis, dict):
        return None
    flops = analysis.get("flops")
    bytes_accessed = analysis.get("bytes accessed")
    if flops is None and bytes_accessed is None:
        return None
    return {
        "flops": float(flops) if flops is not None else None,
        "bytes_accessed": (
            float(bytes_accessed) if bytes_accessed is not None else None
        ),
    }


# -- the compiled loop body --------------------------------------------------

_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>\S+) = (?P<type>.*?) (?P<op>[a-z][a-z0-9-]*)\("
)
_HLO_ARRAY = re.compile(r"^[a-z0-9]+\[(?P<dims>[0-9,]*)\]")
_HLO_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
RELAYOUT_OPS = ("copy", "pad", "transpose")
# the sharded PCG loop's name in the compiled program's op_name metadata
SHARDED_LOOP = "shard_map/while"
# a result this wide in every dimension is a whole block, not an edge
WHOLE_BLOCK_EXTENT = 1000


def loop_body_ops(hlo_text: str) -> list:
    """The top-level instructions of the compiled ``while`` body whose
    loop carries ``SHARDED_LOOP`` in its ``op_name`` metadata, read from
    ``compiled.as_text()``: one dict per instruction with ``name``,
    ``op`` (the opcode), ``dims`` (the result's extents; ``None`` for a
    tuple) and ``cycles`` (the TPU compiler's ``estimated_cycles``, 0
    where it gives none). Raises ``ValueError`` when no such loop is
    compiled in."""
    lines = hlo_text.splitlines()
    body = None
    for line in lines:
        if " while(" in line and f'{SHARDED_LOOP}"' in line:
            body = re.search(r"body=%([^\s,]+)", line).group(1)
            break
    if body is None:
        raise ValueError(
            f"no while loop named {SHARDED_LOOP!r} in the program")
    start = next(i for i, line in enumerate(lines)
                 if line.startswith(f"%{body} "))
    ops = []
    for line in lines[start + 1:]:
        if line.strip() == "}":
            break
        m = _HLO_INSTR.match(line)
        if m is None:
            continue
        arr = _HLO_ARRAY.match(m["type"])
        cycles = _HLO_CYCLES.search(line)
        ops.append({
            "name": m["name"],
            "op": m["op"],
            "dims": (tuple(int(d) for d in arr["dims"].split(",") if d)
                     if arr else None),
            "cycles": int(cycles.group(1)) if cycles else 0,
        })
    return ops


def whole_block_relayouts(ops: list) -> list:
    """The copies, pads and transposes among ``ops`` (``loop_body_ops``)
    whose result spans at least ``WHOLE_BLOCK_EXTENT`` in every
    dimension: the whole-block layout changes a loop body pays for every
    iteration."""
    return [o for o in ops
            if o["op"] in RELAYOUT_OPS and o["dims"]
            and min(o["dims"]) >= WHOLE_BLOCK_EXTENT]


# -- the per-engine report ---------------------------------------------------


def _build(problem: Problem, engine: str, dtype, mode: str, mesh_shape,
           storage_dtype=None, sstep_s: int = 4):
    """(fn, args) through the same entry points the product runs."""
    if mode == "single":
        from poisson_ellipse_tpu.solver.engine import build_solver

        solver, args, _ = build_solver(
            problem, engine, dtype, storage_dtype=storage_dtype,
            sstep_s=sstep_s,
        )
        return solver, args
    if mode == "sharded":
        from poisson_ellipse_tpu.harness.run import resolve_mesh
        from poisson_ellipse_tpu.parallel.pcg_sharded import build_sharded_solver

        if engine not in SHARDED_ENGINES:
            raise ValueError(
                f"engine {engine!r} is single-device only "
                f"(sharded engines: {', '.join(SHARDED_ENGINES)})"
            )
        mesh = resolve_mesh(mesh_shape)
        if engine == "sstep":
            from poisson_ellipse_tpu.parallel.sstep_sharded import (
                build_sstep_sharded_solver,
            )

            return build_sstep_sharded_solver(
                problem, mesh, dtype, s=sstep_s,
                storage_dtype=storage_dtype,
            )
        if storage_dtype is not None:
            raise ValueError(
                "sharded storage-dtype tracing covers the sstep engine; "
                "the classical/pipelined sharded forms run full width"
            )
        if engine in ("mg-pcg", "cheb-pcg"):
            from poisson_ellipse_tpu.parallel.mg_sharded import (
                build_mg_sharded_solver,
            )
            from poisson_ellipse_tpu.solver.engine import (
                PRECOND_KIND_BY_ENGINE,
            )

            return build_mg_sharded_solver(
                problem, mesh, dtype,
                kind=PRECOND_KIND_BY_ENGINE[engine],
            )
        if engine == "fmg":
            from poisson_ellipse_tpu.parallel.mg_sharded import (
                build_fmg_sharded_solver,
            )

            return build_fmg_sharded_solver(problem, mesh, dtype)
        solver, args = build_sharded_solver(
            problem, mesh, dtype, stencil_impl=engine
        )
        return solver, args
    raise ValueError(f"unknown mode: {mode!r} (single or sharded)")


def engine_report(
    problem: Problem,
    engine: str = "xla",
    dtype=jnp.float32,
    mode: str = "single",
    mesh_shape: tuple[int, int] | None = None,
    with_xla_cost: bool = True,
    storage_dtype=None,
    sstep_s: int = 4,
) -> dict:
    """One engine's static cost record.

    Keys: engine/mode/grid/dtype/mesh identification; per-iteration
    collective counts (``psum_per_iter``, ``ppermute_per_iter``, the
    full ``collectives_per_iter`` map); XLA-estimated
    ``flops_per_iter_est`` / ``hbm_bytes_per_iter_est`` (None when the
    backend exposes no cost analysis); and the roofline traffic model's
    ``modeled_passes_per_iter`` / ``modeled_hbm_bytes_per_iter`` for the
    measured-vs-modeled comparison.

    The s-step engines advance ``sstep_s`` iterations per loop body;
    their per-iteration counts divide the body counts by
    ``iters_per_body`` (reported, with the raw body counts kept in
    ``psum_per_body``/``ppermute_per_body`` — the jaxpr-pinned facts).
    ``storage_dtype`` reports the narrow-storage build: the modeled HBM
    bytes column shows the storage-width byte bill (the ~2× cut the
    bandwidth bench key measures end to end).
    """
    from poisson_ellipse_tpu.harness.roofline import (
        modeled_hbm_bytes_per_iter,
        passes_per_iter,
    )

    from poisson_ellipse_tpu.ops.precision import resolve_storage_dtype

    storage_dtype = resolve_storage_dtype(storage_dtype, dtype)
    fn, args = _build(problem, engine, dtype, mode, mesh_shape,
                      storage_dtype=storage_dtype, sstep_s=sstep_s)
    counts = loop_primitive_counts(fn, args)
    cost = xla_cost(fn, args) if with_xla_cost else None
    try:
        passes = passes_per_iter(problem, engine, dtype, sstep_s=sstep_s,
                                 storage_dtype=storage_dtype)
        modeled_bytes = modeled_hbm_bytes_per_iter(
            problem, engine, dtype, storage_dtype=storage_dtype,
            sstep_s=sstep_s,
        )
    except ValueError:  # an engine without a traffic model stays reportable
        passes, modeled_bytes = None, None
    # psum and its invariant-spelled twin are one collective on the wire
    psum = counts.get("psum", 0) + counts.get("psum_invariant", 0)
    per_body = iters_per_loop_body(engine, sstep_s)
    # Krylov-recycling footprint: engines whose contract row declares the
    # recycle cell (solver.engine.ENGINE_CAPS) report the modeled HBM
    # bytes of the default-capacity Lanczos ring. A MODEL only — the
    # ring is opt-in (pcg(recycle=cap)); the default build traced above
    # carries no ring, which is exactly why the psum/ppermute columns
    # are unchanged by it (the recycle contract cell's jaxpr-pinned fact)
    from poisson_ellipse_tpu.solver.engine import ENGINE_CAPS

    ring_bytes = None
    ring_cap = None
    if ENGINE_CAPS.get(engine, {}).get("contracts", {}).get("recycle"):
        from poisson_ellipse_tpu.solver.recycle import (
            RECYCLE_CAP,
            ring_model_bytes,
        )

        ring_cap = RECYCLE_CAP
        ring_bytes = ring_model_bytes(problem, cap=ring_cap, dtype=dtype)
    return {
        "engine": engine,
        "mode": mode,
        "grid": [problem.M, problem.N],
        "dtype": jnp.dtype(dtype).name,
        "storage_dtype": (
            jnp.dtype(storage_dtype).name if storage_dtype is not None
            else None
        ),
        "mesh": list(mesh_shape) if mesh_shape is not None else None,
        "iters_per_body": per_body,
        "psum_per_body": psum,
        "ppermute_per_body": counts.get("ppermute", 0),
        "psum_per_iter": psum / per_body if per_body > 1 else psum,
        "ppermute_per_iter": (
            counts.get("ppermute", 0) / per_body
            if per_body > 1 else counts.get("ppermute", 0)
        ),
        "collectives_per_iter": {
            k: v for k, v in {**counts, "psum": psum}.items()
            if v and k != "psum_invariant"
        },
        "flops_per_iter_est": cost["flops"] if cost else None,
        "hbm_bytes_per_iter_est": cost["bytes_accessed"] if cost else None,
        "modeled_passes_per_iter": passes,
        "modeled_hbm_bytes_per_iter": modeled_bytes,
        "recycle_ring_cap": ring_cap,
        "recycle_ring_model_bytes": ring_bytes,
    }


def collectives_table(
    problem: Problem,
    engines: tuple[str, ...] = ("xla", "pipelined"),
    dtype=jnp.float32,
    mesh_shape: tuple[int, int] = (1, 2),
) -> dict:
    """The BENCH-artifact collectives block: per-engine psum/ppermute
    counts on one mesh, cheap enough to ride every bench run (jaxpr
    trace only — no compile, no execution)."""
    rows = {}
    for engine in engines:
        rep = engine_report(
            problem, engine, dtype, mode="sharded", mesh_shape=mesh_shape,
            with_xla_cost=False,
        )
        rows[engine] = {
            "psum_per_iter": rep["psum_per_iter"],
            "ppermute_per_iter": rep["ppermute_per_iter"],
        }
    return {
        "available": True,
        "grid": [problem.M, problem.N],
        "mesh": list(mesh_shape),
        "engines": rows,
    }


def render_report(rep: dict) -> str:
    """Human-readable form of one :func:`engine_report` record (the
    ``harness inspect`` output)."""
    where = (
        f"sharded {rep['mesh'][0]}x{rep['mesh'][1]}"
        if rep["mode"] == "sharded" and rep["mesh"]
        else rep["mode"]
    )
    storage = rep.get("storage_dtype")
    lines = [
        f"engine {rep['engine']} ({where}), grid "
        f"{rep['grid'][0]}x{rep['grid'][1]}, dtype {rep['dtype']}"
        + (f" (storage {storage})" if storage else "")
        + ":",
        f"  psum/iter      {rep['psum_per_iter']:g}",
        f"  ppermute/iter  {rep['ppermute_per_iter']:g}",
    ]
    if rep.get("iters_per_body", 1) > 1:
        lines.append(
            f"  per while-body ({rep['iters_per_body']} iters): "
            f"{rep['psum_per_body']} psum, {rep['ppermute_per_body']} "
            "ppermute (the jaxpr-pinned s-step cadence)"
        )
    extra = {
        k: v
        for k, v in rep["collectives_per_iter"].items()
        if k not in ("psum", "psum_invariant", "ppermute")
    }
    for name, n in sorted(extra.items()):
        lines.append(f"  {name}/iter {' ' * max(0, 12 - len(name))}{n}")
    flops = rep["flops_per_iter_est"]
    hbm = rep["hbm_bytes_per_iter_est"]
    lines.append(
        "  est FLOPs/iter (XLA)     "
        + (f"{flops:.3e}" if flops is not None else "n/a")
    )
    lines.append(
        "  est HBM bytes/iter (XLA) "
        + (f"{hbm:.3e}" if hbm is not None else "n/a")
    )
    passes = rep["modeled_passes_per_iter"]
    modeled = rep["modeled_hbm_bytes_per_iter"]
    if passes is not None:
        lines.append(
            f"  modeled HBM bytes/iter   {modeled:.3e} "
            f"({passes:g} array passes, harness.roofline)"
        )
        if hbm:
            lines.append(
                f"  measured-vs-modeled      {hbm / modeled:.2f}x "
                "(XLA estimate / roofline model)"
            )
    ring = rep.get("recycle_ring_model_bytes")
    if ring is not None:
        lines.append(
            f"  recycle ring (opt-in)    {ring:.3e} bytes modeled "
            f"(cap {rep['recycle_ring_cap']} full grids, solver.recycle; "
            "loop psum/ppermute counts above are unchanged by it)"
        )
    return "\n".join(lines)
