"""obs/: on-device convergence history, JSONL tracing, static cost.

The observability layer's three contracts, each pinned:

- **History** — ``solve(..., history=True)`` returns the per-iteration
  (zr, diff, α, β) series recorded *inside* the fused while_loop; the
  buffers match a plain Python-loop replay of the recurrence exactly,
  the iterates are bit-identical with history on/off, and with history
  OFF the emitted jaxpr is exactly the historyless one (the feature
  costs zero when disabled).
- **Trace** — the JSONL emitter round-trips through its own validator;
  PhaseTimer is a shim over it; the report formatting guards its zero
  cases.
- **Static cost** — psum/ppermute per iteration read from the jaxpr via
  the product metric (``obs.static_cost``): classical sharded loop 2
  psum, pipelined 1, on a CPU mesh.
"""

from __future__ import annotations

import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs import metrics as obs_metrics
from poisson_ellipse_tpu.obs import trace as obs_trace
from poisson_ellipse_tpu.obs.convergence import HISTORY_FIELDS
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.ops.reduction import grid_dot, grid_dots
from poisson_ellipse_tpu.ops.stencil import apply_a, apply_dinv, diag_d
from poisson_ellipse_tpu.solver.pcg import DENOM_GUARD, pcg
from poisson_ellipse_tpu.solver.engine import solve as engine_solve


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Every test starts and ends with no ambient tracer and a clean
    default metrics registry (both are process-global by design)."""
    obs_trace.stop()
    obs_trace._env_checked = True  # tests control tracing explicitly
    obs_metrics.REGISTRY.reset()
    yield
    obs_trace.stop()
    obs_metrics.REGISTRY.reset()


# ------------------------------------------------------- history: values


def python_reference_trajectory(problem: Problem, a, b, rhs):
    """The classical recurrence replayed as a plain eager Python loop —
    the textbook form of ``solver.pcg.advance``'s body, with loop
    control, convergence decision and recording all on the HOST (the
    structure the on-device buffers replace)."""
    dtype = rhs.dtype
    h1 = jnp.asarray(problem.h1, dtype)
    h2 = jnp.asarray(problem.h2, dtype)
    delta = float(problem.delta)
    weighted = problem.norm == "weighted"
    d = diag_d(a, b, h1, h2)
    r = rhs
    z = apply_dinv(r, d)
    p = z
    zr = grid_dot(z, r, h1, h2)
    w = jnp.zeros_like(rhs)
    rows = {name: [] for name in HISTORY_FIELDS}
    for _k in range(problem.max_iterations):
        ap = apply_a(p, a, b, h1, h2)
        denom = grid_dot(ap, p, h1, h2)
        assert float(denom) >= DENOM_GUARD, "reference replay hit breakdown"
        alpha = zr / denom
        w_new = w + alpha * p
        r_new = r - alpha * ap
        z = apply_dinv(r_new, d)
        dw = w_new - w
        sums = grid_dots((z, r_new), (dw, dw))
        zr_new = sums[0] * h1 * h2
        diff = jnp.sqrt(sums[1] * h1 * h2) if weighted else jnp.sqrt(sums[1])
        beta = zr_new / zr
        for name, val in zip(HISTORY_FIELDS, (zr_new, diff, alpha, beta)):
            rows[name].append(float(val))
        if float(diff) < delta:  # the host-side convergence decision
            break
        w, r, p, zr = w_new, r_new, z + beta * p, zr_new
    return {name: np.asarray(vals) for name, vals in rows.items()}


def test_history_matches_python_loop_reference():
    """Two references, two strengths of claim.

    (1) *Bit-exact* against a host-driven replay through the same
    compiled loop body: ``advance(limit=k)`` one iteration per dispatch
    (the chunking contract — chunking moves the while_loop boundary, not
    the arithmetic), harvesting zr/diff from the returned carries and β
    as the IEEE quotient of consecutive carried zr values. This proves
    the buffers record THE loop's values, not a reconstruction.

    (2) Within f64 round-off of the textbook eager Python replay for all
    four series (separately compiled computations may fuse reductions
    differently, so cross-compilation bit-equality is not a meaningful
    target — 1e-12 relative is)."""
    from poisson_ellipse_tpu.solver.pcg import advance, init_state

    problem = Problem(M=20, N=20)
    a, b, rhs = assembly.assemble(problem, jnp.float64)
    result, trace = pcg(problem, a, b, rhs, history=True)
    assert bool(result.converged)
    n = int(result.iters)
    got = trace.valid()

    # (1) host-driven replay, bit-exact
    state = init_state(problem, a, b, rhs)
    zr_carry = [float(state[4])]  # zr entering iteration k
    host_diff = []
    for k in range(1, n + 1):
        state = advance(problem, a, b, rhs, state, limit=k)
        host_diff.append(float(state[5]))
        zr_carry.append(float(state[4]))
    assert int(state[0]) == n and bool(state[6])
    np.testing.assert_array_equal(got["diff"], np.asarray(host_diff))
    # the terminal iteration freezes zr in the carry (the trace records
    # the raw zr_new); every non-terminal entry must match bitwise
    np.testing.assert_array_equal(
        got["zr"][:-1], np.asarray(zr_carry[1:n])
    )
    host_beta = np.asarray(
        [zr_carry[k + 1] / zr_carry[k] for k in range(n - 1)]
    )
    np.testing.assert_array_equal(got["beta"][:-1], host_beta)

    # (2) textbook eager replay, to f64 round-off
    want = python_reference_trajectory(problem, a, b, rhs)
    assert n == len(want["zr"])
    for name in HISTORY_FIELDS:
        np.testing.assert_allclose(
            got[name], want[name], rtol=1e-12, err_msg=name
        )
    # past-the-end entries stay zero (preallocated, never touched)
    tail = np.asarray(trace.zr)[n:]
    assert tail.size and not tail.any()


def test_history_off_is_bitwise_identical_and_free():
    """history=False must (a) be the default, (b) emit EXACTLY the same
    jaxpr as the default path — no dynamic_update_slice, original
    8-tuple carry (the declared ``history-free`` contract) — and (c)
    history=True must not perturb one bit of the iterates."""
    from poisson_ellipse_tpu.analysis.contracts import assert_contract

    problem = Problem(M=20, N=20)
    assert_contract(
        "history-free", "xla", problem=problem, dtype=jnp.float64
    )

    a, b, rhs = assembly.assemble(problem, jnp.float64)
    plain = pcg(problem, a, b, rhs)
    traced, _ = pcg(problem, a, b, rhs, history=True)
    np.testing.assert_array_equal(np.asarray(plain.w), np.asarray(traced.w))
    assert int(plain.iters) == int(traced.iters)
    assert float(plain.diff) == float(traced.diff)


def test_history_on_stays_device_resident():
    """The recording path must be pure array ops: no callback primitives,
    no device_get — 'zero extra host syncs' as a structural property
    (the declared ``history-resident`` contract)."""
    from poisson_ellipse_tpu.analysis.contracts import assert_contract

    assert_contract(
        "history-resident", "xla", problem=Problem(M=10, N=10),
        dtype=jnp.float64,
    )


# ------------------------------------------------------ history: engines


@pytest.mark.parametrize(
    "engine", ["xla", "pallas", "fused", "pipelined", "pipelined-pallas"]
)
def test_history_on_every_single_chip_engine(engine):
    """Every XLA-loop engine returns (PCGResult, ConvergenceTrace) with
    a self-consistent trace: the final recorded diff is the solver's own
    diff (the trace records the loop, not a reconstruction), and the
    converged iteration's step-norm is below δ."""
    problem = Problem(M=20, N=20)
    plain = engine_solve(problem, engine, jnp.float32)
    result, trace = engine_solve(problem, engine, jnp.float32, history=True)
    np.testing.assert_array_equal(np.asarray(plain.w), np.asarray(result.w))
    assert int(plain.iters) == int(result.iters)
    v = trace.valid()
    n = int(result.iters)
    assert all(v[name].shape == (n,) for name in HISTORY_FIELDS)
    assert v["diff"][-1] == float(result.diff)
    assert v["diff"][-1] < problem.delta
    assert np.isfinite(v["zr"]).all() and (v["zr"] > 0).all()


def test_history_breakdown_records_zero_alpha():
    """A breakdown iteration applies no update, so every engine's trace
    records α = 0 for it — identical telemetry for the identical event
    (the fused kernel's in-kernel guard and the XLA loops' recording
    must not disagree)."""
    from poisson_ellipse_tpu.ops.fused_pcg import pcg_fused
    from poisson_ellipse_tpu.ops.pipelined_pcg import pcg_pipelined

    problem = Problem(M=10, N=10)
    _, _, rhs = assembly.assemble(problem, jnp.float64)
    zeros = jnp.zeros_like(rhs)
    for fn in (pcg, pcg_pipelined):
        result, trace = fn(problem, zeros, zeros, rhs, history=True)
        assert bool(result.breakdown) and int(result.iters) == 1, fn
        assert float(trace.alpha[0]) == 0.0, fn
    rhs32 = rhs.astype(jnp.float32)
    z32 = jnp.zeros_like(rhs32)
    result, trace = pcg_fused(problem, z32, z32, rhs32, history=True)
    assert bool(result.breakdown) and float(trace.alpha[0]) == 0.0


def test_history_on_sharded_engine():
    from poisson_ellipse_tpu.parallel.mesh import make_mesh
    from poisson_ellipse_tpu.parallel.pcg_sharded import solve_sharded

    problem = Problem(M=20, N=20)
    mesh = make_mesh(jax.devices()[:2])
    plain = solve_sharded(problem, mesh, jnp.float64)
    result, trace = solve_sharded(problem, mesh, jnp.float64, history=True)
    np.testing.assert_array_equal(np.asarray(plain.w), np.asarray(result.w))
    assert int(plain.iters) == int(result.iters)
    v = trace.valid()
    assert v["diff"][-1] == float(result.diff)
    # the sharded trace must equal the single-chip one bit for bit: the
    # psum-reduced scalars are the same values the single loop computes
    _, single = pcg(
        problem, *assembly.assemble(problem, jnp.float64), history=True
    )
    sv = single.valid()
    for name in HISTORY_FIELDS:
        np.testing.assert_allclose(
            v[name], sv[name], rtol=1e-12, err_msg=name
        )


def test_history_unsupported_engines_fail_loudly_and_auto_degrades():
    from poisson_ellipse_tpu.solver.engine import build_solver

    problem = Problem(M=10, N=10)
    with pytest.raises(ValueError, match="history"):
        build_solver(problem, "resident", jnp.float32, history=True)
    _, _, resolved = build_solver(problem, "auto", jnp.float32, history=True)
    assert resolved == "xla"
    with pytest.raises(ValueError, match="history"):
        from poisson_ellipse_tpu.parallel.pcg_sharded import (
            build_sharded_solver,
        )

        build_sharded_solver(
            problem, stencil_impl="pipelined", history=True
        )


# ------------------------------------------------------------- trace


def test_trace_jsonl_roundtrips_and_validates(tmp_path):
    path = tmp_path / "run.jsonl"
    tracer = obs_trace.start(path)
    with obs_trace.span("phase:init", grid="20x20"):
        pass
    obs_trace.event("run_report", iters=26, converged=True)
    obs_metrics.counter("runs").inc()
    obs_metrics.gauge("last_iters").set(26)
    obs_metrics.REGISTRY.emit()
    run_id = tracer.run_id
    obs_trace.stop()

    records = obs_trace.read_jsonl(path)
    assert obs_trace.validate_file(path) == []
    kinds = [r["kind"] for r in records]
    assert kinds == ["meta", "span", "event", "counter", "gauge"]
    assert all(r["run"] == run_id for r in records)
    span = records[1]
    assert span["name"] == "phase:init" and span["dur"] >= 0
    assert span["fields"] == {"grid": "20x20"}
    assert records[3] == {
        "v": obs_trace.SCHEMA_VERSION, "run": run_id, "t": records[3]["t"],
        "kind": "counter", "name": "runs", "value": 1.0,
    }


def test_trace_validator_rejects_malformed_records():
    ok = {"v": 1, "run": "r1", "t": 0.5, "kind": "event", "name": "x"}
    assert obs_trace.validate_record(ok) is None
    # v1 (pre-lane), v2 (lane) and v3 (request_id) records all validate
    assert obs_trace.validate_record({**ok, "v": 2}) is None
    assert obs_trace.validate_record({**ok, "v": 3}) is None
    bad = [
        ({**ok, "kind": "bogus"}, "kind"),
        ({k: v for k, v in ok.items() if k != "run"}, "run"),
        ({**ok, "v": 99}, "version"),
        ({**ok, "t": -1}, "t must"),
        ({**ok, "extra": 1}, "unknown"),
        ({**ok, "kind": "span"}, "dur"),
        ({**ok, "kind": "gauge"}, "value"),
        ({**ok, "fields": [1]}, "fields"),
        ({**ok, "lane": -1}, "lane"),
        ({**ok, "lane": 1.5}, "lane"),
        ({**ok, "lane": True}, "lane"),
        ({**ok, "request_id": ""}, "request_id"),
        ({**ok, "request_id": 7}, "request_id"),
        ("not a dict", "object"),
    ]
    for rec, needle in bad:
        err = obs_trace.validate_record(rec)
        assert err is not None and needle in err, (rec, err)


def test_lane_addressed_events_validate_first_class(tmp_path):
    """The batched driver's quarantine events carry ``lane`` as a
    top-level schema key (v2), not a permissive fields poke — a lane
    filter needs no JSON spelunking, and the validator checks it."""
    ok = {"v": 2, "run": "r1", "t": 0.5, "kind": "event",
          "name": "recovery:lane-quarantine", "lane": 3}
    assert obs_trace.validate_record(ok) is None
    path = tmp_path / "lane.jsonl"
    obs_trace.start(path)
    obs_trace.event("recovery:lane-quarantine", lane=2, detail="lane 2")
    obs_trace.event("unaddressed")  # lane stays optional
    obs_trace.stop()
    assert obs_trace.validate_file(path) == []
    recs = obs_trace.read_jsonl(path)
    assert recs[1]["lane"] == 2 and "lane" not in recs[2]


def test_request_addressed_events_validate_first_class(tmp_path):
    """The serve scheduler's lifecycle events carry ``request_id`` as a
    top-level schema key (v3): one request's whole story — admit,
    refill, retire, shed, retry, replay — greps out of a mixed stream
    with no fields poke, and the validator checks the key's shape."""
    ok = {"v": 3, "run": "r1", "t": 0.5, "kind": "event",
          "name": "serve:admit", "request_id": "req-0001"}
    assert obs_trace.validate_record(ok) is None
    path = tmp_path / "request.jsonl"
    obs_trace.start(path)
    obs_trace.event("serve:admit", request_id="req-7", depth=3)
    obs_trace.event("serve:refill", request_id="req-7", lane=1)
    obs_trace.event("unaddressed")  # request_id stays optional
    obs_trace.stop()
    assert obs_trace.validate_file(path) == []
    recs = obs_trace.read_jsonl(path)
    assert recs[1]["request_id"] == "req-7"
    # lane and request_id compose on one record (refill names both)
    assert recs[2]["request_id"] == "req-7" and recs[2]["lane"] == 1
    assert "request_id" not in recs[3]


def test_histogram_window_occupancy_staleness_guard(tmp_path):
    """The stalled-server guard (ISSUE 7 satellite): window occupancy
    rides next to the quantiles in the summary, the OpenMetrics
    rendering and the trace emit — so a frozen p99 with a full window
    and a non-advancing count reads as a stall, not a quiet server."""
    from poisson_ellipse_tpu.obs import export as obs_export

    h = obs_metrics.Histogram("lat")
    assert h.window_occupancy == 0
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    assert h.window_occupancy == 3
    assert h.summary()["window"] == 3
    # occupancy saturates at the window bound while count keeps moving
    for _ in range(obs_metrics.HISTOGRAM_WINDOW + 5):
        h.observe(0.0)
    assert h.window_occupancy == obs_metrics.HISTOGRAM_WINDOW
    assert h.count == 3 + obs_metrics.HISTOGRAM_WINDOW + 5

    # OpenMetrics: the `<name>_window` sample renders inside the summary
    # family and round-trips through the parser/validator
    reg = obs_metrics.MetricsRegistry()
    reg.histogram("solve_seconds").observe(0.5)
    text = obs_export.render_openmetrics(reg.snapshot())
    assert obs_export.validate_openmetrics(text) == []
    assert "poisson_solve_seconds_window 1" in text
    parsed = obs_export.parse_openmetrics(text)
    assert parsed["histograms"]["poisson_solve_seconds"]["window"] == 1.0

    # the trace emit publishes the occupancy gauge
    path = tmp_path / "window.jsonl"
    tracer = obs_trace.start(path)
    reg.emit(tracer)
    obs_trace.stop()
    names = {
        (r["kind"], r["name"]) for r in obs_trace.read_jsonl(path)
    }
    assert ("gauge", "solve_seconds_window") in names


def test_batched_driver_emits_lane_on_quarantine_events(tmp_path):
    from poisson_ellipse_tpu.batch import solve_batched
    from poisson_ellipse_tpu.resilience import FaultPlan, inject_nan

    problem = Problem(M=10, N=10)
    path = tmp_path / "quarantine.jsonl"
    obs_trace.start(path)
    try:
        guarded = solve_batched(
            problem, 3, "batched", jnp.float32, chunk=4,
            faults=FaultPlan(inject_nan(4, "r", lane=1)),
        )
    finally:
        obs_trace.stop()
    assert list(np.asarray(guarded.result.quarantined)) == [
        False, True, False,
    ]
    assert obs_trace.validate_file(path) == []
    quar = [
        r for r in obs_trace.read_jsonl(path)
        if r["name"] == "recovery:lane-quarantine"
    ]
    assert quar and quar[0]["lane"] == 1


def test_trace_inactive_is_a_noop_and_env_activates(tmp_path, monkeypatch):
    # inactive: span/event/note must not raise and must not write
    with obs_trace.span("phase:x"):
        pass
    obs_trace.event("nothing")
    err = io.StringIO()
    obs_trace.note("hello", file=err)
    assert err.getvalue() == "hello\n"
    # POISSON_TRACE starts a tracer lazily on first active() lookup
    path = tmp_path / "env.jsonl"
    monkeypatch.setenv(obs_trace.ENV_VAR, str(path))
    obs_trace._env_checked = False
    obs_trace.event("from-env", x=1)
    obs_trace.stop()
    names = [r["name"] for r in obs_trace.read_jsonl(path)]
    assert names == ["trace-start", "from-env"]


def test_note_emits_structured_twin_when_tracing(tmp_path, capsys):
    path = tmp_path / "note.jsonl"
    obs_trace.start(path)
    obs_trace.note("  40x40: converged", row=1)
    obs_trace.stop()
    assert "40x40: converged" in capsys.readouterr().err
    recs = obs_trace.read_jsonl(path)
    assert recs[-1]["fields"] == {"message": "  40x40: converged", "row": 1}


def test_metrics_registry_snapshot_and_kind_collisions():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("a").inc()
    reg.counter("a").inc(2)
    reg.gauge("b").set(7)
    assert reg.snapshot() == {
        "counters": {"a": 3.0}, "gauges": {"b": 7.0}, "histograms": {},
    }
    with pytest.raises(ValueError, match="already a counter"):
        reg.gauge("a")
    with pytest.raises(ValueError, match="already a counter"):
        reg.histogram("a")
    with pytest.raises(ValueError, match="already a gauge"):
        reg.counter("b")
    with pytest.raises(ValueError, match="cannot decrease"):
        reg.counter("a").inc(-1)
    assert reg.gauge("unset") and reg.snapshot()["gauges"] == {"b": 7.0}


def test_metrics_snapshot_is_name_sorted_not_creation_ordered():
    reg = obs_metrics.MetricsRegistry()
    for name in ("zeta", "alpha", "mid"):
        reg.counter(name).inc()
        reg.gauge(f"g_{name}").set(1)
        reg.histogram(f"h_{name}").observe(0.5)
    snap = reg.snapshot()
    assert list(snap["counters"]) == ["alpha", "mid", "zeta"]
    assert list(snap["gauges"]) == ["g_alpha", "g_mid", "g_zeta"]
    assert list(snap["histograms"]) == ["h_alpha", "h_mid", "h_zeta"]


def test_histogram_quantiles_and_window():
    h = obs_metrics.Histogram("t")
    assert h.quantile(0.5) is None
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100 and h.sum == 5050.0
    assert h.quantile(0.5) == 51.0  # nearest-rank over the window
    assert h.quantile(0.9) == 91.0
    assert h.quantile(0.99) == 100.0
    s = h.summary()
    assert s["count"] == 100 and s["p50"] == 51.0 and s["p99"] == 100.0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    # the window is bounded: count/sum stay lifetime totals
    for v in range(obs_metrics.HISTOGRAM_WINDOW + 10):
        h.observe(0.0)
    assert h.count == 100 + obs_metrics.HISTOGRAM_WINDOW + 10
    assert len(h._window) == obs_metrics.HISTOGRAM_WINDOW
    assert h.quantile(0.99) == 0.0  # old observations aged out


def test_metrics_emit_guards_closed_tracer_and_publishes_histograms(tmp_path):
    path = tmp_path / "metrics.jsonl"
    tracer = obs_trace.start(path)
    reg = obs_metrics.MetricsRegistry()
    reg.counter("runs").inc()
    reg.histogram("lat").observe(2.0)
    reg.emit(tracer)
    obs_trace.stop()
    # a late emit into the closed tracer is a no-op, not a ValueError
    assert tracer.closed
    reg.emit(tracer)
    recs = obs_trace.read_jsonl(path)
    assert obs_trace.validate_file(path) == []
    names = {(r["kind"], r["name"]) for r in recs}
    assert ("counter", "runs") in names
    assert ("counter", "lat_count") in names
    assert ("gauge", "lat_p50") in names and ("gauge", "lat_sum") in names


# ---------------------------------------------------------- export


def test_openmetrics_renders_and_roundtrips_through_validator():
    from poisson_ellipse_tpu.obs import export as obs_export

    reg = obs_metrics.MetricsRegistry()
    reg.counter("runs").inc(3)
    reg.gauge("last_iters").set(546)
    for v in (0.001, 0.002, 0.004):
        reg.histogram("solve_seconds").observe(v)
    snap = reg.snapshot()
    text = obs_export.render_openmetrics(snap)
    assert obs_export.validate_openmetrics(text) == []
    assert text.endswith("# EOF\n")
    assert "# TYPE poisson_runs counter" in text
    assert "poisson_runs_total 3" in text
    assert 'poisson_solve_seconds{quantile="0.5"} 0.002' in text
    parsed = obs_export.parse_openmetrics(text)
    assert parsed["counters"] == {"poisson_runs": 3.0}
    assert parsed["gauges"] == {"poisson_last_iters": 546.0}
    hist = parsed["histograms"]["poisson_solve_seconds"]
    assert hist["count"] == 3.0 and hist["p50"] == 0.002
    # determinism: same registry renders byte-identically
    assert obs_export.render_openmetrics(reg.snapshot()) == text


def test_openmetrics_validator_rejects_malformed_expositions():
    from poisson_ellipse_tpu.obs import export as obs_export

    assert obs_export.validate_openmetrics("junk line\n# EOF\n")
    assert obs_export.validate_openmetrics("# TYPE x counter\nx_total 1\n")
    assert obs_export.validate_openmetrics(
        "x_total 1\n# TYPE x counter\n# EOF\n"
    )  # sample precedes its TYPE
    assert obs_export.validate_openmetrics(
        "# TYPE x counter\nx_total nan-ish\n# EOF\n"
    )
    assert obs_export.validate_openmetrics(
        "# TYPE x counter\n# TYPE x counter\nx_total 1\n# EOF\n"
    )
    # odd metric names sanitize into the grammar instead of failing
    assert obs_export.metric_name("95th %ile latency!", "p") == \
        "p_95th__ile_latency_"


def test_metrics_exporter_writes_atomic_snapshots(tmp_path):
    from poisson_ellipse_tpu.obs import export as obs_export

    reg = obs_metrics.MetricsRegistry()
    reg.counter("writes").inc()
    path = tmp_path / "metrics.prom"
    exporter = obs_export.MetricsExporter(path, registry=reg)
    assert exporter.write() == str(path)
    text = path.read_text()
    assert obs_export.validate_openmetrics(text) == []
    assert "poisson_writes_total 1" in text
    # no temp droppings next to the snapshot
    assert [p.name for p in tmp_path.iterdir()] == ["metrics.prom"]
    with pytest.raises(ValueError, match="interval_s"):
        exporter.start()
    # Event.wait(0) returns immediately: a zero cadence would busy-spin
    with pytest.raises(ValueError, match="positive"):
        obs_export.MetricsExporter(path, registry=reg, interval_s=0).start()
    # periodic mode: the context manager flushes at exit at minimum
    reg.counter("writes").inc()
    with obs_export.MetricsExporter(
        path, registry=reg, interval_s=30.0
    ):
        pass
    assert "poisson_writes_total 2" in path.read_text()


# ---------------------------------------------------------- PhaseTimer


def test_phase_timer_report_zero_guard_and_stable_order():
    from poisson_ellipse_tpu.utils.timing import PhaseTimer

    t = PhaseTimer()
    assert t.report() == ""  # 0 phases: renders, no division
    t.add("solver", 0.0)
    t.add("init", 0.0)
    zero = t.report()
    assert "0.0%" in zero  # 0-second total: guarded percentage
    # name-sorted, not insertion-sorted: diffs cleanly across runs
    assert zero.index("T_init") < zero.index("T_solver")
    t.add("solver", 3.0)
    t.add("init", 1.0)
    lines = t.report().splitlines()
    assert "25.0%" in lines[0] and "75.0%" in lines[1]


def test_phase_timer_is_a_trace_shim(tmp_path):
    """A ``phase()`` region is a live span; a duration measured
    elsewhere and ``add()``-ed has no start to claim, so it is an
    event carrying its seconds."""
    from poisson_ellipse_tpu.utils.timing import PhaseTimer

    path = tmp_path / "phases.jsonl"
    obs_trace.start(path)
    t = PhaseTimer()
    with t.phase("init"):
        pass
    t.add("solver", 1.5)
    obs_trace.stop()
    recs = obs_trace.read_jsonl(path)
    phases = {r["name"]: r for r in recs if r["name"].startswith("phase:")}
    assert set(phases) == {"phase:init", "phase:solver"}
    assert phases["phase:init"]["kind"] == "span"
    assert phases["phase:init"]["dur"] >= 0
    assert phases["phase:solver"]["kind"] == "event"
    assert phases["phase:solver"]["fields"] == {"seconds": 1.5}
    assert "dur" not in phases["phase:solver"]
    assert set(t.totals) == {"init", "solver"} and t.totals["solver"] == 1.5
    assert obs_trace.validate_file(path) == []


# ------------------------------------------------------- program spans


SERVE_SPANS = ("serve.step", "serve.refill", "serve.advance",
               "serve.boundary", "serve.flags", "serve.retire")


def _profiled_spans(trace_dir) -> dict:
    """The program's spans in the newest profiler trace under
    ``trace_dir``, by name without the prefix: [(start_ns, end_ns,
    arguments)]."""
    import collections
    import glob
    import os

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(obs_trace.PROFILER_PREFIX):
                    name = e.name[len(obs_trace.PROFILER_PREFIX):]
                    spans[name].append(
                        (e.start_ns, e.end_ns, dict(e.stats)))
    return spans


def _inside(span, parents) -> bool:
    return any(s <= span[0] and span[1] <= e for s, e, _ in parents)


def test_program_spans_nest_on_the_profiler_clock(tmp_path, monkeypatch):
    """Three requests through the scheduler and one ``auto`` build under
    a profiler trace: every span the serving and build paths open lands
    on the profiler's clock, prefixed, nested as the code brackets them
    and addressed to its request."""
    from poisson_ellipse_tpu.serve import Scheduler
    from poisson_ellipse_tpu.solver.engine import build_solver

    sched = Scheduler(lanes=2, chunk=8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            assert sched.submit(Problem(M=20, N=20),
                                request_id=f"r{i}") is None
        results = sched.drain()
        # auto's AOT probe runs on a TPU backend only
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            _, _, engine = build_solver(Problem(M=20, N=20), "auto",
                                        interpret=True)
    finally:
        jax.profiler.stop_trace()
    assert engine == "resident"
    assert {r.outcome for r in results.values()} == {"completed"}

    spans = _profiled_spans(tmp_path)
    assert set(spans) == {*SERVE_SPANS, "assembly.host", "engine.build",
                          "engine.lower", "engine.compile"}
    for name in SERVE_SPANS[1:]:
        assert all(_inside(s, spans["serve.step"]) for s in spans[name])
    assert all(_inside(s, spans["serve.boundary"])
               for s in spans["serve.retire"])
    for name in ("engine.lower", "engine.compile"):
        assert all(_inside(s, spans["engine.build"]) for s in spans[name])
    ids = ["r0", "r1", "r2"]
    assert sorted(a["request_id"] for *_, a in spans["serve.refill"]) == ids
    assert sorted(a["request_id"] for *_, a in spans["serve.retire"]) == ids
    assert all(a["outcome"] == "converged" and "lane" in a
               for *_, a in spans["serve.retire"])
    # one build span for the call, not one per rung of auto's ladder;
    # each refill assembles once, the resident build twice
    assert len(spans["engine.build"]) == 1
    assert len(spans["assembly.host"]) == 3 + 2


def test_xl_build_span_carries_its_plan(tmp_path):
    """An xl build's ``engine.build`` span says whether its plan keeps z
    in VMEM, and at which tile: in the JSONL record and on the profiler's
    clock."""
    from poisson_ellipse_tpu.solver.engine import build_solver

    sink = io.StringIO()
    obs_trace.start(sink)
    jax.profiler.start_trace(str(tmp_path))
    try:
        build_solver(Problem(M=40, N=40), "xl", interpret=True)
    finally:
        jax.profiler.stop_trace()
        obs_trace.stop()
    recs = [json.loads(line) for line in sink.getvalue().splitlines()]
    (build,) = [r for r in recs if r["name"] == "engine.build"]
    assert build["fields"] == {"engine": "xl", "z_resident": True, "tm": 48}
    (span,) = _profiled_spans(tmp_path)["engine.build"]
    # the profiler keeps a bool as the integer 1
    assert span[2] == {"engine": "xl", "z_resident": 1, "tm": 48}


def test_flag_reads_are_one_span_inside_each_boundary(tmp_path):
    """On a mesh, where each flag read gathers from every chip: one
    ``serve.flags`` span inside every ``serve.boundary``."""
    from poisson_ellipse_tpu.parallel.mesh import make_mesh
    from poisson_ellipse_tpu.serve import Scheduler

    sched = Scheduler(lanes=4, chunk=8, mesh=make_mesh(jax.devices()[:4]))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(6):
            assert sched.submit(Problem(M=20, N=20),
                                request_id=f"r{i}") is None
        results = sched.drain()
    finally:
        jax.profiler.stop_trace()
    assert {r.outcome for r in results.values()} == {"completed"}
    spans = _profiled_spans(tmp_path)
    assert len(spans["serve.boundary"]) > 1
    for start, end, _ in spans["serve.boundary"]:
        inside = [s for s in spans["serve.flags"]
                  if start <= s[0] and s[1] <= end]
        assert len(inside) == 1
    assert len(spans["serve.flags"]) == len(spans["serve.boundary"])


def test_span_is_one_annotation_unless_a_tracer_is_active(tmp_path):
    """With no tracer a span is the profiler annotation alone; with one,
    each span is also a schema-valid record, its request and lane
    first-class; after ``stop()`` nothing more reaches the sink."""
    from poisson_ellipse_tpu.serve import Scheduler

    assert obs_trace.active() is None
    assert type(obs_trace.span("serve.refill", request_id="r0", lane=1)) \
        is jax.profiler.TraceAnnotation

    path = tmp_path / "spans.jsonl"
    obs_trace.start(path)
    try:
        sched = Scheduler(lanes=2, chunk=8)
        assert sched.submit(Problem(M=20, N=20), request_id="r0") is None
        sched.drain()
    finally:
        obs_trace.stop()
    recs = obs_trace.read_jsonl(path)
    assert obs_trace.validate_file(path) == []
    spans = [r for r in recs if r["kind"] == "span"]
    assert {r["name"] for r in spans} == {*SERVE_SPANS, "assembly.host"}
    (refill,) = [r for r in spans if r["name"] == "serve.refill"]
    (retire,) = [r for r in spans if r["name"] == "serve.retire"]
    assert (refill["request_id"], refill["lane"]) == ("r0", 0)
    assert "fields" not in refill
    assert (retire["request_id"], retire["lane"]) == ("r0", 0)
    assert retire["fields"] == {"outcome": "converged"}

    with obs_trace.span("serve.step"):
        pass
    assert len(obs_trace.read_jsonl(path)) == len(recs)


# ---------------------------------------------------------- static cost


def test_static_cost_classical_two_psum_pipelined_one():
    """THE metric: classical sharded loop = 2 psum/iter, pipelined = 1,
    on a 1×2 CPU mesh — the same engine_report record harness inspect
    prints and bench.py's artifact asserts."""
    from poisson_ellipse_tpu.obs.static_cost import engine_report

    problem = Problem(M=20, N=20)
    classical = engine_report(
        problem, "xla", mode="sharded", mesh_shape=(1, 2), with_xla_cost=False
    )
    pipelined = engine_report(
        problem, "pipelined", mode="sharded", mesh_shape=(1, 2),
        with_xla_cost=False,
    )
    assert classical["psum_per_iter"] == 2
    assert pipelined["psum_per_iter"] == 1
    assert classical["ppermute_per_iter"] == 4  # the halo ring
    assert classical["collectives_per_iter"] == {"psum": 2, "ppermute": 4}


def test_static_cost_single_chip_and_modeled_columns():
    from poisson_ellipse_tpu.obs.static_cost import engine_report

    problem = Problem(M=20, N=20)
    rep = engine_report(problem, "xla", mode="single")
    assert rep["psum_per_iter"] == 0 and rep["ppermute_per_iter"] == 0
    assert rep["modeled_passes_per_iter"] == 13.0
    g1, g2 = problem.node_shape
    assert rep["modeled_hbm_bytes_per_iter"] == 13.0 * g1 * g2 * 4
    # CPU XLA exposes a cost analysis: the measured-vs-modeled column
    # exists (values are backend estimates, only presence is pinned)
    assert rep["flops_per_iter_est"] is None or rep["flops_per_iter_est"] > 0


def test_collectives_table_shape():
    from poisson_ellipse_tpu.obs.static_cost import collectives_table

    t = collectives_table(Problem(M=20, N=20))
    assert t["available"] is True and t["mesh"] == [1, 2]
    assert t["engines"]["xla"]["psum_per_iter"] == 2
    assert t["engines"]["pipelined"]["psum_per_iter"] == 1


_LOOP_HLO = """\
%body.3 (param.0: (s32[], f32[2049,2049])) -> (s32[], f32[2049,2049]) {
  %param.0 = (s32[]{:T(128)}, f32[2049,2049]{1,0:T(8,128)}) parameter(0)
  %fusion.5 = (f32[1,2049]{1,0:T(1,128)}, f32[1,2049]{1,0:T(1,128)}) fusion(%gte.1), kind=kLoop, calls=%fc.5, backend_config={"window_config":{"estimated_cycles":"2273"}}
  %copy.84 = f32[2049,2049]{0,1:T(8,128)S(1)} copy(%gte.1), backend_config={"window_config":{"estimated_cycles":"137785"}}
  %copy.86 = f32[1,2049]{0,1:T(8,128)} copy(%cp.1), backend_config={"window_config":{"estimated_cycles":"100"}}
  ROOT %tuple.9 = (s32[]{:T(128)}, f32[2049,2049]{1,0:T(8,128)}) tuple(%add.1, %copy.84)
}
ENTRY %main (p: f32[2049,2049]) -> f32[2049,2049] {
  %while.26 = (s32[]{:T(128)}, f32[2049,2049]{1,0:T(8,128)}) while(%tuple.1), condition=%cond.2, body=%body.3, metadata={op_name="jit(solver)/shard_map/while" stack_frame_id=58}
}
"""


def test_loop_body_ops_reads_the_compiled_loop():
    from poisson_ellipse_tpu.obs.static_cost import (
        loop_body_ops,
        whole_block_relayouts,
    )
    from poisson_ellipse_tpu.parallel.mesh import make_mesh
    from poisson_ellipse_tpu.parallel.pcg_sharded import build_sharded_solver

    ops = loop_body_ops(_LOOP_HLO)
    assert [(o["op"], o["dims"], o["cycles"]) for o in ops] == [
        ("parameter", None, 0), ("fusion", None, 2273),
        ("copy", (2049, 2049), 137785), ("copy", (1, 2049), 100),
        ("tuple", None, 0),
    ]
    assert [o["name"] for o in whole_block_relayouts(ops)] == ["copy.84"]
    with pytest.raises(ValueError, match="no while loop"):
        loop_body_ops(_LOOP_HLO.replace("shard_map/while", "pjit/while"))
    # the same reader on a program this backend compiled
    solver, args = build_sharded_solver(
        Problem(M=12, N=12), make_mesh(jax.devices()[:4]), jnp.float32)
    real = loop_body_ops(solver.lower(*args).compile().as_text())
    assert any(o["op"] == "all-reduce" for o in real)


def test_multichip_table_carries_collectives():
    from poisson_ellipse_tpu.harness.bench_multichip import scaling_table

    t = scaling_table("strong", (20, 20), [(1, 2)], stencil_impl="pipelined")
    assert t["collectives_per_iter"]["psum"] == 1
    t2 = scaling_table("strong", (20, 20), [(1, 2)])
    assert t2["collectives_per_iter"]["psum"] == 2


# -------------------------------------------------------- inspect CLI


def test_harness_inspect_subcommand(capsys):
    from poisson_ellipse_tpu.harness.__main__ import main

    rc = main([
        "inspect", "pipelined", "--mode", "sharded", "--mesh", "1", "2",
        "--grid", "20x20", "--no-xla-cost", "--json",
    ])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["engine"] == "pipelined" and rep["psum_per_iter"] == 1

    rc = main(["inspect", "xla", "--grid", "10x10", "--no-xla-cost"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "psum/iter" in out and "modeled HBM bytes/iter" in out

    assert main(["inspect", "resident", "--mode", "sharded"]) == 2
    assert "error" in capsys.readouterr().err


def test_harness_trace_flag_end_to_end(tmp_path, capsys):
    from poisson_ellipse_tpu.harness.__main__ import main

    path = tmp_path / "cli.jsonl"
    rc = main(["10", "10", "--mode", "single", "--trace", str(path), "--json"])
    assert rc == 0
    assert obs_trace.validate_file(path) == []
    names = [r["name"] for r in obs_trace.read_jsonl(path)]
    for expected in ("trace-start", "cli-args", "phase:init", "phase:solver",
                     "phase:finalize", "run_report", "runs", "cli-exit"):
        assert expected in names, (expected, names)
    # the CLI closed its tracer: nothing ambient leaks into later runs
    assert obs_trace.active() is None


def test_harness_profile_is_one_event_not_spans(tmp_path, capsys):
    """``--profile``'s per-op means come from a replay apart from the
    run: one ``profile`` event carrying them, no span claiming a start."""
    from poisson_ellipse_tpu.harness.__main__ import main

    path = tmp_path / "profile.jsonl"
    rc = main(["10", "10", "--mode", "single", "--profile", "--trace",
               str(path), "--json"])
    assert rc == 0
    phases = json.loads(capsys.readouterr().out.strip())["phase_s"]
    assert obs_trace.validate_file(path) == []
    recs = obs_trace.read_jsonl(path)
    (profile,) = [r for r in recs if r["name"] == "profile"]
    assert profile["kind"] == "event"
    assert profile["fields"]["phase_s"] == pytest.approx(phases)
    assert not [r for r in recs if r["name"].startswith("profile:")]


def test_harness_metrics_flag_writes_openmetrics_snapshot(tmp_path, capsys):
    from poisson_ellipse_tpu.harness.__main__ import main
    from poisson_ellipse_tpu.obs import export as obs_export

    path = tmp_path / "run.prom"
    rc = main(["10", "10", "--mode", "single", "--metrics", str(path),
               "--json"])
    assert rc == 0
    text = path.read_text()
    assert obs_export.validate_openmetrics(text) == []
    assert "poisson_runs_total 1" in text
    assert "poisson_last_iters" in text
    assert 'poisson_solve_seconds{quantile="0.5"}' in text


# ------------------------------------------------------- golden corpus


def test_trace_golden_corpus_from_a_batched_guarded_run(tmp_path):
    """One recorded batched+guarded run exercising every event family
    the schema carries — phase spans, recovery events (lane-addressed
    quarantine included), cache hit/miss, a bench artifact — validated
    record by record, so schema drift breaks loudly here instead of in
    a consumer's dashboard."""
    from poisson_ellipse_tpu.batch import solve_batched
    from poisson_ellipse_tpu.harness.__main__ import main
    from poisson_ellipse_tpu.resilience import FaultPlan, inject_nan
    from poisson_ellipse_tpu.runtime.compile_cache import WarmPool

    problem = Problem(M=10, N=10)
    path = tmp_path / "corpus.jsonl"
    # the harness CLI contributes the phase:*/run_report/counter records
    rc = main(["10", "10", "--mode", "single", "--trace", str(path),
               "--json"])
    assert rc == 0
    obs_trace.start(path)  # append the serving + resilience families
    try:
        pool = WarmPool()
        pool.warmup("batched", (10, 10), jnp.float32, lanes=3)
        pool.solve(problem, 3, "batched", jnp.float32)
        solve_batched(
            problem, 3, "batched", jnp.float32, chunk=4,
            faults=FaultPlan(inject_nan(4, "r", lane=1)),
        )
        obs_trace.event(
            "bench_artifact", metric="T_solver", value=0.001, valid=True
        )
    finally:
        obs_trace.stop()

    records = obs_trace.read_jsonl(path)
    assert obs_trace.validate_file(path) == []
    names = {r["name"] for r in records}
    for expected in (
        "phase:init", "phase:solver", "phase:finalize",  # phase:*
        "recovery:lane-quarantine",                       # recovery:*
        "cache:miss", "cache:hit",                        # cache:*
        "bench_artifact", "run_report",
    ):
        assert expected in names, (expected, sorted(names))
    lanes = [r for r in records if "lane" in r]
    assert lanes and all(
        isinstance(r["lane"], int) and r["lane"] >= 0 for r in lanes
    )
    kinds = {r["kind"] for r in records}
    assert kinds == {"meta", "span", "event", "counter", "gauge"}


# -------------------------------------------------------- diagnose CLI


def test_harness_diagnose_subcommand(tmp_path, capsys):
    from poisson_ellipse_tpu.harness.__main__ import main

    metrics = tmp_path / "diag.prom"
    trace = tmp_path / "diag.jsonl"
    rc = main([
        "diagnose", "xla", "--grid", "20x20", "--no-xla-cost",
        "--metrics", str(metrics), "--trace", str(trace), "--json",
    ])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    # the acceptance contract: diagnosing changes nothing
    assert rep["bit_identical"] is True
    assert rep["converged"] is True
    spec = rep["spectrum"]
    assert spec["available"] and spec["kappa"] > 1
    assert spec["predicted_iters"] == rep["iters"]  # measured-exact replay
    prof = rep["profile"]
    assert prof["iters"] == rep["iters"]
    assert prof["t_compile_s"] >= 0 and prof["t_solve_s"] > 0
    assert prof["modeled_hbm_bytes_per_iter"] > 0
    # exports validate: OpenMetrics file + schema-valid trace
    from poisson_ellipse_tpu.obs import export as obs_export

    assert obs_export.validate_openmetrics(metrics.read_text()) == []
    assert "poisson_diagnose_kappa" in metrics.read_text()
    assert obs_trace.validate_file(trace) == []
    assert "diagnose_report" in {
        r["name"] for r in obs_trace.read_jsonl(trace)
    }

    # human-readable form names the contract and the spectral story
    rc = main(["diagnose", "xla", "--grid", "10x10", "--no-profile"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "BIT-IDENTICAL" in out and "kappa" in out

    # engines that record no history are a curated error, not a traceback
    assert main(["diagnose", "resident"]) == 2
    assert "history" in capsys.readouterr().err
    # ... as are a bad repeat (checked BEFORE any solve is paid for), a
    # malformed grid, and a zero metrics cadence on the main prog
    assert main(["diagnose", "xla", "--repeat", "0"]) == 2
    assert "repeat" in capsys.readouterr().err
    assert main(["diagnose", "xla", "--grid", "40by40"]) == 2
    assert "error" in capsys.readouterr().err
    assert main(["diagnose", "xla",
                 "--metrics", "/nonexistent-dir/x.prom"]) == 2
    assert "cannot write" in capsys.readouterr().err
    assert main(["10", "10", "--metrics", "x.prom",
                 "--metrics-interval", "0"]) == 2
    assert "metrics-interval" in capsys.readouterr().err
    # an unwritable --metrics path fails FAST with the curated exit-2,
    # not a traceback out of the finally block after a paid-for solve
    assert main(["10", "10", "--metrics", "/nonexistent-dir/x.prom"]) == 2
    assert "cannot write" in capsys.readouterr().err
