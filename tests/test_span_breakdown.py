"""tools/span_breakdown.py: the program's spans read back from a
profiler trace, and a serve step and a build split by them."""

from __future__ import annotations

import importlib.util
import os
import time

import jax
import pytest

from benchmark import tracing
from poisson_ellipse_tpu.obs import trace as obs_trace

_TOOL = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools",
    "span_breakdown.py",
)
_spec = importlib.util.spec_from_file_location("span_breakdown", _TOOL)
sb = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sb)


def test_parts_split_a_serve_step_and_a_build():
    spans = {
        "scheduler.step": [4, 0.0405],   # the benchmark's: not read
        "serve.step": [4, 0.040],
        "serve.refill": [1, 0.010],
        "serve.advance": [4, 0.008],
        "serve.boundary": [4, 0.020],
        "serve.retire": [1, 0.004],
        "assembly.host": [3, 0.012],
        "engine.build": [1, 0.100],
        "engine.lower": [1, 0.050],
        "engine.compile": [1, 0.010],
    }
    out = sb.parts(spans)
    step = out["serve_ms_per_step"]
    assert step["step"] == pytest.approx(10.0)
    assert step["refill"] == pytest.approx(2.5)
    assert step["advance"] == pytest.approx(2.0)
    assert step["boundary_self"] == pytest.approx(4.0)
    assert step["retire"] == pytest.approx(1.0)
    assert step["step_self"] == pytest.approx(0.5)
    build = out["s_per_build"]
    assert build["build"] == pytest.approx(0.1)
    assert build["assemble"] == pytest.approx(0.012)
    assert build["lower"] == pytest.approx(0.05)
    assert build["compile"] == pytest.approx(0.01)
    assert build["assemblies"] == 3


def test_parts_are_absent_without_the_program_spans():
    # a tree without the program's spans: only the benchmark's remain
    assert sb.parts({"scheduler.step": [4, 0.04], "build": [2, 0.2]}) == {}
    assert set(sb.parts({"serve.step": [2, 0.02]})) == {"serve_ms_per_step"}


def test_program_spans_name_the_idle_gaps(tmp_path):
    """Spans recorded under a real profiler trace come back without the
    prefix, beside the benchmark's, on one clock: a device gap inside
    ``serve.boundary`` is named by it, not by the enclosing bench span."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span(tracing.WINDOW_SPAN):
            with tracing.span("scheduler.step"):
                with obs_trace.span("serve.step"):
                    with obs_trace.span("serve.boundary"):
                        # the boundary outlasts everything around it
                        time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    program = sb.program_spans(str(tmp_path))
    assert sorted(n for n, _, _ in program) == ["serve.boundary",
                                               "serve.step"]
    bench = tracing.events_from_dir(str(tmp_path))["host"]
    (boundary,) = [s for s in program if s[0] == "serve.boundary"]
    mid = boundary[1] + boundary[2] / 2
    # two device ops a microsecond apart around the boundary's midpoint
    devices = {"/device:TPU:0": [["op", mid - 2e3, 1e3],
                                 ["op", mid + 1e3, 1e3]]}
    plain = dict(tracing.reduce({"devices": devices,
                                 "host": bench})["idle_gaps"])
    both = tracing.reduce({"devices": devices, "host": bench + program})
    named = dict(both["idle_gaps"])
    assert max(plain, key=plain.get) == "scheduler.step"
    assert max(named, key=named.get) == "serve.boundary"
    assert "scheduler.step" not in named
    assert sum(named.values()) == pytest.approx(sum(plain.values()))
    assert both["spans"]["serve.step"][0] == 1
