"""The trace reduction: busy union, idle share, collective time, top ops
and idle gaps named by the host span."""

import json
import os
import time

import pytest

from benchmark import tracing

MS = 1e6  # ns


def synthetic():
    # window 0..100 ms; device A busy 10-30 (two overlapping ops) and
    # 50-60 (an all-reduce); device B busy 0-40
    return {
        "devices": {
            "/device:TPU:0": [["fusion.1", 10 * MS, 15 * MS],
                              ["fusion.2", 20 * MS, 10 * MS],
                              ["all-reduce.3", 50 * MS, 10 * MS],
                              ["fusion.9", 120 * MS, 5 * MS]],
            "/device:TPU:1": [["collective-permute-done.1", 0, 40 * MS]],
        },
        "host": [["window", 0, 100 * MS],
                 ["scheduler.step", 0, 45 * MS],
                 ["refill", 32 * MS, 8 * MS],
                 ["sleep", 60 * MS, 40 * MS]],
    }


def test_busy_idle_and_collectives():
    out = tracing.reduce(synthetic())
    assert out["window_s"] == pytest.approx(0.1)
    # A: 20 + 10 = 30 ms; B: 40 ms; mean 35 ms
    assert out["busy_s"] == pytest.approx(0.035)
    assert out["idle_share"] == pytest.approx(0.65)
    # A: 10 ms all-reduce, B: 40 ms collective-permute; mean 25 ms
    assert out["collective_s"] == pytest.approx(0.025)


def test_one_device():
    out = tracing.reduce(synthetic(), ["/device:TPU:0"])
    assert out["busy_s"] == pytest.approx(0.030)
    assert out["collective_s"] == pytest.approx(0.010)
    ops = dict(out["device_ops"])
    # fusion.9 lies outside the window
    assert "fusion.9" not in ops
    assert ops["fusion.1"] == pytest.approx(0.015)
    assert out["op_s"] == pytest.approx(
        {"fusion.1": 0.015, "fusion.2": 0.010, "all-reduce.3": 0.010})


def test_op_times_are_averaged_over_devices():
    op_s = tracing.reduce(synthetic())["op_s"]
    assert op_s["fusion.1"] == pytest.approx(0.0075)
    assert op_s["collective-permute-done.1"] == pytest.approx(0.020)


def test_gaps_are_named_by_the_innermost_host_span():
    gaps = dict(tracing.reduce(synthetic(), ["/device:TPU:0"])["idle_gaps"])
    # 0-10: step; 30-50: midpoint 40 lies in refill (32-40)? no: 40 is
    # its end, so step; 60-100: sleep
    assert gaps == pytest.approx({"scheduler.step": 0.030, "sleep": 0.040})


def test_span_totals():
    spans = tracing.reduce(synthetic())["spans"]
    assert spans["scheduler.step"] == [1, pytest.approx(0.045)]
    assert "window" not in spans


def test_one_window_span_is_required():
    ev = synthetic()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        tracing.reduce(ev)


def test_op_name():
    assert tracing.op_name(
        "%fusion.3 = f32[4,511]{1,0} fusion(f32[4] %x), kind=kLoop") \
        == "fusion.3"
    assert tracing.op_name("jit_fn(123)") == "jit_fn(123)"


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "serve_trace_events.json")


def test_recorded_tpu_trace():
    """Events recorded from a traced serve-400x600 run on a TPU v5e."""
    with open(RECORDED) as fh:
        rec = json.load(fh)
    out = tracing.reduce(rec["events"])
    for key, value in rec["expected"].items():
        assert out[key] == pytest.approx(value), key
    assert 0 < out["busy_s"] < out["window_s"]
    names = {n for n, _ in out["idle_gaps"]}
    assert names <= {"scheduler.step", "submit", "collect", "sleep",
                     "untraced"}
    times = [t for _, t in out["device_ops"]]
    assert times == sorted(times, reverse=True)


def test_events_from_a_cpu_trace(tmp_path):
    """The host spans come back from a real profiler file."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracing.start(str(tmp_path))
    with tracing.span(tracing.WINDOW_SPAN):
        with tracing.span("dispatch"):
            y = f(x)
        with tracing.span("wait"):
            y.block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    ev = tracing.events_from_dir(str(tmp_path))
    names = [n for n, _, _ in ev["host"]]
    assert sorted(names) == ["dispatch", "wait", "window"]
    window = next(s for s in ev["host"] if s[0] == "window")
    for name, start, dur in ev["host"]:
        assert window[1] <= start and start + dur <= window[1] + window[2]


def test_layout_copy_share_counts_plain_copies_only():
    from benchmark import harness

    reader = harness.load_module(os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "metrics",
        "layout_copy_share.solve.py"))
    ev = synthetic()
    ev["devices"]["/device:TPU:0"] += [["copy.85", 62 * MS, 8 * MS],
                                       ["copy-start.1", 70 * MS, 5 * MS],
                                       ["copy_fusion.2", 75 * MS, 5 * MS]]
    view = type("View", (), {"trace": tracing.reduce(ev)})
    # 8 ms on one of two devices over a 100 ms window
    assert reader.read(view) == pytest.approx(4.0)
    view.trace = tracing.reduce(synthetic())
    assert reader.read(view) is None
