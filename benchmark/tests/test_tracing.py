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


def reader(metric):
    from benchmark import harness

    return harness.Bench().reader(metric)


def serve_events():
    """Two ``serve.step`` spans of the program inside the benchmark's
    ``scheduler.step``: refills, advances, boundaries holding
    retirements."""
    ev = synthetic()
    ev["program"] = [["serve.step", 0, 20 * MS],
                     ["serve.refill", 0, 6 * MS],
                     ["serve.advance", 6 * MS, 2 * MS],
                     ["serve.boundary", 8 * MS, 10 * MS],
                     ["serve.retire", 12 * MS, 4 * MS],
                     ["serve.step", 22 * MS, 20 * MS],
                     ["serve.advance", 22 * MS, 3 * MS],
                     ["serve.boundary", 25 * MS, 15 * MS],
                     ["serve.retire", 30 * MS, 2 * MS],
                     ["serve.retire", 33 * MS, 2 * MS]]
    return ev


def test_serve_span_readers():
    view = type("View", (), {"trace": tracing.reduce(serve_events())})
    got = {m: reader(m).read(view) for m in (
        "refill_ms.serve", "advance_ms.serve", "boundary_ms.serve",
        "retire_ms.serve")}
    # per serve.step (2): refill 6 ms, advance 5, boundary 25 less
    # retire 8, retire 8
    assert got == pytest.approx({"refill_ms.serve": 3.0,
                                 "advance_ms.serve": 2.5,
                                 "boundary_ms.serve": 8.5,
                                 "retire_ms.serve": 4.0})
    # the benchmark's own span still feeds step_ms.serve
    assert reader("step_ms.serve").read(view) == pytest.approx(45.0)


def test_serve_span_readers_find_nothing_without_program_spans():
    view = type("View", (), {"trace": tracing.reduce(synthetic())})
    for m in ("refill_ms.serve", "advance_ms.serve", "boundary_ms.serve",
              "retire_ms.serve"):
        assert reader(m).read(view) is None
    view.trace = None
    assert reader("refill_ms.serve").read(view) is None


def test_program_spans_name_gaps_and_are_totalled():
    out = tracing.reduce(serve_events(), ["/device:TPU:0"])
    gaps = dict(out["idle_gaps"])
    # device gaps 0-10, 30-50 and 60-100 ms, each named by the innermost
    # span at its midpoint: 5 ms lies in serve.refill (not in the
    # benchmark's scheduler.step around it), 40 ms in the second
    # serve.step (22-42), 80 ms in sleep
    assert gaps == pytest.approx({"serve.refill": 0.010,
                                  "serve.step": 0.020, "sleep": 0.040})
    assert out["spans"]["serve.step"] == [2, pytest.approx(0.040)]
    assert out["spans"]["scheduler.step"] == [1, pytest.approx(0.045)]


def test_collectives_by_opcode():
    assert tracing.is_collective(
        "%psum_invariant.38 = f32[] all-reduce(f32[] %x), channel_id=1")
    assert tracing.is_collective("collective-permute-start.3")
    assert tracing.is_collective(
        "%cp.1 = (f32[4], f32[4]) collective-permute-start(f32[4] %a)")
    assert not tracing.is_collective(
        "%fusion.2 = f32[] fusion(f32[] %all-reduce.3), kind=kLoop")
    assert not tracing.is_collective("psum_invariant.38")
    ev = synthetic()
    ev["devices"]["/device:TPU:1"].append(["psum_invariant.38", 60 * MS,
                                           10 * MS])
    plain = tracing.reduce(ev)
    ev["collectives"] = ["psum_invariant.38"]
    named = tracing.reduce(ev)
    # 10 ms more of collectives on one device of two
    assert named["collective_s"] - plain["collective_s"] == \
        pytest.approx(0.005)


def test_collective_share_reads_both_splits_alike():
    view = type("View", (), {"trace": tracing.reduce(synthetic())})
    # 25 ms of the 100 ms window
    assert reader("collective_share.solve").read(view) == pytest.approx(25.0)
    assert reader("collective_share.serve").read(view) == pytest.approx(25.0)


def test_program_spans_from_a_cpu_trace(tmp_path):
    """The program's spans come back under ``program``, stripped of its
    prefix; ``host`` keeps the benchmark's alone."""
    import jax

    from poisson_ellipse_tpu.obs import trace as obs_trace

    tracing.start(str(tmp_path))
    with tracing.span(tracing.WINDOW_SPAN):
        with tracing.span("scheduler.step"):
            with obs_trace.span("serve.step"):
                with obs_trace.span("serve.boundary"):
                    time.sleep(0.002)
    jax.profiler.stop_trace()
    ev = tracing.events_from_dir(str(tmp_path))
    assert sorted(n for n, _, _ in ev["host"]) == ["scheduler.step",
                                                    "window"]
    assert sorted(n for n, _, _ in ev["program"]) == ["serve.boundary",
                                                       "serve.step"]
    spans = tracing.reduce({**ev, "devices": {"/device:CPU:0": []}})["spans"]
    assert spans["serve.step"][0] == spans["serve.boundary"][0] == 1
