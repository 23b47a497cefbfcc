"""The readers serve-400x600-4chip brings, on synthetic event lists:
``flags_ms.serve``, the program's ``serve.flags`` spans per
``serve.step``, and ``collective_share.serve``, whose one collective in
the lane-sharded advance is the convergence psum, an all-reduce that a
TPU trace names ``psum_invariant.N``."""

import pytest

from benchmark import harness, tracing

MS = 1e6  # ns
PLANES = [f"/device:TPU:{i}" for i in range(4)]


def mesh_events(flags=True):
    """A 100 ms window on four chips: each runs two chunk loops and one
    psum of the convergence word; the host takes two scheduler steps,
    each with a boundary that reads the flags."""
    devices = {}
    for i, plane in enumerate(PLANES):
        devices[plane] = [["while.2", 10 * MS, 8 * MS],
                          ["psum_invariant.7", (18 + i) * MS, 2 * MS],
                          ["while.2", 40 * MS, 8 * MS]]
    program = [["serve.step", 0, 30 * MS],
               ["serve.advance", 5 * MS, 3 * MS],
               ["serve.boundary", 20 * MS, 8 * MS],
               ["serve.step", 35 * MS, 30 * MS],
               ["serve.advance", 36 * MS, 3 * MS],
               ["serve.boundary", 50 * MS, 12 * MS],
               ["serve.retire", 55 * MS, 5 * MS]]
    if flags:
        program += [["serve.flags", 20 * MS, 3 * MS],
                    ["serve.flags", 50 * MS, 4 * MS]]
    return {"devices": devices,
            "host": [["window", 0, 100 * MS],
                     ["scheduler.step", 0, 66 * MS]],
            "program": program,
            "collectives": ["psum_invariant.7"]}


def view_of(events):
    return type("View", (), {"trace": tracing.reduce(events, PLANES)})


def reader(metric):
    return harness.Bench().reader(metric)


def test_flags_ms_per_serve_step():
    # 3 + 4 ms of flag reads over two serve.step spans
    got = reader("flags_ms.serve").read(view_of(mesh_events()))
    assert got == pytest.approx(3.5)


def test_flags_ms_finds_nothing_in_a_program_without_the_span():
    assert reader("flags_ms.serve").read(
        view_of(mesh_events(flags=False))) is None
    view = view_of(mesh_events())
    view.trace = None
    assert reader("flags_ms.serve").read(view) is None


def test_collective_share_serve_reads_the_psum_by_its_opcode():
    """2 ms of psum on each chip in the 100 ms window, though the op's
    name says nothing of a collective."""
    assert not tracing.COLLECTIVE.search("psum_invariant.7")
    assert reader("collective_share.serve").read(
        view_of(mesh_events())) == pytest.approx(2.0)
    unnamed = dict(mesh_events(), collectives=[])
    assert reader("collective_share.serve").read(view_of(unnamed)) is None


def test_the_new_readers_are_reported_where_listed():
    spec = harness.Bench().spec
    listed = {m["name"]: m["workloads"] for m in spec["per_layer"]}
    assert listed["flags_ms.serve"] == ["serve-400x600",
                                        "serve-400x600-4chip"]
    assert listed["collective_share.serve"] == ["serve-400x600-4chip"]
    four = [c for c in spec["workloads"] if c["name"] == "serve-400x600-4chip"]
    assert [(c["config"], c["traffic"], c["chips"]) for c in four] == [
        ("ellipse-400x600-2x2", "served-poisson-2x2", 4)]
