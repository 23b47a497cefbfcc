"""Profiler capture and its reduction to the numbers the metrics read.

The reduction works on plain event lists, so that it can be checked on a
small recorded trace without a chip:

    {"devices": {plane: [[op, start_ns, dur_ns], ...]},
     "host": [[span, start_ns, dur_ns], ...]}

``devices`` holds one list per device plane (the TPU's "XLA Ops" line);
``host`` the benchmark's own spans (``jax.profiler.TraceAnnotation``
named ``bench:<span>``), which share the profiler's clock with the
device planes.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

import numpy as np

SPAN_PREFIX = "bench:"
# device planes; "/device:CUSTOM:..." planes hold no device's ops
DEVICE_PREFIX = "/device:"
NOT_A_DEVICE = "/device:CUSTOM:"
WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|collective-permute|all-gather|reduce-scatter|all-to-all")
TOP = 10


def span(name: str):
    """A host span on the profiler's clock (free while no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def start(trace_dir: str) -> None:
    """Start the profiler with the Python tracer off: the benchmark's own
    spans are enough on the host, and the tracer would record every call
    of a serving loop."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def op_name(text: str) -> str:
    """The HLO instruction's name: a TPU trace names each op by its whole
    HLO line (``%fusion.3 = f32[...] fusion(...)``)."""
    if text.startswith("%"):
        return text[1:].split(" = ", 1)[0]
    return text


def events_from_dir(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir`` into event lists."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if (plane.name.startswith(DEVICE_PREFIX)
                and not plane.name.startswith(NOT_A_DEVICE)):
            ops = [[op_name(e.name), float(e.start_ns), float(e.duration_ns)]
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host += [[e.name[len(SPAN_PREFIX):], float(e.start_ns),
                      float(e.duration_ns)]
                     for line in plane.lines for e in line.events
                     if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "host": host}


def _merged(starts, ends):
    """The union of [start, end) intervals as sorted disjoint arrays."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    # a new interval begins where a start lies past every earlier end
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:], s.size) - 1
    return s[first], e[last]


class _Spans:
    """The host spans (the window left out), searchable by time."""

    def __init__(self, spans):
        spans = sorted((s, s + d, name) for name, s, d in spans
                       if name != WINDOW_SPAN)
        self.starts = np.array([s for s, _, _ in spans], float)
        self.ends = np.array([e for _, e, _ in spans], float)
        self.names = [n for _, _, n in spans]
        self.reach = np.maximum.accumulate(self.ends) if spans else self.ends

    def innermost(self, times) -> list:
        """For each time, the name of the shortest span covering it."""
        idx = np.searchsorted(self.starts, times, side="right") - 1
        out = []
        for t, i in zip(times, idx):
            best = None
            while i >= 0 and self.reach[i] > t:
                if t < self.ends[i] and (
                        best is None
                        or self.ends[i] - self.starts[i] < best[0]):
                    best = (self.ends[i] - self.starts[i], self.names[i])
                i -= 1
            out.append(best[1] if best else "untraced")
        return out


def reduce(events: dict, devices: list | None = None) -> dict:
    """Busy time, idle share, collective time, each op's time, the top
    ops and the idle gaps named by what the host was doing, over the
    ``window`` host span.

    ``devices`` names the planes of the devices the cell used (default:
    every device plane). Per-device numbers are averaged over them.
    """
    windows = [(s, s + d) for name, s, d in events["host"]
               if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    window_ns = hi - lo
    planes = devices if devices is not None else sorted(events["devices"])
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy, coll = [], []
    op_time = collections.Counter()
    gap_time = collections.Counter()
    spans = events["host"]
    search = _Spans(spans)
    for plane in planes:
        ops = events["devices"].get(plane, [])
        names, inverse = np.unique([n for n, _, _ in ops],
                                   return_inverse=True)
        start = np.array([s for _, s, _ in ops], float)
        end = start + np.array([d for _, _, d in ops], float)
        cs, ce = np.maximum(start, lo), np.minimum(end, hi)
        inside = ce > cs
        clipped = np.where(inside, ce - cs, 0.0)
        per_name = np.bincount(inverse.ravel(), weights=clipped,
                               minlength=len(names))
        for name, t in zip(names, per_name):
            if t > 0:
                op_time[str(name)] += t / len(planes)
        coll.append(sum(t for name, t in zip(names, per_name)
                        if COLLECTIVE.search(str(name))))
        ms, me = _merged(cs[inside], ce[inside])
        busy.append(float(np.sum(me - ms)))
        gs = np.concatenate([[lo], me])
        ge = np.concatenate([ms, [hi]])
        open_ = ge > gs
        gs, ge = gs[open_], ge[open_]
        for name, g in zip(search.innermost(0.5 * (gs + ge)), ge - gs):
            gap_time[name] += float(g) / len(planes)
    busy_ns = sum(busy) / len(planes)
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "collective_s": float(sum(coll)) / len(planes) * 1e-9,
        "op_s": {n: float(t) * 1e-9 for n, t in op_time.items()},
        "device_ops": [[n, float(t) * 1e-9]
                       for n, t in op_time.most_common(TOP)],
        "idle_gaps": [[n, float(t) * 1e-9]
                      for n, t in gap_time.most_common(TOP)],
        "spans": _span_totals(spans, lo, hi),
    }


def _span_totals(spans, lo, hi) -> dict:
    """Per host span name: [count, total seconds] of spans that start in
    the window."""
    out = collections.defaultdict(lambda: [0, 0.0])
    for name, s, d in spans:
        if name != WINDOW_SPAN and lo <= s < hi:
            out[name][0] += 1
            out[name][1] += d * 1e-9
    return dict(out)
