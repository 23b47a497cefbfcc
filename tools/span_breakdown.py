"""Run one benchmark cell traced and split its host time by the program's
own spans.

The benchmark's trace reduction (``benchmark/tracing.py``) reads only its
own ``bench:`` host spans, which its drivers put around whole calls
(``scheduler.step``, ``build``). The program opens ``poisson:`` spans
inside those calls (``obs.trace.span``). This script runs a cell as
``benchmark/run.py --trace 1`` does, keeps the program's spans of the
same trace, and reduces the trace again with both kinds:

    python3 -m tools.span_breakdown --workload serve-400x600 --seed 7 --seconds 50

It prints one JSON line: ``result``, the cell's traced result line as
``benchmark/run.py`` prints it, and ``program``: per span name [count,
total s] in the window, the device's idle gaps named by the innermost
span of either kind, and the parts of a serve step (ms per
``serve.step``) and of a build (s per ``engine.build``).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time


def program_spans(trace_dir: str) -> list:
    """The program's host spans in the newest trace under ``trace_dir``,
    as ``benchmark.tracing`` lists host spans: [name, start_ns, dur_ns]."""
    from jax.profiler import ProfileData

    from poisson_ellipse_tpu.obs.trace import PROFILER_PREFIX as prefix

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return [[e.name[len(prefix):], float(e.start_ns), float(e.duration_ns)]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def parts(spans: dict) -> dict:
    """Per serve step (ms) and per build (s), from span totals."""
    total = lambda name: spans.get(name, [0, 0.0])[1]
    out = {}
    steps = spans.get("serve.step", [0])[0]
    if steps:
        ms = lambda t: 1000.0 * t / steps
        out["serve_ms_per_step"] = {
            "step": ms(total("serve.step")),
            "refill": ms(total("serve.refill")),
            "advance": ms(total("serve.advance")),
            "boundary_self": ms(total("serve.boundary")
                                - total("serve.retire")),
            "retire": ms(total("serve.retire")),
            "step_self": ms(total("serve.step") - total("serve.refill")
                            - total("serve.advance")
                            - total("serve.boundary")),
            "assembly": ms(total("assembly.host")),
        }
    builds = spans.get("engine.build", [0])[0]
    if builds:
        s = lambda t: t / builds
        out["s_per_build"] = {
            "build": s(total("engine.build")),
            "assemble": s(total("assembly.host")),
            "lower": s(total("engine.lower")),
            "compile": s(total("engine.compile")),
            "assemblies": spans.get("assembly.host", [0])[0] / builds,
        }
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from benchmark import harness, tracing

    bench = harness.Bench()
    chips = bench.cell(args.workload)["chips"]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        harness.CACHE_DIR, "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"{args.workload} needs {chips} TPU chip(s)", file=sys.stderr)
        return 2
    from poisson_ellipse_tpu.runtime.compile_cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    # the harness reads the trace before it deletes it: keep the
    # program's spans of that read, and hand the harness its own view
    bench_events = tracing.events_from_dir
    program = []

    def read(trace_dir):
        program.extend(program_spans(trace_dir))
        return bench_events(trace_dir)

    tracing.events_from_dir = read
    keep = {}
    result = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                              True, devices[:chips], t_start, keep=keep)
    planes = [f"/device:{d.platform.upper()}:{d.id}"
              for d in devices[:chips]]
    events = keep["events"]
    both = tracing.reduce({"devices": events["devices"],
                           "host": events["host"] + program}, planes)
    print(json.dumps({"result": result, "program": {
        "spans": both["spans"], "idle_gaps": both["idle_gaps"],
        "idle_s": both["window_s"] - both["busy_s"],
        **parts(both["spans"])}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
