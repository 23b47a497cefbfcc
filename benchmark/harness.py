"""Run one cell of ``BENCHMARK.json`` once and print its result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by its name:

- ``benchmark/configs/<config>.json``: the deployment (grid, box, ε
  choices, tolerance, precision) and the limits of its comparison;
- ``benchmark/traffic/<traffic>.json``: the mix's parameters, read by
  ``benchmark.traffic``, and the driver module that serves it;
- ``benchmark/drivers/<driver>.py``: one per kind of traffic; its
  ``run(ctx)`` sets up, calls ``ctx.open_window()`` before its first
  timed call and ``ctx.close_window()`` after its last, waits for what
  is due, frees the program's state and returns the run's record;
- ``benchmark/metrics/<metric>.py``: a reader whose ``read(view)``
  returns the per-layer metric, or None where it finds nothing to read.
  A quantity split by the end-to-end metric it moves
  (``device_idle_share.solve``, ``device_idle_share.serve``) may share
  one reader, named by the part before the first dot
  (``device_idle_share.py``), where no file has the whole name.

A new cell, configuration, mix or metric is new files and new entries.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import tempfile
import time
import types

from benchmark import compare, tracing

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".bench_cache")


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str):
    """Import a file by path: metric and driver files are found by name,
    and a name may hold dots."""
    name = "benchmark_file_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``bench_dir``."""

    def __init__(self, root: str = ROOT, bench_dir: str = BENCH_DIR):
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))
        self.dir = bench_dir

    def cell(self, name: str) -> dict:
        for cell in self.spec["workloads"]:
            if cell["name"] == name:
                return cell
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return load_json(os.path.join(self.dir, "configs", name + ".json"))

    def traffic(self, name: str) -> dict:
        return load_json(os.path.join(self.dir, "traffic", name + ".json"))

    def driver(self, name: str):
        return load_module(os.path.join(self.dir, "drivers", name + ".py"))

    def reader(self, metric: str):
        path = os.path.join(self.dir, "metrics", metric + ".py")
        if not os.path.exists(path):
            path = os.path.join(self.dir, "metrics",
                                metric.split(".", 1)[0] + ".py")
        return load_module(path)

    def metrics_for(self, cell: str, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]


class Context:
    """What a driver sees: the cell, its configuration and mix, the seed
    and window, the devices, and the window's hooks."""

    def __init__(self, cell: dict, config: dict, mix: dict, seed: int,
                 seconds: float, trace: bool, devices: list, t_start: float,
                 trace_dir: str | None = None):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.t_start = t_start
        self.trace_dir = trace_dir
        self.setup_s = None
        self.compile_requests = 0
        self.cache_hits = 0
        self._in_window = False
        self._window_span = None
        self.span = tracing.span

    def problem(self, eps):
        """The program's problem for one solve of this configuration."""
        from poisson_ellipse_tpu.models.problem import Problem

        M, N = self.config["grid"]
        a1, b1, a2, b2 = self.config["box"]
        return Problem(M=M, N=N, a1=a1, b1=b1, a2=a2, b2=b2,
                       f_val=self.config["f"], delta=self.config["delta"],
                       norm=self.config["norm"], eps=eps)

    def count_compile(self, event: str, duration: float, **_kw) -> None:
        """JAX times every compile request, persistent-cache reads too."""
        if self._in_window and event.endswith("backend_compile_duration"):
            self.compile_requests += 1

    def count_cache_hit(self, event: str, **_kw) -> None:
        if self._in_window and event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    @property
    def compiles(self) -> int:
        """Programs compiled in the window: requests the cache missed."""
        return self.compile_requests - self.cache_hits

    def open_window(self) -> None:
        """Set-up ends here; the trace, when asked for, starts."""
        self.setup_s = time.perf_counter() - self.t_start
        if self.trace:
            tracing.start(self.trace_dir)
        self._window_span = tracing.span(tracing.WINDOW_SPAN)
        self._window_span.__enter__()
        self._in_window = True

    def close_window(self) -> None:
        """The window ends here. The trace runs on until the traffic's
        run has waited for what is due, so that stopping it delays no
        answer."""
        self._in_window = False
        self._window_span.__exit__(None, None, None)

    def stop_trace(self) -> None:
        import jax

        if self.trace:
            jax.profiler.stop_trace()


def per_layer(bench: Bench, cell: dict, config: dict, record: dict,
              reduced: dict | None, device_kind: str, chips: int) -> dict:
    """The cell's per-layer metrics that their readers find."""
    view = types.SimpleNamespace(record=record, trace=reduced, config=config,
                                 cell=cell, device_kind=device_kind,
                                 chips=chips)
    metrics = {}
    for m in bench.metrics_for(cell["name"], "per_layer"):
        value = bench.reader(m["name"]).read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(bench: Bench, name: str, seed: int, seconds: float,
             trace: bool, devices: list, t_start: float,
             log=sys.stderr, keep: dict | None = None) -> dict:
    """Drive one run of cell ``name`` on ``devices``; return the result
    line's object. Raises where a run cannot produce one. ``keep``, where
    given, receives the run's record and the trace's event lists."""
    import jax

    cell = bench.cell(name)
    config = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    driver = bench.driver(mix["driver"])
    if trace and "trace_seconds" in mix:
        # a mix whose device trace is dense traces a shorter window, so
        # that stopping the profiler and reading its file stay short
        seconds = min(seconds, float(mix["trace_seconds"]))
    with contextlib.ExitStack() as stack:
        trace_dir = None
        if trace:
            os.makedirs(CACHE_DIR, exist_ok=True)
            trace_dir = stack.enter_context(
                tempfile.TemporaryDirectory(dir=CACHE_DIR, prefix="trace-"))
        ctx = Context(cell, config, mix, seed, seconds, trace, devices,
                      t_start, trace_dir)
        jax.monitoring.register_event_duration_secs_listener(
            ctx.count_compile)
        jax.monitoring.register_event_listener(ctx.count_cache_hit)
        record = driver.run(ctx)
        ctx.stop_trace()
        peak = memory_peak(devices)
        reduced = None
        if trace:
            planes = [f"/device:{d.platform.upper()}:{d.id}" for d in devices]
            events = tracing.events_from_dir(trace_dir)
            if keep is not None:
                keep["events"] = events
            reduced = tracing.reduce(events, planes)
    answers = record.pop("answers")
    if keep is not None:
        keep["record"] = record
    checks = compare.check(config, answers, devices[0],
                           record.pop("unanswered", 0), log=log)
    correct = compare.passed(checks)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics = {}
    if not trace:
        for m in bench.metrics_for(name, "end_to_end"):
            value = ctx.setup_s if m["name"] == "setup_s" \
                else record["metrics"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = per_layer(bench, cell, config, record, reduced,
                            dev.device_kind, len(devices))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    out = {"correct": correct, "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["compiles_in_window"] = ctx.compiles
    out["cache_reads_in_window"] = ctx.cache_hits
    out["notes"] = record.get("notes", {})
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    import argparse

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = Bench()
    chips = bench.cell(args.workload)["chips"]
    # a fixed directory in the checkout: only the first run compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE_DIR, "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"{args.workload} needs {chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from poisson_ellipse_tpu.runtime.compile_cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), devices[:chips], t_start)
    print(f"correct: {out['correct']}", file=sys.stderr)
    for key, check in out["checks"].items():
        print(f"check {key}: {check['value']!r} limit {check['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
