"""Readings that set the benchmark's limits and rates, on the chip. The
benchmark's own runs never run this.

    python3 benchmark/calibrate.py readings --workload <cell> --seeds 1,2,3 --seconds 2
        sound runs of the cell (the whole harness, short windows), one
        line each with the numbers compared;
    python3 benchmark/calibrate.py control --config <config> --eps all
        the control: the reference kept in bfloat16 put in the
        program's place, against the float32 reference, one line per ε;
    python3 benchmark/calibrate.py sweep --workload <cell> --rates 4,6,8 --seconds 20
        the served cell at each open-loop rate: completions per second,
        the latency tail and the backlog left at the window's close;
    python3 benchmark/calibrate.py events --workload <cell> --seconds 1 --out <file>
        one traced run whose trace's event lists are written to <file>
        (the recorded trace the reduction's tests read).

Every mode runs in one process, so programs compile once.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import compare, harness, reference  # noqa: E402

# the control's iteration cap, in multiples of the float32 reference's
CONTROL_CAP = 4


def control_readings(config: dict, eps_values: list, device) -> list:
    out = []
    for eps in eps_values:
        spec = reference.problem_spec(config, eps)
        t0 = time.perf_counter()
        _, k32, _ = reference.solve(spec, device=device)
        w, k, conv = reference.solve(spec, "bfloat16",
                                     max_iter=CONTROL_CAP * k32,
                                     device=device)
        got = compare.readings(config, [{"eps": eps, "w": w, "iters": k,
                                         "converged": conv}], device)
        out.append({"eps": eps, "f32_iters": k32, "bf16_iters": k,
                    "bf16_converged": conv, **got,
                    "seconds": time.perf_counter() - t0})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("readings", "control", "sweep",
                                     "events"))
    ap.add_argument("--out")
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--eps", default="all")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        harness.CACHE_DIR, "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from poisson_ellipse_tpu.runtime.compile_cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    bench = harness.Bench()
    if args.mode == "control":
        config = bench.config(args.config)
        eps_values = (config["eps_choices"] if args.eps == "all"
                      else [None if e == "null" else float(e)
                            for e in args.eps.split(",")])
        for rec in control_readings(config, eps_values, jax.devices()[0]):
            print(json.dumps(rec), flush=True)
        return 0

    cell = bench.cell(args.workload)
    devices = jax.devices()[:cell["chips"]]
    if args.mode == "events":
        keep = {}
        out = harness.run_cell(bench, args.workload, 1, args.seconds, True,
                               devices, time.perf_counter(),
                               log=io.StringIO(), keep=keep)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump({"events": keep["events"]}, fh)
        print(json.dumps(out), flush=True)
        return 0
    if args.mode == "readings":
        for seed in map(int, args.seeds.split(",")):
            t0 = time.perf_counter()
            log = io.StringIO()
            out = harness.run_cell(bench, args.workload, seed, args.seconds,
                                   False, devices, t0, log=log)
            print(json.dumps({"seed": seed, "correct": out["correct"],
                              "attempted": out["attempted"],
                              "failed": out["failed"],
                              "checks": out["checks"],
                              "log": log.getvalue(),
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
        return 0

    mix_name = cell["traffic"]
    base = bench.traffic(mix_name)
    for i, rate in enumerate(map(float, args.rates.split(","))):
        bench.traffic = lambda name, rate=rate: (
            dict(base, rate_per_s=rate) if name == mix_name
            else harness.Bench.traffic(bench, name))
        t0 = time.perf_counter()
        log = io.StringIO()
        out = harness.run_cell(bench, args.workload,
                               2**31 + 7919 * i + int(rate * 1000),
                               args.seconds, False, devices, t0, log=log)
        print(json.dumps({"rate_per_s": rate, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "metrics": out["metrics"],
                          "notes": out["notes"],
                          "log": log.getvalue()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
