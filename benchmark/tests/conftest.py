"""The benchmark's tests run on the CPU at small sizes, with four virtual
devices for the sharded cell:

    python3 -m pytest benchmark/tests -q
"""

import json
import os
import shutil
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

# test-sized grids for the configurations, and a quick drain
SMALL_GRIDS = {"ellipse-4096": [64, 64], "ellipse-4096-2x2": [64, 64],
               "ellipse-400x600": [40, 60], "ellipse-400x600-2x2": [40, 60]}
SERVED_MIXES = ("served-poisson", "served-poisson-2x2")


def make_small_bench(root):
    """A copy of the benchmark under ``root`` whose configurations run at
    test size; returns the ``Bench`` that reads it."""
    from benchmark import harness

    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    add_four_chip_served_cell(root)
    for name, grid in SMALL_GRIDS.items():
        edit_json(os.path.join(root, "benchmark", "configs", name + ".json"),
                  grid=grid)
    for mix in SERVED_MIXES:
        # a drain long enough for the four-chip warm-up on a busy CPU
        edit_json(os.path.join(root, "benchmark", "traffic", mix + ".json"),
                  rate_per_s=20, drain_s=10)
    return harness.Bench(root=root, bench_dir=os.path.join(root, "benchmark"))


def add_four_chip_served_cell(root):
    """serve-400x600-4chip, the 400x600 stream through one scheduler whose
    lanes are sharded over four chips, as files and entries in the copy
    under ``root``: the served driver's multi-chip path. The benchmark
    itself does not hold the cell yet (its knee and spreads are not
    measured on the chip)."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "serve_4chip_cell.json")) as fh:
        cell = json.load(fh)
    bench_dir = os.path.join(root, "benchmark")
    for kind, name in (("configs", cell["config_entry"]["name"]),
                       ("traffic", cell["cell"]["traffic"])):
        with open(os.path.join(bench_dir, kind, name + ".json"), "w") as fh:
            json.dump(cell["config" if kind == "configs" else "mix"], fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    spec["configs"].append(cell["config_entry"])
    spec["workloads"].append(cell["cell"])
    for m in spec["end_to_end"]:
        if m["name"] in ("latency_p50_s", "solves_per_s"):
            m["workloads"].append(cell["cell"]["name"])
    with open(path, "w") as fh:
        json.dump(spec, fh)


def edit_json(path, **changes):
    with open(path) as fh:
        data = json.load(fh)
    data.update(changes)
    with open(path, "w") as fh:
        json.dump(data, fh)


@pytest.fixture
def small_bench(tmp_path):
    return make_small_bench(str(tmp_path))


def run(bench, cell, seed=7, seconds=0.5, keep=None):
    """One run of ``cell`` on the CPU devices the cell asks for."""
    import io
    import time

    import jax

    from benchmark import harness

    chips = bench.cell(cell)["chips"]
    return harness.run_cell(bench, cell, seed, seconds, False,
                            jax.devices()[:chips], time.perf_counter(),
                            log=io.StringIO(), keep=keep)
