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
               "ellipse-400x600": [40, 60]}


def make_small_bench(root):
    """A copy of the benchmark under ``root`` whose configurations run at
    test size; returns the ``Bench`` that reads it."""
    from benchmark import harness

    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for name, grid in SMALL_GRIDS.items():
        edit_json(os.path.join(root, "benchmark", "configs", name + ".json"),
                  grid=grid)
    edit_json(os.path.join(root, "benchmark", "traffic",
                           "served-poisson.json"), rate_per_s=20, drain_s=2)
    return harness.Bench(root=root, bench_dir=os.path.join(root, "benchmark"))


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
