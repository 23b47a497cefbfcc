"""A configuration, a traffic mix, a metric and a cell come in as new
files and new entries, with no file that exists edited."""

import hashlib
import json
import os

from benchmark import harness
from conftest import run


def digest(root):
    out = {}
    for base, _, files in os.walk(root):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_and_entries_only(small_bench, tmp_path):
    root = str(tmp_path)
    bench_dir = os.path.join(root, "benchmark")
    before = digest(bench_dir)
    with open(os.path.join(bench_dir, "configs",
                           "ellipse-400x600.json")) as fh:
        config = dict(json.load(fh), name="ellipse-200x300", grid=[20, 30])
    with open(os.path.join(bench_dir, "configs", "ellipse-200x300.json"),
              "w") as fh:
        json.dump(config, fh)
    with open(os.path.join(bench_dir, "traffic", "twice.json"), "w") as fh:
        json.dump({"driver": "repeat_solve"}, fh)
    with open(os.path.join(bench_dir, "metrics", "solves.solve.py"),
              "w") as fh:
        fh.write("def read(view):\n    return len(view.record['iters'])\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "ellipse-200x300", "source": "x",
                            "file": "benchmark/configs/ellipse-200x300.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "solve-200x300",
                              "config": "ellipse-200x300",
                              "traffic": "twice", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("solve-200x300")
    spec["per_layer"].append({"name": "solves.solve", "unit": "solves",
                              "better": "higher", "source": "host_clock",
                              "layer": "solver", "moves": "solve_s",
                              "workloads": ["solve-200x300"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)

    bench = harness.Bench(root=root, bench_dir=bench_dir)
    keep = {}
    out = run(bench, "solve-200x300", keep=keep)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"solve_s", "setup_s"}
    layer = harness.per_layer(bench, bench.cell("solve-200x300"), config,
                              keep["record"], None, "cpu", 1)
    assert layer == {"solves.solve": {"value": out["attempted"],
                                      "unit": "solves"}}
    after = digest(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before


def test_split_metrics_share_a_reader(small_bench):
    """``<quantity>.<split>`` falls back to ``<quantity>.py``; a file with
    the whole name wins."""
    for metric in ("device_idle_share.solve", "device_idle_share.serve",
                   "collective_share.solve", "collective_share.serve"):
        shared = small_bench.reader(metric)
        assert os.path.basename(shared.__file__) == \
            metric.split(".")[0] + ".py"
    own = small_bench.reader("build_s.request")
    assert os.path.basename(own.__file__) == "build_s.request.py"
