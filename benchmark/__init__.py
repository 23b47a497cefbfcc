"""The benchmark: one command runs one cell of ``BENCHMARK.json`` once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See ``benchmark.harness`` for how cells, configurations, traffic mixes
and metrics are found by name.
"""
