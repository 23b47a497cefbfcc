"""Run one benchmark cell once:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the result as the last line of standard output. Exits non-zero,
with no result, where JAX finds no TPU or fewer chips than the cell asks
for, or where the program under test is not beside the benchmark.
"""

import os
import sys

# run as a script: the checkout's root, not this directory, is the path
sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
