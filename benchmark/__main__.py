"""``python3 -m benchmark``: the same as ``benchmark/run.py``."""

import sys

from benchmark.harness import main

sys.exit(main())
