"""Device time in XLA's plain ``copy.N`` ops over the traced window,
averaged over the cell's chips, in %. In the sharded loop these are the
whole-block layout changes that the compiler puts around the halo-extended
blocks; asynchronous ``copy-start``/``copy-done`` pairs and copies fused
into other ops are not counted."""

import re

COPY = re.compile(r"copy\.\d+")


def read(view):
    if view.trace is None:
        return None
    copy_s = sum(t for name, t in view.trace["op_s"].items()
                 if COPY.fullmatch(name))
    if copy_s <= 0:
        return None
    return 100.0 * copy_s / view.trace["window_s"]
