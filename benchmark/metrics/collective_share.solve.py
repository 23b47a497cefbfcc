"""Device time in all-reduce, collective-permute, all-gather,
reduce-scatter and all-to-all ops over the traced window, averaged over
the cell's chips, in %."""


def read(view):
    if view.trace is None or view.trace["collective_s"] <= 0:
        return None
    return 100.0 * view.trace["collective_s"] / view.trace["window_s"]
