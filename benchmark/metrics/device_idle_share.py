"""1 − (union of device-op intervals) / (traced window), averaged over
the cell's chips, in %."""


def read(view):
    if view.trace is None:
        return None
    return 100.0 * view.trace["idle_share"]
