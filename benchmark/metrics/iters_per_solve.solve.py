"""Mean iterations per solve in the window, from the solves' results."""


def read(view):
    iters = view.record.get("iters")
    return sum(iters) / len(iters) if iters else None
