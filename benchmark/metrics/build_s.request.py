"""Mean seconds of the ``build`` host span (``build_solver``: assembly,
engine choice, its compile probe) per request in the traced window."""


def read(view):
    if view.trace is None or "build" not in view.trace["spans"]:
        return None
    count, total = view.trace["spans"]["build"]
    return total / count
