"""Mean milliseconds of the ``scheduler.step`` host span
(``Scheduler.step``) in the traced window."""


def read(view):
    if view.trace is None or "scheduler.step" not in view.trace["spans"]:
        return None
    count, total = view.trace["spans"]["scheduler.step"]
    return 1000.0 * total / count
