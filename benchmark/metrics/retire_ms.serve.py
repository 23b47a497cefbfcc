"""Milliseconds of the program's ``serve.retire`` spans (one lane
retired: the answer's fetch, the lane parked) per ``serve.step``
(``Scheduler.step``) in the traced window."""

from benchmark import tracing


def read(view):
    if view.trace is None:
        return None
    return tracing.ms_per(view.trace["spans"], "serve.retire", "serve.step")
