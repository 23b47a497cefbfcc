"""Milliseconds of the program's ``serve.refill`` spans (one lane swapped
in: the host assembly, ``init_state``, the scatter into the batch) per
``serve.step`` (``Scheduler.step``) in the traced window."""

from benchmark import tracing


def read(view):
    if view.trace is None:
        return None
    return tracing.ms_per(view.trace["spans"], "serve.refill", "serve.step")
