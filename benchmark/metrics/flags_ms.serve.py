"""Milliseconds of the program's ``serve.flags`` spans (the chunk
boundary's reads of the per-lane flags conv, bd, quar, iters and diff,
each of which gathers from every chip on a mesh) per ``serve.step``
(``Scheduler.step``) in the traced window."""

from benchmark import tracing


def read(view):
    if view.trace is None:
        return None
    return tracing.ms_per(view.trace["spans"], "serve.flags", "serve.step")
