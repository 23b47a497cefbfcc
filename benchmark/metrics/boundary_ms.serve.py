"""Milliseconds of the program's ``serve.boundary`` spans (one bucket's
chunk-boundary read: the flag reads and the rebase), less the
``serve.retire`` spans inside them, per ``serve.step``
(``Scheduler.step``) in the traced window."""

from benchmark import tracing


def read(view):
    if view.trace is None:
        return None
    return tracing.ms_per(view.trace["spans"], "serve.boundary", "serve.step",
                          less="serve.retire")
