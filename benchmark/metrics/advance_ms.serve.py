"""Milliseconds of the program's ``serve.advance`` spans (one bucket's
faults, the ``int(ctx.state[0])`` read, the chunk's limit and its
dispatch) per ``serve.step`` (``Scheduler.step``) in the traced window."""

from benchmark import tracing


def read(view):
    if view.trace is None:
        return None
    return tracing.ms_per(view.trace["spans"], "serve.advance", "serve.step")
