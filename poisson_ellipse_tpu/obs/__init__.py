"""obs — the observability layer over the engine zoo.

Three legs, mirroring what a production solver service has to expose:

- :mod:`.convergence` — on-device per-iteration history (zr / diff /
  α / β) carried through the fused ``lax.while_loop`` as preallocated
  ring buffers: convergence curves with zero host syncs, surfaced as
  ``solve(..., history=True)`` on the classical, fused, pipelined and
  sharded engines.
- :mod:`.trace` + :mod:`.metrics` — structured JSONL run tracing (run
  ids, monotonic phase spans, counters/gauges) behind ``--trace FILE`` /
  ``POISSON_TRACE=``, whose spans are also ``jax.profiler`` annotations
  (``poisson:<name>``); ``utils.timing.PhaseTimer`` is a thin shim over
  it.
- :mod:`.static_cost` — compile-time accounting from the jaxpr and
  XLA's cost analysis: psum/ppermute per iteration, estimated FLOPs and
  HBM bytes, measured-vs-modeled roofline columns — the layer that
  turns the pipelined engine's "1 collective/iter vs classical 2" claim
  into a regression-checked metric (``harness inspect``, BENCH
  artifacts).
- :mod:`.spectrum` — spectral diagnostics from the convergence trace:
  the Lanczos tridiagonal hiding in the recorded α/β, Ritz values,
  κ(M⁻¹A), the asymptotic CG rate, sharp iteration prediction and
  plateau detection (``harness diagnose``, the ``spectrum`` BENCH key).
- :mod:`.profile` — fenced compile/H2D/solve/D2H phase profiling with
  measured GB/s / FLOP/s joined against the static traffic model.
- :mod:`.export` — OpenMetrics text rendering of a metrics snapshot +
  atomic/periodic snapshot-to-file wiring (``--metrics FILE``).

:mod:`.static_cost` and :mod:`.profile` import the solver engines, so
they are intentionally NOT imported here — ``from poisson_ellipse_tpu.
obs import static_cost`` (or ``profile``) at use sites keeps this
package importable from inside the solver modules it instruments.
"""

from poisson_ellipse_tpu.obs.convergence import (
    HISTORY_FIELDS,
    ConvergenceTrace,
    history_init,
    history_record,
    trace_of,
)
from poisson_ellipse_tpu.obs.export import MetricsExporter, render_openmetrics
from poisson_ellipse_tpu.obs.metrics import (
    REGISTRY,
    Histogram,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
)
from poisson_ellipse_tpu.obs.spectrum import ritz_values, spectrum_report
from poisson_ellipse_tpu.obs.trace import Tracer, event, note, span, start, stop

__all__ = [
    "HISTORY_FIELDS",
    "ConvergenceTrace",
    "Histogram",
    "MetricsExporter",
    "MetricsRegistry",
    "REGISTRY",
    "Tracer",
    "counter",
    "event",
    "gauge",
    "histogram",
    "history_init",
    "history_record",
    "note",
    "render_openmetrics",
    "ritz_values",
    "span",
    "spectrum_report",
    "start",
    "stop",
    "trace_of",
]
