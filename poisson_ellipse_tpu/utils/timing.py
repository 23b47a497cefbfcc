"""Phase timers reproducing the reference's benchmark phases (layer L6).

The reference's richest timing model is stage4's five accumulators
``T_gpu, T_copy, T_mpi, T_prec, T_dot`` (``poisson_mpi_cuda2.cu:696-700``)
incremented around every kernel launch / memcpy / collective and
``MPI_Reduce(MAX)``-aggregated to rank 0 (``:962-979``), with ``main``
splitting program wall-clock into init/solver/finalize (``:992-1034``).

On TPU the fast path is one fused ``lax.while_loop`` — instrumenting inside
it would destroy the very fusion being measured. So timing splits in two:

- ``PhaseTimer``: host-side wall-clock accumulator for the *coarse* phases
  (assembly/init, solve, finalize) — the analog of stage4's ``main`` split.
  Every region is fenced with ``jax.block_until_ready`` plus a scalar
  device→host fetch, so the region ends when the device's work does.

- ``profile_phases`` (harness.profile): a *segmented replay* of the PCG
  iteration that times each constituent op (halo, stencil, dot, precond,
  update) in isolation over k repetitions — the analog of stage4's
  per-phase accumulators, measured without slowing the production loop.

``PhaseTimer`` is a thin shim over the structured trace layer
(``obs.trace``): every region it closes is also emitted as a ``span``
record (``phase:<name>``) into the ambient JSONL trace when one is
active, so the human report and the machine trace come from the same
measurement.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from poisson_ellipse_tpu.obs import trace as _trace


def fence(tree) -> None:
    """Synchronise host with device work producing ``tree``.

    ``block_until_ready`` plus a 1-scalar device→host transfer: the
    transfer is the only sync observed to be reliable on every backend
    this framework targets (see module docstring).
    """
    tree = jax.block_until_ready(tree)
    leaves = jax.tree_util.tree_leaves(tree)
    if leaves:
        leaf = leaves[-1]
        if hasattr(leaf, "ravel") and leaf.size:
            float(jnp.asarray(leaf).ravel()[-1])


@dataclass
class PhaseTimer:
    """Named wall-clock accumulators, reference-style.

    >>> t = PhaseTimer()
    >>> with t.phase("init"):   ...
    >>> with t.phase("solver"): ...
    >>> t.report()
    """

    totals: dict[str, float] = field(default_factory=dict)

    def phase(self, name: str):
        return _Region(self, name)

    def add(self, name: str, seconds: float) -> None:
        self.totals[name] = self.totals.get(name, 0.0) + seconds
        # the shim half: the same measurement lands in the JSONL trace
        # (no-op when tracing is inactive)
        _trace.span_event(f"phase:{name}", seconds)

    def report(self, out=None) -> str:
        """Name-sorted rows with a share-of-total column.

        Stable column order (sorted by phase name, not insertion) and a
        guarded percentage — 0 phases or an all-zero total must render,
        not divide by zero — so reports derived from two traces of the
        same run diff cleanly.
        """
        total = sum(self.totals.values())
        lines = [
            f"  T_{name:<10s} {self.totals[name]:10.4f} s  "
            f"{(100.0 * self.totals[name] / total) if total > 0 else 0.0:5.1f}%"
            for name in sorted(self.totals)
        ]
        text = "\n".join(lines)
        if out is not None:
            print(text, file=out)
        return text


class _Region:
    def __init__(self, timer: PhaseTimer, name: str):
        self.timer = timer
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.timer.add(self.name, time.perf_counter() - self.t0)
        return False
