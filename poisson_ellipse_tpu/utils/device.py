"""Device capability table: VMEM capacity by ``device_kind``.

The engine capacity gates (``ops.resident_pcg.fits_resident``,
``ops.streamed_pcg.StreamPlan``) were measured on a 128 MiB-VMEM part;
this module keys those budgets off the actual device the solve will run
on, so ``select_engine`` picks by the chip's own VMEM.
``Device.memory_stats()`` exposes no VMEM figure, so the table below is
the source. It holds only the kind this repo has run on: a TPU of any
other kind is an error naming that kind, never a guessed budget.
Non-TPU devices (the CPU the tests run on, where the Pallas kernels
interpret) take the measured budget.
"""

from __future__ import annotations

import contextlib

import jax

_MIB = 1024 * 1024

# VMEM capacity by device kind, for the chips this repo has run on.
# "TPU v5 lite" is what a v5e chip reports (and what
# jax.experimental.topologies names for a described v5e).
_VMEM_CAPACITY = {
    "TPU v5 lite": 128 * _MIB,
}

# The part the repo's budgets were measured on (see resident_pcg /
# streamed_pcg): non-TPU devices — CPU interpret runs — use it,
# reproducing the measured behaviour exactly.
_MEASURED_CAPACITY = 128 * _MIB


# Fault-injection hook (resilience.faultinject.simulated_vmem): when set,
# every device reports this capacity, so the engine capacity gates
# (fits_resident / fits_streamed) and select_engine can be driven through
# their degradation paths deterministically, with no real OOM required.
_CAPACITY_OVERRIDE: int | None = None


@contextlib.contextmanager
def vmem_capacity_override(capacity_bytes: int):
    """Pretend every device ships ``capacity_bytes`` of VMEM while the
    context is active. Test/chaos harness hook — the production tables
    above stay the only real source."""
    global _CAPACITY_OVERRIDE
    prev = _CAPACITY_OVERRIDE
    _CAPACITY_OVERRIDE = int(capacity_bytes)
    try:
        yield
    finally:
        _CAPACITY_OVERRIDE = prev


def vmem_capacity_bytes(device=None) -> int:
    """VMEM capacity of ``device`` (default: the first default-backend
    device) from the table; the measured budget off a TPU. Raises
    ``ValueError`` for a TPU kind the table does not hold."""
    if _CAPACITY_OVERRIDE is not None:
        return _CAPACITY_OVERRIDE
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    if kind in _VMEM_CAPACITY:
        return _VMEM_CAPACITY[kind]
    if getattr(device, "platform", None) == "tpu":
        raise ValueError(
            f"no VMEM capacity known for TPU kind {kind!r}; add it to "
            "utils.device._VMEM_CAPACITY (known: "
            f"{', '.join(sorted(_VMEM_CAPACITY))})"
        )
    return _MEASURED_CAPACITY


def scaled_vmem_budget(measured_bytes: int, device=None) -> int:
    """Scale a budget measured on the 128 MiB bench part to ``device``.

    Proportional scaling: the measured budgets encode what fraction of
    capacity is usable once Mosaic's own reserves are paid (e.g.
    125/128 resident, 114/128 streamed); that fraction, not the byte
    count, is the transferable fact. Non-TPU devices scale by 1.0.
    """
    return int(
        measured_bytes * vmem_capacity_bytes(device) / _MEASURED_CAPACITY
    )
