"""Bytes-per-iteration roofline model: achieved HBM bandwidth per engine.

The reference's stage4 report attributes time to named phases (T_gpu,
T_copy, T_mpi, T_prec, T_dot — ``poisson_mpi_cuda2.cu:696-700``) but never
relates them to what the hardware could do. Here every run carries the
next level: modelled HBM array-passes per PCG iteration for the engine
that executed, the achieved streaming bandwidth they imply, and the
fraction of the chip's HBM roofline that represents. A resident-engine
row showing ~0 passes/iter is the point: that engine left the HBM
roofline entirely (its iterations are VMEM/VPU-bound), which is why it
outruns the XLA path several-fold.

The pass counts are a traffic *model* (array reads + writes the
iteration must stream from/to HBM, assuming perfect fusion of
elementwise consumers), not a measurement; they use unpadded node-array
bytes, so the implied GB/s slightly understates true traffic on padded
layouts. Small grids report low roofline fractions because fixed
per-iteration overheads (kernel launch, loop bookkeeping) dominate —
the number quantifies exactly how far from streaming-bound a
configuration is.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from poisson_ellipse_tpu.models.problem import Problem

# Published peak HBM bandwidth by device kind (bytes/s).
_HBM_PEAK = {
    "TPU v4": 1_228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2_765e9,
    "TPU v5p": 2_765e9,
    "TPU v6 lite": 1_640e9,
    "TPU v6e": 1_640e9,
}


def hbm_peak_bytes_per_s(device=None) -> Optional[float]:
    """Peak HBM bandwidth of the (default) device, or None if unknown."""
    if device is None:
        devices = jax.devices()
        if not devices:
            return None
        device = devices[0]
    return _HBM_PEAK.get(getattr(device, "device_kind", ""), None)


def passes_per_iter(problem: Problem, engine: str, dtype=jnp.float32,
                    sstep_s: int = 4, storage_dtype=None) -> float:
    """Modelled HBM array-passes per PCG iteration for one engine.

    One "pass" = one full node-array read or write against HBM.

      xla / pallas — every iterate and operand streams each use:
        stencil (read p, a, b; write ap)                      4
        denom dot (read ap, p — assume fused into stencil)    0
        w/r update (read w, r, p, ap; write w, r)             6
        z = r * dinv (read r?, dinv; write z — r fused)       2
        zr dot (fused into z)                                 0
        p = z + beta*p (read z?, p; write p — z fused)        1
        => ~13 passes (matches the measured HBM-bound regime)
      fused — K1 reads z, p, 4 coefficient arrays, writes pn, ap (8);
        K2 reads w, r, pn, ap, dinv, writes w, r, z (8) => 16
        (more traffic than xla — why it only wins while compute-bound)
      pipelined / pipelined-pallas — bundle+stencil pass reads
        r, u, w, s, p, dinv, a, b and writes n (9); the seven-vector
        update pass reads n, z, s, p, u, w, r, x, dinv and writes
        z, s, p, x, r, u, w (16); + the 4-stencil residual replacement
        amortised over its cadence => ~25.6. Twice xla's traffic —
        the price of halving the reductions; the engine's payoff is
        collective latency on the mesh, not HBM economy.
      resident — HBM touched twice per *solve*, not per iteration => 0
      streamed — state is VMEM-resident; only non-resident operands
        stream (``StreamPlan.streamed_passes_per_iter``)
    """
    if engine in ("xla", "pallas"):
        return 13.0
    if engine in ("mg-pcg", "cheb-pcg", "fmg"):
        # the classical loop's 13 plus the preconditioner's modeled
        # extra traffic (V-cycle levels geometrically discounted /
        # Chebyshev degree; mg.engine.modeled_extra_passes). More
        # bytes per iteration, ~order-of-magnitude fewer iterations —
        # the trade the bench "precond" key measures end to end. fmg's
        # reported iterations are its verification-handoff iterations
        # (the same V-cycle-preconditioned loop), so the per-iteration
        # figure is mg-pcg's; the F-cycle prelude's fixed O(N) bytes
        # are the work-unit model's column (mg.fmg.work_units_per_point),
        # not a per-iteration quantity.
        from poisson_ellipse_tpu.mg.engine import modeled_extra_passes

        return 13.0 + modeled_extra_passes(problem, engine, dtype)
    if engine == "fused":
        return 16.0
    if engine in ("pipelined", "pipelined-pallas"):
        from poisson_ellipse_tpu.ops.precision import replace_every

        # the replacement amortisation follows the EFFECTIVE cadence:
        # 32 at full width, 8 under sub-compute storage (4× the rebuild
        # passes — the narrow build's model must carry them)
        return 25.0 + 4.0 * 5.0 / replace_every(storage_dtype, dtype)
    if engine in ("sstep", "sstep-pallas"):
        # per BLOCK of s iterations: 2s−1 Â = D⁻¹A applications (read
        # v/a/b/dinv, write out: ~6 passes each), one Gram pass over the
        # K = 2s+1 basis arrays (d rides fused), one reconstruction pass
        # over the basis + 3 writes; replacement (1 stencil ≈ 5 passes)
        # amortised over its storage-effective cadence. More bytes/iter
        # than classical — the engine's win is 1/s collectives, and with
        # bf16 storage the whole bill halves
        # (modeled_hbm_bytes_per_iter's storage itemsize).
        from poisson_ellipse_tpu.ops.precision import replace_every

        s = sstep_s
        K = 2 * s + 1
        return ((2 * s - 1) * 6.0 + 2 * K + 3.0) / s + 5.0 / replace_every(
            storage_dtype, dtype
        )
    if engine == "xl":
        from poisson_ellipse_tpu.ops.xl_pcg import XLPlan

        return XLPlan(problem, dtype).passes_per_iter()
    if engine == "resident":
        return 0.0
    if engine == "streamed":
        from poisson_ellipse_tpu.ops.streamed_pcg import StreamPlan

        return StreamPlan(problem, dtype).streamed_passes_per_iter()
    raise ValueError(f"no traffic model for engine {engine!r}")


def modeled_hbm_bytes_per_iter(problem: Problem, engine: str,
                               dtype=jnp.float32, storage_dtype=None,
                               sstep_s: int = 4) -> float:
    """The traffic model's HBM bytes per iteration for one engine —
    ``passes_per_iter`` × unpadded node-array bytes. This is the
    "modeled" column ``obs.static_cost`` sets next to XLA's own
    bytes-accessed estimate (the "measured" static column), so model
    drift against the compiler's accounting is visible per engine in
    ``harness inspect`` instead of only as a bench-day surprise.

    ``storage_dtype`` models the narrow-storage byte bill: the loop
    engines stream state AND operands at storage width, so every
    modeled pass narrows by the storage/compute itemsize ratio — bf16
    under f32 is exactly the ~2× cut the ``bandwidth`` bench key
    measures. (streamed/xl narrow their operand share only; their
    modeled figure with storage set is therefore a lower bound for
    them, labelled as the loop-engine model.)
    """
    from poisson_ellipse_tpu.ops.precision import storage_itemsize

    g1, g2 = problem.node_shape
    return (
        passes_per_iter(problem, engine, dtype, sstep_s=sstep_s,
                        storage_dtype=storage_dtype)
        * g1 * g2 * storage_itemsize(dtype, storage_dtype)
    )


def roofline(
    problem: Problem,
    engine: str,
    iters: int,
    t_solver: float,
    dtype=jnp.float32,
    device=None,
    n_devices: int = 1,
    storage_dtype=None,
    sstep_s: int = 4,
) -> dict:
    """Achieved per-device GB/s + fraction-of-HBM-peak for a measured solve.

    Returns {"passes_per_iter", "hbm_gbps", "hbm_peak_frac"} —
    hbm_peak_frac is None when the device's peak is unknown (CPU runs).
    For sharded runs (n_devices > 1) the global traffic divides over the
    mesh, so the figures are per-chip utilisation against one chip's
    peak; halo-exchange bytes (ICI, not HBM) are not modelled.
    """
    from poisson_ellipse_tpu.ops.precision import storage_itemsize

    g1, g2 = problem.node_shape
    array_bytes = g1 * g2 * storage_itemsize(dtype, storage_dtype)
    passes = passes_per_iter(problem, engine, dtype, sstep_s=sstep_s,
                             storage_dtype=storage_dtype)
    bytes_per_dev = passes * array_bytes * max(iters, 1) / max(n_devices, 1)
    gbps = bytes_per_dev / t_solver / 1e9 if t_solver > 0 else 0.0
    peak = hbm_peak_bytes_per_s(device)
    return {
        "passes_per_iter": passes,
        "hbm_gbps": round(gbps, 2),
        "hbm_peak_frac": (
            round(bytes_per_dev / t_solver / peak, 4)
            if peak and t_solver > 0
            else None
        ),
    }
