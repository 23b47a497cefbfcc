"""Pallas TPU kernels for the hot PCG ops (reference stage4 kernel parity).

The reference's device-kernel inventory (``stage4-mpi+cuda/
poisson_mpi_cuda2.cu``): ``apply_A_kernel`` (:507-536), ``apply_Dinv_kernel``
(:541-562), ``dot_kernel`` (:574-598), ``update_w_r_kernel`` (fused axpy +
‖Δw‖² partials, :626-660), ``update_p_kernel`` (:663-676). Here the same
five live as Pallas kernels tiled over VMEM:

- the stencil reads a (TM+2)-row halo window per TM-row output tile. A
  ``BlockSpec`` index map cannot express overlapping windows (offsets are
  in whole blocks), so inputs stay in ``ANY``/HBM and each tile DMAs its
  window into VMEM scratch explicitly — the TPU-idiomatic form of the
  reference's 16×16 CUDA tiling (its halo reads come from L2 instead).
- the dot / update kernels are row-tiled reductions that accumulate a
  per-call scalar in SMEM scratch across the (sequential) TPU grid —
  where the CUDA dot deliberately ships 32768 partials to the host
  (:570-573, :779-785), the TPU grid's serial execution lets one SMEM
  cell do the whole reduction on device.

Layout contract (the "block" layout of ``ops.stencil``): operand arrays
are halo-extended, shape (bm+2, bn+2); outputs are (bm, bn). The stencil
pads internally up to Mosaic's (8, 128) DMA tiling (padding carries zero
coefficients, so padded nodes behave like the Dirichlet exterior — same
trick as ``parallel.mesh.padded_dims``); the elementwise/reduction
kernels want a row count with a power-of-two factor to tile well (see
``_row_tile``).

Measured on v5e (800×1200 / 2400×3200 full solves): the XLA-fused path
stays ahead of the Pallas stencil (0.072 s vs 0.078 s / 1.20 s vs 1.82 s)
because XLA fuses the stencil into the surrounding vector ops and its
slice windows need no alignment padding — so ``stencil="xla"`` remains
the solver default and these kernels are the explicitly-tiled alternative
(and the reference-kernel parity surface).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Rows of output computed per grid step. 128 keeps the three (TM+2)-row
# f32 input windows + one TM-row output tile a few MB — comfortably in
# the ~16 MB VMEM with room for Mosaic's own buffers.
TILE_ROWS = 128


# VMEM working-set budget for one kernel invocation's live blocks. The
# hardware has ~16 MB; leave headroom for Mosaic's own pipeline buffers.
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def _row_tile(g1: int, g2: int, itemsize: int, n_buffers: int) -> int:
    """Largest 8-multiple row tile dividing g1 whose n_buffers blocks
    (double-buffered by the pipeline) fit the VMEM budget.

    The elementwise/reduction kernels use plain BlockSpec pipelining, so
    the tile must divide the row count exactly; callers pad rows to an
    8-multiple first (``_pad_rows``), which guarantees a divisor exists.
    Bounding by bytes (not a fixed row cap) keeps wide benchmark grids
    like 3201-column 2400x3200 compilable.
    """
    row_bytes = g2 * itemsize * n_buffers * 2  # ×2: pipeline double buffer
    cap = max(_VMEM_BUDGET_BYTES // max(row_bytes, 1), 8)
    best = 8
    for tm in range(8, min(cap, g1) + 1, 8):
        if g1 % tm == 0:
            best = tm
    return best if g1 % 8 == 0 else g1


def _pad_rows(*arrays):
    """Zero-pad each (g1, g2) array to an 8-multiple row count.

    Node grids are (M+1, N+1) — an odd row count for every even-M
    benchmark size — and a whole-array VMEM block would overflow on big
    grids, so the elementwise kernels tile over an 8-aligned padding
    instead (padding rows are zeros: harmless to the reductions, sliced
    off the outputs).
    """
    g1 = arrays[0].shape[0]
    k = round_up(g1, 8)
    if k == g1:
        return arrays
    return tuple(jnp.pad(x, ((0, k - g1), (0, 0))) for x in arrays)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _stencil_kernel(h1, h2, tm, bn, w_hbm, a_hbm, b_hbm, out_ref, w_s, a_s, b_s, sems):
    """One TM-row tile of the 5-point variable-coefficient stencil."""
    r0 = pl.program_id(0) * tm
    copies = [
        pltpu.make_async_copy(src.at[pl.ds(r0, tm + 8), :], dst, sems.at[i])
        for i, (src, dst) in enumerate(
            [(w_hbm, w_s), (a_hbm, a_s), (b_hbm, b_s)]
        )
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()

    # expression tree mirrors ops.stencil.apply_a_block term for term so
    # the two paths agree to the ulp (iteration-count parity)
    wc = w_s[1 : tm + 1, 1 : bn + 1]
    ax = -(
        a_s[2 : tm + 2, 1 : bn + 1] * (w_s[2 : tm + 2, 1 : bn + 1] - wc) / h1
        - a_s[1 : tm + 1, 1 : bn + 1] * (wc - w_s[0:tm, 1 : bn + 1]) / h1
    ) / h1
    ay = -(
        b_s[1 : tm + 1, 2 : bn + 2] * (w_s[1 : tm + 1, 2 : bn + 2] - wc) / h2
        - b_s[1 : tm + 1, 1 : bn + 1] * (wc - w_s[1 : tm + 1, 0:bn]) / h2
    ) / h2
    out_ref[:] = ax + ay


def apply_a_block_pallas(w_ext, a_ext, b_ext, h1, h2, interpret=None,
                         vma=None):
    """A·w over a halo-extended block: (bm+2, bn+2) inputs → (bm, bn).

    Pallas twin of ``ops.stencil.apply_a_block`` (bit-compatible FP form:
    each difference divided by h before combining, as the reference does).

    ``vma``: mesh axis names the output varies over — required when the
    kernel runs per-shard inside ``jax.shard_map`` (whose vma checking
    needs every pallas_call out_shape annotated).

    Each TM-row output tile DMAs an aligned (TM+8)-row input window —
    Mosaic requires HBM slice offsets/sizes 8-row-aligned, so a bare
    (TM+2)-row halo window is not expressible. Inputs are therefore
    zero-padded up to ``round_up(bm, TM) + 8`` rows first; the pads of the
    loop-invariant coefficient arrays are hoisted out of solver loops by
    XLA's LICM, leaving ~one extra elementwise pass (over w) per call.
    """
    if interpret is None:
        interpret = _interpret_default()
    bm = w_ext.shape[0] - 2
    bn = w_ext.shape[1] - 2
    # balance the row tile across ceil(bm/TILE_ROWS) tiles (8-aligned) so
    # at most 7 garbage pad rows are computed per call, instead of up to
    # tm-1 with a fixed tile (bm=799 would waste 97 rows every iteration)
    n_tiles = -(-bm // TILE_ROWS)
    tm = round_up(-(-bm // n_tiles), 8)
    k = round_up(bm, tm)
    # Mosaic DMA slices must be (8, 128)-tile-aligned in both dims: pad
    # rows to k+8 (each tile DMAs an aligned (tm+8)-row window) and cols
    # to a lane multiple
    cols = round_up(bn + 2, 128)
    pad = ((0, k + 8 - (bm + 2)), (0, cols - (bn + 2)))
    w_p = jnp.pad(w_ext, pad)
    a_p = jnp.pad(a_ext, pad)
    b_p = jnp.pad(b_ext, pad)
    dtype = w_ext.dtype
    # grid spacings are compile-time constants of the problem; baking them
    # in as Python floats keeps them out of SMEM entirely
    kernel = functools.partial(_stencil_kernel, float(h1), float(h2), tm, bn)
    out = pl.pallas_call(
        kernel,
        grid=(k // tm,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(
            (tm, bn), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((k, bn), dtype, vma=vma),
        scratch_shapes=[
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=interpret,
    )(w_p, a_p, b_p)
    return out[:bm]


def apply_a_pallas(w, a, b, h1, h2, interpret=None):
    """A·w on the full node grid (Pallas twin of ``ops.stencil.apply_a``):
    interior written, boundary ring stays zero."""
    return jnp.pad(
        apply_a_block_pallas(w, a, b, h1, h2, interpret=interpret), 1
    )


def _stencil_dots_kernel(h1, h2, tm, bn, n_pairs, n_tiles, *refs):
    """One TM-row tile of the fused stencil + dot-partials pass.

    Layout of ``refs`` (the pallas_call flattens them positionally):
      inputs   w_hbm, a_hbm, b_hbm (ANY/HBM, DMA'd in aligned windows),
               then 2·n_pairs VMEM-blocked dot operands x₀ y₀ x₁ y₁ …
      outputs  out_ref (the stencil tile), sums_out (SMEM, (n_pairs,))
      scratch  w_s, a_s, b_s window buffers, DMA semaphores, SMEM acc
    """
    w_hbm, a_hbm, b_hbm = refs[0:3]
    pair_refs = refs[3 : 3 + 2 * n_pairs]
    out_ref, sums_out = refs[3 + 2 * n_pairs : 5 + 2 * n_pairs]
    w_s, a_s, b_s, sems, acc = refs[5 + 2 * n_pairs :]

    i = pl.program_id(0)
    r0 = i * tm
    copies = [
        pltpu.make_async_copy(src.at[pl.ds(r0, tm + 8), :], dst, sems.at[k])
        for k, (src, dst) in enumerate(
            [(w_hbm, w_s), (a_hbm, a_s), (b_hbm, b_s)]
        )
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()

    # expression tree mirrors ops.stencil.apply_a_block term for term
    # (each difference divided by h before combining) — ulp-compatible
    # with the XLA stencil, same as _stencil_kernel
    wc = w_s[1 : tm + 1, 1 : bn + 1]
    ax = -(
        a_s[2 : tm + 2, 1 : bn + 1] * (w_s[2 : tm + 2, 1 : bn + 1] - wc) / h1
        - a_s[1 : tm + 1, 1 : bn + 1] * (wc - w_s[0:tm, 1 : bn + 1]) / h1
    ) / h1
    ay = -(
        b_s[1 : tm + 1, 2 : bn + 2] * (w_s[1 : tm + 1, 2 : bn + 2] - wc) / h2
        - b_s[1 : tm + 1, 1 : bn + 1] * (wc - w_s[1 : tm + 1, 0:bn]) / h2
    ) / h2
    out_ref[:] = ax + ay

    @pl.when(i == 0)
    def _():
        for j in range(n_pairs):
            acc[j] = jnp.zeros((), wc.dtype)

    for j in range(n_pairs):
        acc[j] += jnp.sum(pair_refs[2 * j][:] * pair_refs[2 * j + 1][:])

    @pl.when(i == n_tiles - 1)
    def _():
        for j in range(n_pairs):
            sums_out[j] = acc[j]


def apply_a_block_dots_pallas(w_ext, a_ext, b_ext, h1, h2, pairs,
                              interpret=None, vma=None):
    """A·w over a halo-extended block PLUS k dot partials, one VMEM pass.

    ``pairs`` is a sequence of (x, y) arrays shaped like the (bm, bn)
    output; returns ``(Aw_block, sums)`` with ``sums[j] = Σ xⱼ·yⱼ`` (raw,
    unweighted — the ``ops.reduction.grid_dots`` contract). The point is
    HBM economy for the pipelined iteration: the classical structure
    reads each dot operand once for the stencil pass and again for the
    reduction pass, whereas here every operand streams through VMEM
    exactly once while the stencil tile is in flight — and on a mesh the
    (k,) partials vector is exactly what rides the iteration's single
    stacked ``lax.psum`` (``parallel.pipelined_sharded``).

    Tiling/alignment contract is ``apply_a_block_pallas``'s: stencil
    inputs stay in ANY/HBM and are DMA'd in aligned (TM+8)-row windows;
    the dot operands ride ordinary double-buffered BlockSpec pipelining.
    The TPU grid runs tiles sequentially, so SMEM accumulators finish the
    reductions on device (``_dot_kernel``'s structure, widened to k).
    """
    if interpret is None:
        interpret = _interpret_default()
    pairs = tuple(pairs)
    n_pairs = len(pairs)
    if n_pairs == 0:
        raise ValueError("need at least one (x, y) dot pair")
    bm = w_ext.shape[0] - 2
    bn = w_ext.shape[1] - 2
    n_tiles = -(-bm // TILE_ROWS)
    tm = round_up(-(-bm // n_tiles), 8)
    k = round_up(bm, tm)
    cols = round_up(bn + 2, 128)
    pad = ((0, k + 8 - (bm + 2)), (0, cols - (bn + 2)))
    w_p = jnp.pad(w_ext, pad)
    a_p = jnp.pad(a_ext, pad)
    b_p = jnp.pad(b_ext, pad)
    # zero row padding: contributes nothing to the dot partials
    flat = []
    for x, y in pairs:
        flat += [jnp.pad(x, ((0, k - bm), (0, 0))), jnp.pad(y, ((0, k - bm), (0, 0)))]
    dtype = w_ext.dtype
    blk = lambda: pl.BlockSpec(
        (tm, bn), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    kernel = functools.partial(
        _stencil_dots_kernel, float(h1), float(h2), tm, bn, n_pairs,
        k // tm,
    )
    out, sums = pl.pallas_call(
        kernel,
        grid=(k // tm,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3
        + [blk() for _ in range(2 * n_pairs)],
        out_specs=(
            pl.BlockSpec((tm, bn), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((k, bn), dtype, vma=vma),
            jax.ShapeDtypeStruct((n_pairs,), dtype, vma=vma),
        ),
        scratch_shapes=[
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SMEM((n_pairs,), dtype),
        ],
        interpret=interpret,
    )(w_p, a_p, b_p, *flat)
    return out[:bm], sums


def apply_a_dots_pallas(w, a, b, h1, h2, pairs, interpret=None):
    """Full-node-grid twin of ``apply_a_block_dots_pallas``: (M+1, N+1)
    inputs, stencil written on the interior with a zero boundary ring,
    dot pairs over the full node grid (iterates are zero on the ring, so
    full-grid sums equal interior sums — the ``ops.reduction`` layout
    invariant)."""
    # dot operands enter the kernel cropped to the stencil's (bm, bn)
    # interior tile shape; the ring they lose is exactly zero
    cropped = tuple((x[1:-1, 1:-1], y[1:-1, 1:-1]) for x, y in pairs)
    out, sums = apply_a_block_dots_pallas(
        w, a, b, h1, h2, cropped, interpret=interpret
    )
    return jnp.pad(out, 1), sums


def _batched_stencil_kernel(h1, h2, tm, bn, w_hbm, a_hbm, b_hbm, out_ref,
                            w_s, a_s, b_s, sems):
    """One (lane, TM-row) tile of the batched 5-point stencil.

    The lane dimension rides the FIRST grid axis: grid=(B, n_tiles), so
    each program DMAs its lane's aligned (TM+8)-row window of ``w`` and
    the lane-shared coefficient windows. Coefficient windows depend only
    on the row tile, so their DMA re-fetches per lane are VMEM-friendly
    re-reads of the same HBM lines (the shared-geometry serving layout).
    """
    lane = pl.program_id(0)
    r0 = pl.program_id(1) * tm
    copies = [
        pltpu.make_async_copy(
            w_hbm.at[lane, pl.ds(r0, tm + 8), :], w_s, sems.at[0]
        ),
        pltpu.make_async_copy(a_hbm.at[pl.ds(r0, tm + 8), :], a_s, sems.at[1]),
        pltpu.make_async_copy(b_hbm.at[pl.ds(r0, tm + 8), :], b_s, sems.at[2]),
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()

    # expression tree mirrors ops.stencil.apply_a_block term for term
    wc = w_s[1 : tm + 1, 1 : bn + 1]
    ax = -(
        a_s[2 : tm + 2, 1 : bn + 1] * (w_s[2 : tm + 2, 1 : bn + 1] - wc) / h1
        - a_s[1 : tm + 1, 1 : bn + 1] * (wc - w_s[0:tm, 1 : bn + 1]) / h1
    ) / h1
    ay = -(
        b_s[1 : tm + 1, 2 : bn + 2] * (w_s[1 : tm + 1, 2 : bn + 2] - wc) / h2
        - b_s[1 : tm + 1, 1 : bn + 1] * (wc - w_s[1 : tm + 1, 0:bn]) / h2
    ) / h2
    out_ref[0] = ax + ay


def _batched_tiling(w):
    """(tm, k, cols, pads) for a (B, bm+2, bn+2) batched operand — the
    ``apply_a_block_pallas`` alignment contract per lane."""
    bm = w.shape[1] - 2
    bn = w.shape[2] - 2
    n_tiles = -(-bm // TILE_ROWS)
    tm = round_up(-(-bm // n_tiles), 8)
    k = round_up(bm, tm)
    cols = round_up(bn + 2, 128)
    return bm, bn, tm, k, cols


def apply_a_batched_block_pallas(w, a_ext, b_ext, h1, h2, interpret=None):
    """A·w per lane over halo-extended blocks: (B, bm+2, bn+2) iterate,
    (bm+2, bn+2) lane-shared coefficients → (B, bm, bn).

    The batched twin of ``apply_a_block_pallas`` with the lane dimension
    mapped onto the Pallas grid — grid=(B, row_tiles) — so one kernel
    launch covers the whole batch instead of B launches (per-launch
    overhead is exactly what lane batching amortises).
    """
    if interpret is None:
        interpret = _interpret_default()
    B = w.shape[0]
    bm, bn, tm, k, cols = _batched_tiling(w)
    pad2 = ((0, k + 8 - (bm + 2)), (0, cols - (bn + 2)))
    w_p = jnp.pad(w, ((0, 0),) + pad2)
    a_p = jnp.pad(a_ext, pad2)
    b_p = jnp.pad(b_ext, pad2)
    dtype = w.dtype
    kernel = functools.partial(
        _batched_stencil_kernel, float(h1), float(h2), tm, bn
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, k // tm),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(
            (1, tm, bn), lambda l, i: (l, i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((B, k, bn), dtype),
        scratch_shapes=[
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=interpret,
    )(w_p, a_p, b_p)
    return out[:, :bm]


def apply_a_batched_pallas(w, a, b, h1, h2, interpret=None):
    """A·w per lane on full (B, M+1, N+1) node grids (zero boundary
    ring), lane-shared (M+1, N+1) coefficients — the batched twin of
    ``apply_a_pallas``."""
    return jnp.pad(
        apply_a_batched_block_pallas(w, a, b, h1, h2, interpret=interpret),
        ((0, 0), (1, 1), (1, 1)),
    )


def _batched_stencil_dots_kernel(h1, h2, tm, bn, n_pairs, n_tiles, *refs):
    """One (lane, TM-row) tile of the fused batched stencil + per-lane
    dot partials. Ref layout follows ``_stencil_dots_kernel`` with the
    lane on grid axis 0 and a per-lane column in the (n_pairs, B) SMEM
    sums output; the TPU grid's sequential execution walks lane-major,
    so the accumulator finishes lane l before lane l+1 begins.
    """
    w_hbm, a_hbm, b_hbm = refs[0:3]
    pair_refs = refs[3 : 3 + 2 * n_pairs]
    out_ref, sums_out = refs[3 + 2 * n_pairs : 5 + 2 * n_pairs]
    w_s, a_s, b_s, sems, acc = refs[5 + 2 * n_pairs :]

    lane = pl.program_id(0)
    i = pl.program_id(1)
    r0 = i * tm
    copies = [
        pltpu.make_async_copy(
            w_hbm.at[lane, pl.ds(r0, tm + 8), :], w_s, sems.at[0]
        ),
        pltpu.make_async_copy(a_hbm.at[pl.ds(r0, tm + 8), :], a_s, sems.at[1]),
        pltpu.make_async_copy(b_hbm.at[pl.ds(r0, tm + 8), :], b_s, sems.at[2]),
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()

    wc = w_s[1 : tm + 1, 1 : bn + 1]
    ax = -(
        a_s[2 : tm + 2, 1 : bn + 1] * (w_s[2 : tm + 2, 1 : bn + 1] - wc) / h1
        - a_s[1 : tm + 1, 1 : bn + 1] * (wc - w_s[0:tm, 1 : bn + 1]) / h1
    ) / h1
    ay = -(
        b_s[1 : tm + 1, 2 : bn + 2] * (w_s[1 : tm + 1, 2 : bn + 2] - wc) / h2
        - b_s[1 : tm + 1, 1 : bn + 1] * (wc - w_s[1 : tm + 1, 0:bn]) / h2
    ) / h2
    out_ref[0] = ax + ay

    @pl.when(i == 0)
    def _():
        for j in range(n_pairs):
            acc[j] = jnp.zeros((), wc.dtype)

    for j in range(n_pairs):
        acc[j] += jnp.sum(pair_refs[2 * j][0] * pair_refs[2 * j + 1][0])

    @pl.when(i == n_tiles - 1)
    def _():
        for j in range(n_pairs):
            sums_out[j, lane] = acc[j]


def apply_a_dots_batched_pallas(w, a, b, h1, h2, pairs, interpret=None):
    """Per-lane A·w PLUS per-lane dot partials, one fused VMEM pass.

    ``w`` is (B, M+1, N+1); ``a``/``b`` lane-shared (M+1, N+1);
    ``pairs`` a sequence of ((B, M+1, N+1), (B, M+1, N+1)) operand
    pairs. Returns ``(Aw, sums)`` with ``Aw`` (B, M+1, N+1) (zero ring)
    and ``sums`` (n_pairs, B) raw per-lane Σ xⱼ·yⱼ — exactly the
    stacked (k, B) bundle of ``batch.batched_pcg.lane_dots``, produced
    while each lane's stencil tile is in flight. The batched pipelined
    engine's whole (8, B) bundle rides this single kernel launch.
    """
    if interpret is None:
        interpret = _interpret_default()
    pairs = tuple(pairs)
    n_pairs = len(pairs)
    if n_pairs == 0:
        raise ValueError("need at least one (x, y) dot pair")
    B = w.shape[0]
    bm, bn, tm, k, cols = _batched_tiling(w)
    pad2 = ((0, k + 8 - (bm + 2)), (0, cols - (bn + 2)))
    w_p = jnp.pad(w, ((0, 0),) + pad2)
    a_p = jnp.pad(a, pad2)
    b_p = jnp.pad(b, pad2)
    # dot operands enter cropped to the (bm, bn) interior tile shape and
    # zero-row-padded to the tile multiple (zero rows add nothing)
    flat = []
    for x, y in pairs:
        flat += [
            jnp.pad(x[:, 1:-1, 1:-1], ((0, 0), (0, k - bm), (0, 0))),
            jnp.pad(y[:, 1:-1, 1:-1], ((0, 0), (0, k - bm), (0, 0))),
        ]
    dtype = w.dtype
    blk = lambda: pl.BlockSpec(
        (1, tm, bn), lambda l, i: (l, i, 0), memory_space=pltpu.VMEM
    )
    kernel = functools.partial(
        _batched_stencil_dots_kernel, float(h1), float(h2), tm, bn,
        n_pairs, k // tm,
    )
    out, sums = pl.pallas_call(
        kernel,
        grid=(B, k // tm),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3
        + [blk() for _ in range(2 * n_pairs)],
        out_specs=(
            pl.BlockSpec(
                (1, tm, bn), lambda l, i: (l, i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, k, bn), dtype),
            jax.ShapeDtypeStruct((n_pairs, B), dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.VMEM((tm + 8, cols), dtype),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SMEM((n_pairs,), dtype),
        ],
        interpret=interpret,
    )(w_p, a_p, b_p, *flat)
    return jnp.pad(out[:, :bm], ((0, 0), (1, 1), (1, 1))), sums


def _dinv_kernel(r_ref, d_ref, out_ref):
    d = d_ref[:]
    safe = jnp.where(d != 0.0, d, 1.0)
    out_ref[:] = jnp.where(d != 0.0, r_ref[:] / safe, 0.0)


def apply_dinv_pallas(r, d, interpret=None):
    """z = r / D with zero guard (``apply_Dinv_kernel``, cu:541-562)."""
    if interpret is None:
        interpret = _interpret_default()
    g1, g2 = r.shape
    r_p, d_p = _pad_rows(r, d)
    k = r_p.shape[0]
    tm = _row_tile(k, g2, r.dtype.itemsize, 3)
    out = pl.pallas_call(
        _dinv_kernel,
        grid=(k // tm,),
        in_specs=[
            pl.BlockSpec((tm, g2), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tm, g2), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tm, g2), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((k, g2), r.dtype),
        interpret=interpret,
    )(r_p, d_p)
    return out[:g1]


def _dot_kernel(x_ref, y_ref, out_ref, acc):
    @pl.when(pl.program_id(0) == 0)
    def _():
        acc[0] = jnp.zeros((), x_ref.dtype)

    acc[0] += jnp.sum(x_ref[:] * y_ref[:])

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _():
        out_ref[0] = acc[0]


def dot_pallas(x, y, h1, h2, interpret=None):
    """Grid-weighted inner product h1·h2·Σxy (``dot_kernel``, cu:574-598).

    The TPU grid runs tiles sequentially, so one SMEM accumulator
    replaces the reference's 32768 host-summed partials (cu:779-785).
    """
    if interpret is None:
        interpret = _interpret_default()
    g2 = x.shape[1]
    x_p, y_p = _pad_rows(x, y)  # zero rows contribute nothing to the sum
    k = x_p.shape[0]
    tm = _row_tile(k, g2, x.dtype.itemsize, 2)
    s = pl.pallas_call(
        _dot_kernel,
        grid=(k // tm,),
        in_specs=[
            pl.BlockSpec((tm, g2), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tm, g2), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1,), x.dtype),
        scratch_shapes=[pltpu.SMEM((1,), x.dtype)],
        interpret=interpret,
    )(x_p, y_p)
    return s[0] * jnp.asarray(h1, x.dtype) * jnp.asarray(h2, x.dtype)


def _update_wr_kernel(alpha_ref, w_ref, r_ref, p_ref, ap_ref,
                      w_out, r_out, dw2_out, acc):
    @pl.when(pl.program_id(0) == 0)
    def _():
        acc[0] = jnp.zeros((), w_ref.dtype)

    alpha = alpha_ref[0]
    w_old = w_ref[:]
    w_new = w_old + alpha * p_ref[:]
    w_out[:] = w_new
    r_out[:] = r_ref[:] - alpha * ap_ref[:]
    # realised increment (w_new - w_old), not alpha*p: the two differ in
    # FP and the convergence oracle counts depend on it (cu:626-660 also
    # differences the stored iterates)
    dw = w_new - w_old
    acc[0] += jnp.sum(dw * dw)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _():
        dw2_out[0] = acc[0]


def update_w_r_pallas(alpha, w, r, p, ap, interpret=None):
    """Fused w += αp, r −= αAp, Σ(Δw)² (``update_w_r_kernel``, cu:626-660).

    Returns (w_new, r_new, dw2). The ‖Δw‖² partial is computed from the
    realised increment exactly as the reference kernel does.
    """
    if interpret is None:
        interpret = _interpret_default()
    g1, g2 = w.shape
    w_p, r_p, p_p, ap_p = _pad_rows(w, r, p, ap)
    k = w_p.shape[0]
    tm = _row_tile(k, g2, w.dtype.itemsize, 6)
    blk = lambda: pl.BlockSpec(
        (tm, g2), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    w_new, r_new, dw2 = pl.pallas_call(
        _update_wr_kernel,
        grid=(k // tm,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            blk(),
            blk(),
            blk(),
            blk(),
        ],
        out_specs=(blk(), blk(), pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=(
            jax.ShapeDtypeStruct((k, g2), w.dtype),
            jax.ShapeDtypeStruct((k, g2), w.dtype),
            jax.ShapeDtypeStruct((1,), w.dtype),
        ),
        scratch_shapes=[pltpu.SMEM((1,), w.dtype)],
        interpret=interpret,
    )(jnp.reshape(alpha, (1,)), w_p, r_p, p_p, ap_p)
    return w_new[:g1], r_new[:g1], dw2[0]


def _update_p_kernel(beta_ref, z_ref, p_ref, out_ref):
    out_ref[:] = z_ref[:] + beta_ref[0] * p_ref[:]


def update_p_pallas(beta, z, p, interpret=None):
    """p = z + βp (``update_p_kernel``, cu:663-676)."""
    if interpret is None:
        interpret = _interpret_default()
    g1, g2 = p.shape
    z_p, p_p = _pad_rows(z, p)
    k = z_p.shape[0]
    tm = _row_tile(k, g2, p.dtype.itemsize, 3)
    blk = lambda: pl.BlockSpec(
        (tm, g2), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        _update_p_kernel,
        grid=(k // tm,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk(), blk()],
        out_specs=blk(),
        out_shape=jax.ShapeDtypeStruct((k, g2), p.dtype),
        interpret=interpret,
    )(jnp.reshape(beta, (1,)), z_p, p_p)[:g1]


# --------------------------------------------------------------------------
# mixed-precision kernels: storage-width HBM tiles, compute-width VMEM math
# --------------------------------------------------------------------------
#
# The bf16-storage axis (``ops.precision``): arrays live at storage width
# in HBM — halving the stencil's dominant byte stream — and every tile is
# upcast to the compute dtype *after* the DMA, inside VMEM, so the
# arithmetic (and the SMEM dot accumulators) run at full precision. These
# are the explicitly-tiled twins of what the XLA path gets from fusing a
# ``convert_element_type`` into the consumer; the FP expression tree is
# the same term-for-term stencil as ``_stencil_kernel``, evaluated at
# compute width on upcast operands.


def _stencil_kernel_mixed(h1, h2, tm, bn, compute, w_hbm, a_hbm, b_hbm,
                          out_ref, w_s, a_s, b_s, sems):
    """One TM-row stencil tile: storage-width windows, compute-width math."""
    r0 = pl.program_id(0) * tm
    copies = [
        pltpu.make_async_copy(src.at[pl.ds(r0, tm + 8), :], dst, sems.at[i])
        for i, (src, dst) in enumerate(
            [(w_hbm, w_s), (a_hbm, a_s), (b_hbm, b_s)]
        )
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()

    # the tile-local upcast: the DMA moved storage-width bytes; the VPU
    # sees compute-width operands from here on
    w_c = w_s[:].astype(compute)
    a_c = a_s[:].astype(compute)
    b_c = b_s[:].astype(compute)
    wc = w_c[1 : tm + 1, 1 : bn + 1]
    ax = -(
        a_c[2 : tm + 2, 1 : bn + 1] * (w_c[2 : tm + 2, 1 : bn + 1] - wc) / h1
        - a_c[1 : tm + 1, 1 : bn + 1] * (wc - w_c[0:tm, 1 : bn + 1]) / h1
    ) / h1
    ay = -(
        b_c[1 : tm + 1, 2 : bn + 2] * (w_c[1 : tm + 1, 2 : bn + 2] - wc) / h2
        - b_c[1 : tm + 1, 1 : bn + 1] * (wc - w_c[1 : tm + 1, 0:bn]) / h2
    ) / h2
    out_ref[:] = (ax + ay).astype(out_ref.dtype)


def apply_a_block_mixed_pallas(w_ext, a_ext, b_ext, h1, h2,
                               compute_dtype=jnp.float32, out_dtype=None,
                               interpret=None, vma=None):
    """Mixed-precision A·w over a halo-extended block.

    Inputs may each carry their own (storage) dtype — bf16 state with
    bf16-rounded coefficients is the intended pairing — and are upcast
    tile-locally to ``compute_dtype`` in VMEM; the output is written at
    ``out_dtype`` (default: ``compute_dtype``, so downstream reductions
    see full-width values). Alignment/tiling contract is
    ``apply_a_block_pallas``'s.
    """
    if interpret is None:
        interpret = _interpret_default()
    out_dtype = jnp.dtype(out_dtype or compute_dtype)
    bm = w_ext.shape[0] - 2
    bn = w_ext.shape[1] - 2
    n_tiles = -(-bm // TILE_ROWS)
    tm = round_up(-(-bm // n_tiles), 8)
    k = round_up(bm, tm)
    cols = round_up(bn + 2, 128)
    pad = ((0, k + 8 - (bm + 2)), (0, cols - (bn + 2)))
    w_p = jnp.pad(w_ext, pad)
    a_p = jnp.pad(a_ext, pad)
    b_p = jnp.pad(b_ext, pad)
    kernel = functools.partial(
        _stencil_kernel_mixed, float(h1), float(h2), tm, bn,
        jnp.dtype(compute_dtype),
    )
    out = pl.pallas_call(
        kernel,
        grid=(k // tm,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(
            (tm, bn), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((k, bn), out_dtype, vma=vma),
        scratch_shapes=[
            pltpu.VMEM((tm + 8, cols), w_p.dtype),
            pltpu.VMEM((tm + 8, cols), a_p.dtype),
            pltpu.VMEM((tm + 8, cols), b_p.dtype),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=interpret,
    )(w_p, a_p, b_p)
    return out[:bm]


def apply_a_mixed_pallas(w, a, b, h1, h2, compute_dtype=jnp.float32,
                         out_dtype=None, interpret=None):
    """Full-node-grid mixed stencil: storage-width (M+1, N+1) inputs,
    compute-width interior output with a zero boundary ring."""
    return jnp.pad(
        apply_a_block_mixed_pallas(
            w, a, b, h1, h2, compute_dtype=compute_dtype,
            out_dtype=out_dtype, interpret=interpret,
        ),
        1,
    )


def _stencil_dots_kernel_mixed(h1, h2, tm, bn, n_pairs, n_tiles, compute,
                               *refs):
    """Mixed twin of ``_stencil_dots_kernel``: storage-width operands,
    compute-width stencil arithmetic AND dot accumulation (the SMEM
    accumulator is compute-width — the f32 accumulator route TPU018
    lints for)."""
    w_hbm, a_hbm, b_hbm = refs[0:3]
    pair_refs = refs[3 : 3 + 2 * n_pairs]
    out_ref, sums_out = refs[3 + 2 * n_pairs : 5 + 2 * n_pairs]
    w_s, a_s, b_s, sems, acc = refs[5 + 2 * n_pairs :]

    i = pl.program_id(0)
    r0 = i * tm
    copies = [
        pltpu.make_async_copy(src.at[pl.ds(r0, tm + 8), :], dst, sems.at[k])
        for k, (src, dst) in enumerate(
            [(w_hbm, w_s), (a_hbm, a_s), (b_hbm, b_s)]
        )
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()

    w_c = w_s[:].astype(compute)
    a_c = a_s[:].astype(compute)
    b_c = b_s[:].astype(compute)
    wc = w_c[1 : tm + 1, 1 : bn + 1]
    ax = -(
        a_c[2 : tm + 2, 1 : bn + 1] * (w_c[2 : tm + 2, 1 : bn + 1] - wc) / h1
        - a_c[1 : tm + 1, 1 : bn + 1] * (wc - w_c[0:tm, 1 : bn + 1]) / h1
    ) / h1
    ay = -(
        b_c[1 : tm + 1, 2 : bn + 2] * (w_c[1 : tm + 1, 2 : bn + 2] - wc) / h2
        - b_c[1 : tm + 1, 1 : bn + 1] * (wc - w_c[1 : tm + 1, 0:bn]) / h2
    ) / h2
    out_ref[:] = (ax + ay).astype(out_ref.dtype)

    @pl.when(i == 0)
    def _():
        for j in range(n_pairs):
            acc[j] = jnp.zeros((), compute)

    for j in range(n_pairs):
        acc[j] += jnp.sum(
            pair_refs[2 * j][:].astype(compute)
            * pair_refs[2 * j + 1][:].astype(compute)
        )

    @pl.when(i == n_tiles - 1)
    def _():
        for j in range(n_pairs):
            sums_out[j] = acc[j]


def apply_a_block_dots_mixed_pallas(w_ext, a_ext, b_ext, h1, h2, pairs,
                                    compute_dtype=jnp.float32,
                                    interpret=None, vma=None):
    """Mixed fused stencil + dot-partials pass over a halo-extended block.

    The storage-axis twin of ``apply_a_block_dots_pallas``: every operand
    (stencil inputs AND the 2·n_pairs dot operands) may stream at its own
    storage width and is upcast tile-locally; the stencil output and the
    (n_pairs,) partial sums come back at ``compute_dtype`` — reductions
    never accumulate at storage width (the TPU018 contract).
    """
    if interpret is None:
        interpret = _interpret_default()
    pairs = tuple(pairs)
    n_pairs = len(pairs)
    if n_pairs == 0:
        raise ValueError("need at least one (x, y) dot pair")
    compute = jnp.dtype(compute_dtype)
    bm = w_ext.shape[0] - 2
    bn = w_ext.shape[1] - 2
    n_tiles = -(-bm // TILE_ROWS)
    tm = round_up(-(-bm // n_tiles), 8)
    k = round_up(bm, tm)
    cols = round_up(bn + 2, 128)
    pad = ((0, k + 8 - (bm + 2)), (0, cols - (bn + 2)))
    w_p = jnp.pad(w_ext, pad)
    a_p = jnp.pad(a_ext, pad)
    b_p = jnp.pad(b_ext, pad)
    flat = []
    for x, y in pairs:
        flat += [jnp.pad(x, ((0, k - bm), (0, 0))),
                 jnp.pad(y, ((0, k - bm), (0, 0)))]
    blk = lambda: pl.BlockSpec(
        (tm, bn), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    kernel = functools.partial(
        _stencil_dots_kernel_mixed, float(h1), float(h2), tm, bn, n_pairs,
        k // tm, compute,
    )
    out, sums = pl.pallas_call(
        kernel,
        grid=(k // tm,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3
        + [blk() for _ in range(2 * n_pairs)],
        out_specs=(
            pl.BlockSpec((tm, bn), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((k, bn), compute, vma=vma),
            jax.ShapeDtypeStruct((n_pairs,), compute, vma=vma),
        ),
        scratch_shapes=[
            pltpu.VMEM((tm + 8, cols), w_p.dtype),
            pltpu.VMEM((tm + 8, cols), a_p.dtype),
            pltpu.VMEM((tm + 8, cols), b_p.dtype),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SMEM((n_pairs,), compute),
        ],
        interpret=interpret,
    )(w_p, a_p, b_p, *flat)
    return out[:bm], sums


def apply_a_dots_mixed_pallas(w, a, b, h1, h2, pairs,
                              compute_dtype=jnp.float32, interpret=None):
    """Full-node-grid twin of ``apply_a_block_dots_mixed_pallas`` (ring
    cropped off the dot operands exactly as ``apply_a_dots_pallas``)."""
    cropped = tuple((x[1:-1, 1:-1], y[1:-1, 1:-1]) for x, y in pairs)
    out, sums = apply_a_block_dots_mixed_pallas(
        w, a, b, h1, h2, cropped, compute_dtype=compute_dtype,
        interpret=interpret,
    )
    return jnp.pad(out, 1), sums
