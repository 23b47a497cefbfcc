"""XL engine: whole-solve kernel for grids whose STATE exceeds VMEM.

``ops.streamed_pcg`` pins the PCG state in VMEM and streams operands;
past ~2400x3200 (f32) the state itself no longer fits and the framework
previously fell back to the XLA while_loop (~13 modelled HBM passes per
iteration, measured ~67% of HBM peak at 4096² — the north-star grid).
This kernel streams the state (all of it, or all but z) and the
operands through double-buffered tile DMA, and restructures the
iteration so the traffic floor is lower than XLA's:

- **z-state form** (as the streamed engine's all-streamed regime): the
  state is (w, z, p) with z = Dinv·r, so the p-update needs no
  preconditioner stream and pass C reads dinv exactly once.
- **deferred w-update**: w += alpha*p is postponed one iteration and
  rides the NEXT AB sweep, where p's tile is already in VMEM for the
  p-update — p is read once per iteration instead of twice, and the
  realised ‖Δw‖² falls out for free. Convergence is therefore detected
  one sweep late (the loop body that *applies* iteration i's update is
  body i+1); the reported iteration count is exact, and the final
  body's extra stencil work is wasted once per solve, not per
  iteration.
- **VMEM ring for the stencil halo**: the updated direction pn is kept
  in a 3-tile ring, so the 5-point stencil's row neighbours come from
  VMEM, never re-read from HBM.

Per iteration, two sweeps (the two PCG scalar sync points set the
floor):

  AB  w += alpha*p_old; ||dw||^2;                 reads  z, p, w, a, b
      pn = z + beta*p_old -> ring + p_hbm;        writes w, p, ap
      ap = A(pn); denom partial
  C   z -= alpha*(Dinv*ap);                       reads  z, dinv, ap
      zr partial = sum(z^2 / Dinv)                writes z

- **resident z** (``XLPlan.z_resident``): where one whole z array and
  the remaining tile slots fit VMEM (square f32 grids up to 4863² on a
  128 MiB part, the 4096² north star among them), z lives in a VMEM
  scratch for the whole solve. AB reads its tile there, C updates it in
  place: 3 of the 12 passes go, ~9.08 HBM array-passes/iter. Larger
  grids stream z too: ~12.08 passes/iter vs the XLA loop's ~13. Both
  forms run the same per-element arithmetic in the same order, so at
  one tile height they give the same bits.

Measured (TPU v5e, f32): 4096² = 3.00 s for 3226 iterations with z
resident, 3.99 s with z streamed, both at about 83% of HBM peak, vs
5.16 s for the XLA loop; 8192² (z streamed) = 28.7 s / 5889 iterations
at 81.3% of peak on ONE chip — a grid the reference reaches only on a
multi-node MPI cluster. All per-element
FP forms are shared with the streamed z-state regime (verified there to
preserve the published iteration-count oracles); reductions are
tile-sequential as in every Pallas engine.

Reference lineage: this is the stage4 decomposition taken to its
single-chip limit — where ``poisson_mpi_cuda2.cu:846-939`` launches six
kernels and ships scalars through the host each iteration, here the
whole solve is ONE kernel launch and the scalars never leave SMEM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops.streamed_pcg import (
    _VMEM_LIMIT,
    _VMEM_USABLE,
    _interpret_default,
    _round_up,
    _shift_cols_left,
    _shift_cols_right,
    streamed_operand_set,
)
from poisson_ellipse_tpu.solver.pcg import DENOM_GUARD, PCGResult
from poisson_ellipse_tpu.utils.device import scaled_vmem_budget

# Candidate row-tile heights for the default policy. Measured at 4096²
# (all streamed) the timings are flat across 64/96/128 (4.28-4.30 s)
# while 256 is ~3% slower and 384 overflows VMEM (that form holds ~25
# tile slots), so the policy minimises padded rows among the tiles whose
# form fits — at 4097 rows that picks 96 (g1p = 4128 vs 4224 with 128),
# 2.3% less streamed work for free.
_TM_CANDIDATES = (64, 96, 128, 256)

# Tile slots of tm rows the z-resident form still holds (p, w, wout, a,
# b, ap, dv, apc double-buffered + the 3-slot pn ring); ``a`` adds an
# 8-row halo to each of its 2 slots. The all-streamed form adds z, zc
# and zcout's double-buffered slots.
_Z_RESIDENT_SLOTS = 19
_Z_STREAM_SLOTS = 6
_A_HALO_ROWS = 16


class XLPlan:
    """Tiling of the XL solve, and whether z stays in VMEM.

    z = Dinv·r is the state vector that costs most to stream (AB reads
    it, C reads and writes it back: 3 of the 12 passes an iteration), so
    when one whole padded z array plus the tile slots the resident form
    still needs fits ``_VMEM_USABLE`` (scaled to the default device's
    VMEM, as the kernel's own ``vmem_limit_bytes``), z lives in a VMEM
    scratch for the whole solve (``z_resident``). The default tile is
    then the least-padded candidate whose resident form fits; when none
    does, the least-padded candidate whose all-streamed form fits (the
    largest tile's 25 slots overflow VMEM on rows of 4736 f32 or more).
    ``dtype`` is the compute dtype: z keeps compute width whatever the
    operands' storage width."""

    def __init__(self, problem: Problem, dtype, tm: int | None = None):
        g1, g2 = problem.node_shape
        self.g2p = _round_up(g2, 128)
        row = self.g2p * jnp.dtype(dtype).itemsize
        budget = scaled_vmem_budget(_VMEM_USABLE)

        def tiling(t):
            t = t if g1 >= t else _round_up(g1, 8)
            return t, _round_up(g1, t)

        def fits(t, z_resident):
            t, g1p = tiling(t)
            z_rows = g1p if z_resident else _Z_STREAM_SLOTS * t
            rows = z_rows + _Z_RESIDENT_SLOTS * t + _A_HALO_ROWS
            return rows * row <= budget

        if tm is None:
            # least padded rows; larger tile breaks ties (fewer steps)
            tm = min(
                [t for t in _TM_CANDIDATES if fits(t, True)]
                or [t for t in _TM_CANDIDATES if fits(t, False)]
                or _TM_CANDIDATES,
                key=lambda t: (_round_up(g1, t), -t),
            )
        if tm % 8 or tm < 8:
            raise ValueError(f"tm must be a positive multiple of 8, got {tm}")
        self.z_resident = fits(tm, True)
        self.tm, self.g1p = tiling(tm)
        self.n_tiles = self.g1p // self.tm

    def passes_per_iter(self) -> float:
        """Modelled HBM array-passes per iteration (roofline report)."""
        # AB: p r, w r+w, pn w, ap w, a r (+8-row halo), b r;
        # C: dinv r, ap r; streamed z adds AB's z r and C's z r+w
        return (9.0 if self.z_resident else 12.0) + 8.0 / self.tm


def _sem_map():
    """Semaphore base index per named DMA stream (2 slots each; the
    pn-store follows the 3-slot ring)."""
    names = ["z", "p", "w", "wst", "a", "b", "ap", "pnst",
             "zc", "dv", "apc", "zst", "r0"]
    out, i = {}, 0
    for n in names:
        out[n] = i
        i += 3 if n == "pnst" else 2
    return out, i


_SEM, _NSEMS = _sem_map()


def _mega_kernel(problem: Problem, plan: XLPlan, weighted: bool, *,
                 # HBM inputs
                 dinv_hbm, a_hbm, b_hbm, r0_hbm,
                 # outputs (w is the result; p/ap are HBM scratch)
                 w_hbm, iters_out, diff_out, flags_out, p_hbm, ap_hbm,
                 # VMEM tile buffers + SMEM accumulators
                 p_buf, w_buf, wout_buf, ring, a_buf, b_buf, ap_buf,
                 dv_buf, apc_buf, acc, sems,
                 # z: one whole VMEM array (plan.z_resident), or an HBM
                 # scratch with its AB, C-read and C-write tile buffers
                 z_vmem=None, z_hbm=None, z_buf=None, zc_buf=None,
                 zcout_buf=None):
    dtype = r0_hbm.dtype
    z_resident = plan.z_resident
    tm, g2p, n_tiles = plan.tm, plan.g2p, plan.n_tiles
    h1h2 = jnp.asarray(float(problem.h1) * float(problem.h2), dtype)
    delta = jnp.asarray(problem.delta, dtype)
    max_iter = problem.max_iterations
    M, N = problem.M, problem.N

    _HBM = {"z": z_hbm, "p": p_hbm, "w": w_hbm, "dv": dinv_hbm,
            "a": a_hbm, "b": b_hbm, "zc": z_hbm, "apc": ap_hbm,
            "r0": r0_hbm}
    # r0 lands where the init sweep leaves a buffer idle
    _BUF = {"z": z_buf, "p": p_buf, "w": w_buf, "dv": dv_buf,
            "a": a_buf, "b": b_buf, "zc": zc_buf, "apc": apc_buf,
            "r0": apc_buf if z_resident else zc_buf}
    _ROWS = {"a": tm + 8}
    # the streams of each sweep; a resident z streams in neither
    ab_streams = ("p", "w") if z_resident else ("z", "p", "w")
    c_streams = ("dv", "apc") if z_resident else ("zc", "dv", "apc")

    def load(name, t, slot):
        rows = _ROWS.get(name, tm)
        return pltpu.make_async_copy(
            _HBM[name].at[pl.ds(t * tm, rows), :],
            _BUF[name].at[pl.ds(slot * rows, rows), :],
            sems.at[_SEM[name] + slot],
        )

    def store(name, buf, hbm, t, slot):
        return pltpu.make_async_copy(
            buf.at[pl.ds(slot * tm, tm), :],
            hbm.at[pl.ds(t * tm, tm), :],
            sems.at[_SEM[name] + slot],
        )

    def tile_of(buf, slot, rows=None):
        rows = tm if rows is None else rows
        out = buf[pl.ds(slot * rows, rows), :]
        # operand buffers may be typed at a narrow storage width
        # (``build_xl_solver(storage_dtype=…)``): upcast tile-locally so
        # the arithmetic stays at compute width while the DMA stream —
        # this engine's bottleneck — stays narrow
        return out.astype(dtype) if out.dtype != dtype else out

    # -- one-time init sweep: w = 0, p = 0, z = r0*Dinv, zr0 ---------------
    # serial (one-time cost); w_buf doubles as the zero source.
    w_buf[...] = jnp.zeros(w_buf.shape, dtype)
    acc[0] = jnp.zeros((), dtype)

    def init_tile(t, carry):
        for name in ("r0", "dv"):
            cp = load(name, t, 0)
            cp.start()
            cp.wait()
        rt = tile_of(_BUF["r0"], 0)
        zt = rt * tile_of(dv_buf, 0)
        stores = [("wst", w_buf, w_hbm), ("pnst", w_buf, p_hbm)]
        if z_resident:
            z_vmem[pl.ds(t * tm, tm), :] = zt
        else:
            zcout_buf[pl.ds(0, tm), :] = zt
            stores.insert(0, ("zst", zcout_buf, z_hbm))
        for name, buf, hbm in stores:
            cp = store(name, buf, hbm, t, 0)
            cp.start()
            cp.wait()
        acc[0] += jnp.sum(zt * rt)
        return carry

    lax.fori_loop(0, n_tiles, init_tile, 0)
    zr0 = acc[0] * h1h2

    # -- the stencil on ring tile s (value-level, reference FP form) -------
    def stencil_ring(s, aslot):
        rslot = lax.rem(s, 3)
        pc = tile_of(ring, rslot)
        # aligned 8-row reads + value concats for the single halo rows
        # (Mosaic wants dynamic VMEM offsets at sublane multiples); the
        # unselected branches of the jnp.where reads are ring garbage at
        # the grid edges, discarded by the select.
        prev = lax.rem(s + 2, 3)
        nxt = lax.rem(s + 1, 3)
        above = ring[pl.ds(prev * tm + tm - 8, 8), :]
        below = ring[pl.ds(nxt * tm, 8), :]
        zero_row = jnp.zeros((1, g2p), dtype)
        up_row = jnp.where(s >= 1, above[7:8, :], zero_row)
        dn_row = jnp.where(s + 1 < n_tiles, below[0:1, :], zero_row)
        pu = jnp.concatenate([up_row, pc[:-1]], axis=0)
        pd = jnp.concatenate([pc[1:], dn_row], axis=0)
        aw = tile_of(a_buf, aslot, tm + 8)[0 : tm + 1, :]
        anc = aw[0:tm, :]
        ans = aw[1 : tm + 1, :]
        bwc = tile_of(b_buf, aslot)
        bec = _shift_cols_left(bwc)
        pl_ = _shift_cols_right(pc)
        pr = _shift_cols_left(pc)
        ax = anc * (pc - pu) + ans * (pc - pd)
        ay = bwc * (pc - pl_) + bec * (pc - pr)
        gi = s * tm + lax.broadcasted_iota(jnp.int32, (tm, g2p), 0)
        gj = lax.broadcasted_iota(jnp.int32, (tm, g2p), 1)
        interior = (gi >= 1) & (gi <= M - 1) & (gj >= 1) & (gj <= N - 1)
        return jnp.where(interior, ax + ay, jnp.zeros_like(pc)), pc

    # -- the while loop ----------------------------------------------------
    carry0 = (
        jnp.asarray(0, jnp.int32),          # bodies executed
        zr0,
        jnp.asarray(0.0, dtype),            # alpha (deferred: prev body's)
        jnp.asarray(0.0, dtype),            # beta
        jnp.asarray(jnp.inf, dtype),        # diff
        jnp.asarray(False), jnp.asarray(False),
    )

    def cond(c):
        i, _zr, _a, _b, _d, conv, bd = c
        # one extra body confirms the previous iteration's convergence
        return (i < max_iter + 1) & ~conv & ~bd

    def body(c):
        i, zr, alpha, beta, diff, _cv, _bd = c

        # ---- AB sweep: step t updates tile t (w += alpha p, pn = z +
        # beta p) and stencils tile t-1 (ring holds pn tiles t-2..t).
        # State loads (p/w, and z when streamed) for tile t are
        # prefetched at step t-1 into slot t%2; a/b for stencil s are
        # prefetched at step s into slot s%2 and consumed at step s+1 —
        # in-use and in-flight slots stay disjoint for every stream.
        acc[0] = jnp.zeros((), dtype)   # dw2
        acc[1] = jnp.zeros((), dtype)   # denom partial
        for name in ab_streams:
            load(name, 0, 0).start()

        def ab_step(t, carry):
            slot2 = lax.rem(t, 2)
            rslot = lax.rem(t, 3)

            @pl.when(t + 1 < n_tiles)
            def _():
                nslot = lax.rem(t + 1, 2)
                for name in ab_streams:
                    load(name, t + 1, nslot).start()

            # ---- update phase for tile t
            @pl.when(t < n_tiles)
            def _():
                for name in ab_streams:
                    load(name, t, slot2).wait()
                # stencil operands for this tile, consumed next step
                load("a", t, slot2).start()
                load("b", t, slot2).start()
                # slots being rewritten must have drained their stores
                @pl.when(t >= 2)
                def _():
                    store("wst", wout_buf, w_hbm, t - 2, slot2).wait()

                @pl.when(t >= 3)
                def _():
                    store("pnst", ring, p_hbm, t - 3, rslot).wait()

                pt = tile_of(p_buf, slot2)
                wt = tile_of(w_buf, slot2)
                zt = (z_vmem[pl.ds(t * tm, tm), :] if z_resident
                      else tile_of(z_buf, slot2))
                w_new = wt + alpha * pt
                dw = w_new - wt
                wout_buf[pl.ds(slot2 * tm, tm), :] = w_new
                store("wst", wout_buf, w_hbm, t, slot2).start()
                pn = zt + beta * pt
                ring[pl.ds(rslot * tm, tm), :] = pn
                store("pnst", ring, p_hbm, t, rslot).start()
                acc[0] += jnp.sum(dw * dw)

            # ---- stencil phase for tile t-1
            @pl.when(t >= 1)
            def _():
                s = t - 1
                aslot = lax.rem(s, 2)
                load("a", s, aslot).wait()
                load("b", s, aslot).wait()

                @pl.when(s >= 2)
                def _():
                    store("ap", ap_buf, ap_hbm, s - 2, aslot).wait()

                apt, pc = stencil_ring(s, aslot)
                ap_buf[pl.ds(aslot * tm, tm), :] = apt
                store("ap", ap_buf, ap_hbm, s, aslot).start()
                # per-tile SMEM accumulation inside one pipelined Mosaic
                # kernel (the dw2 cell fills in the update phase, this
                # one a stencil-lag behind): already one kernel, no
                # collective to stack
                # tpulint: disable=TPU007
                acc[1] += jnp.sum(apt * pc)

            return carry

        lax.fori_loop(0, n_tiles + 1, ab_step, 0)
        # drain trailing stores (static tails: unrolls)
        for tt in range(max(n_tiles - 2, 0), n_tiles):
            store("wst", wout_buf, w_hbm, tt, tt % 2).wait()
            store("ap", ap_buf, ap_hbm, tt, tt % 2).wait()
        for tt in range(max(n_tiles - 3, 0), n_tiles):
            store("pnst", ring, p_hbm, tt, tt % 3).wait()
        dw2 = acc[0]
        denom = acc[1] * h1h2

        ndiff = jnp.sqrt(dw2 * h1h2) if weighted else jnp.sqrt(dw2)
        # convergence of the PREVIOUS reference iteration (body 0 has no
        # previous update: alpha = 0 makes its dw2 exactly 0)
        conv = (i >= 1) & (ndiff < delta)
        ndiff = jnp.where(i >= 1, ndiff, diff)
        # this body's denominator belongs to reference iteration i+1: a
        # guard trip only counts while that iteration is within the cap —
        # the confirming body past max_iter evaluates a denominator the
        # reference never computes, and must not flag it
        breakdown = ~conv & (denom < DENOM_GUARD) & (i < max_iter)
        guard = denom < DENOM_GUARD
        alpha_new = zr / jnp.where(guard, jnp.ones_like(denom), denom)
        alpha_new = jnp.where(guard, jnp.zeros_like(alpha_new), alpha_new)

        # ---- C sweep: z update + zr partial off one dinv stream
        acc[2] = jnp.zeros((), dtype)
        for name in c_streams:
            load(name, 0, 0).start()

        def c_step(t, carry):
            slot2 = lax.rem(t, 2)

            @pl.when(t + 1 < n_tiles)
            def _():
                nslot = lax.rem(t + 1, 2)
                for name in c_streams:
                    load(name, t + 1, nslot).start()

            for name in c_streams:
                load(name, t, slot2).wait()

            if not z_resident:
                @pl.when(t >= 2)
                def _():
                    store("zst", zcout_buf, z_hbm, t - 2, slot2).wait()

            dvt = tile_of(dv_buf, slot2)
            zt = (z_vmem[pl.ds(t * tm, tm), :] if z_resident
                  else tile_of(zc_buf, slot2))
            z_new = zt - alpha_new * (dvt * tile_of(apc_buf, slot2))
            if z_resident:
                # in place: no other step of the sweep touches tile t
                z_vmem[pl.ds(t * tm, tm), :] = z_new
            else:
                zcout_buf[pl.ds(slot2 * tm, tm), :] = z_new
                store("zst", zcout_buf, z_hbm, t, slot2).start()
            # guarded reciprocal: d = 1/Dinv on the interior, 0 off it
            dt = jnp.where(
                dvt != 0.0,
                1.0 / jnp.where(dvt != 0.0, dvt, jnp.ones_like(dvt)),
                jnp.zeros_like(dvt),
            )
            # per-tile SMEM accumulation in the C sweep of the same
            # kernel — the AB-sweep cells are sequenced by the pipeline,
            # not by a fusable reduction pair
            # tpulint: disable=TPU007
            acc[2] += jnp.sum((z_new * z_new) * dt)
            return carry

        lax.fori_loop(0, n_tiles, c_step, 0)
        if not z_resident:
            for tt in range(max(n_tiles - 2, 0), n_tiles):
                store("zst", zcout_buf, z_hbm, tt, tt % 2).wait()
        zr_new = acc[2] * h1h2

        zr_out = jnp.where(breakdown, zr, zr_new)
        beta_new = jnp.where(breakdown, beta, zr_new / zr)
        return (i + 1, zr_out, alpha_new, beta_new, ndiff, conv, breakdown)

    out = lax.while_loop(cond, body, carry0)
    bodies, conv, bd = out[0], out[5], out[6]
    # body i applies reference-iteration i's deferred w-update and checks
    # its convergence; its denominator belongs to reference-iteration
    # i+1. Converged exit therefore reports bodies-1; breakdown and the
    # max_iter cap report the body count (capped).
    iters_out[0] = jnp.where(
        conv, bodies - 1, jnp.minimum(bodies, max_iter)
    )
    diff_out[0] = out[4]
    flags_out[0] = conv.astype(jnp.int32)
    flags_out[1] = bd.astype(jnp.int32)


def build_xl_solver(problem: Problem, dtype=jnp.float32, interpret=None,
                    tm: int | None = None, geometry=None, theta=None,
                    storage_dtype=None):
    """(jitted whole-solve kernel, args) for state-beyond-VMEM grids.

    args = (dinv, a, b, r0): f64-assembled, rounded once — the shared
    operand fidelity contract (``fused_pcg.build_fused_solver``). The
    jitted solver carries the ``XLPlan`` it was built with as ``.plan``.

    ``storage_dtype`` (``ops.precision``) streams the coefficient
    operands (dinv, a, b) at that width, upcast per tile inside the
    kernel (``tile_of``); the state scratch (and a resident z) stays at
    compute width — the operand share of this engine's passes narrows,
    the state share keeps full precision (the conservative rung; the full
    state-narrow form is the sharded/sstep engines' territory).
    """
    from poisson_ellipse_tpu.ops.precision import resolve_storage_dtype

    if jnp.dtype(dtype).itemsize >= 8:
        raise ValueError("xl solver supports f32/bf16; use engine='xla'")
    st = resolve_storage_dtype(storage_dtype, dtype)
    if interpret is None:
        interpret = _interpret_default()
    g1, g2 = problem.node_shape
    plan = XLPlan(problem, dtype, tm=tm)
    g1p, g2p, tm = plan.g1p, plan.g2p, plan.tm
    args = streamed_operand_set(problem, dtype, g1p, g2p,
                                geometry=geometry, theta=theta)
    if st is not None:
        dinv0, a0, b0, r00 = args
        args = (
            jnp.asarray(dinv0).astype(st), jnp.asarray(a0).astype(st),
            jnp.asarray(b0).astype(st), r00,
        )

    anyspec = lambda: pl.BlockSpec(memory_space=pl.ANY)
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = lambda slots, rows=None, narrow=False: pltpu.VMEM(
        (slots * (rows if rows else tm), g2p),
        st if (narrow and st is not None) else dtype,
    )
    full = jax.ShapeDtypeStruct((g1p, g2p), dtype)
    # (name, spec, shape) per output; w is the result, the rest scratch
    outputs = [
        ("w_hbm", anyspec(), full),
        ("iters_out", smem(), jax.ShapeDtypeStruct((1,), jnp.int32)),
        ("diff_out", smem(), jax.ShapeDtypeStruct((1,), dtype)),
        ("flags_out", smem(), jax.ShapeDtypeStruct((2,), jnp.int32)),
        ("p_hbm", anyspec(), full),
        ("ap_hbm", anyspec(), full),
    ]
    scratch = [
        ("p_buf", tile(2)),
        ("w_buf", tile(2)),
        ("wout_buf", tile(2)),
        ("ring", tile(3)),                      # pn
        ("a_buf", tile(2, tm + 8, narrow=True)),
        ("b_buf", tile(2, narrow=True)),
        ("ap_buf", tile(2)),
        ("dv_buf", tile(2, narrow=True)),
        ("apc_buf", tile(2)),
    ]
    if plan.z_resident:
        scratch.append(("z_vmem", pltpu.VMEM((g1p, g2p), dtype)))
    else:
        outputs.append(("z_hbm", anyspec(), full))
        scratch += [("z_buf", tile(2)), ("zc_buf", tile(2)),
                    ("zcout_buf", tile(2))]
    scratch += [("acc", pltpu.SMEM((3,), dtype)),
                ("sems", pltpu.SemaphoreType.DMA((_NSEMS,)))]
    ref_names = (("dinv_hbm", "a_hbm", "b_hbm", "r0_hbm")
                 + tuple(n for n, _, _ in outputs)
                 + tuple(n for n, _ in scratch))

    def kernel(*refs):
        _mega_kernel(problem, plan, problem.norm == "weighted",
                     **dict(zip(ref_names, refs)))

    call = pl.pallas_call(
        kernel,
        in_specs=[anyspec()] * 4,
        out_specs=tuple(spec for _, spec, _ in outputs),
        out_shape=tuple(shape for _, _, shape in outputs),
        scratch_shapes=[shape for _, shape in scratch],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=scaled_vmem_budget(_VMEM_LIMIT)
        ),
        interpret=interpret,
        name="xl_pcg",
    )

    # the jitted function's name is the custom call's name in the device
    # trace: one stable name per engine
    def xl_pcg(dinv, a, b, r0):
        w_pad, iters, diff, flags = call(dinv, a, b, r0)[:4]
        return PCGResult(
            w=w_pad[:g1, :g2],
            iters=iters[0],
            diff=diff[0],
            converged=flags[0].astype(bool),
            breakdown=flags[1].astype(bool),
        )

    # no donation: build-once-call-many — callers re-feed these operands
    # every dispatch (bench --repeat protocol)
    # tpulint: disable=TPU004
    solver = jax.jit(xl_pcg)
    solver.plan = plan
    return solver, args


def solve_xl(problem: Problem, dtype=jnp.float32, interpret=None) -> PCGResult:
    solver, args = build_xl_solver(problem, dtype, interpret=interpret)
    return solver(*args)
