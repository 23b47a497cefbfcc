"""XL engine: whole-solve kernel for grids whose STATE exceeds VMEM.

``ops.streamed_pcg`` pins the PCG state in VMEM and streams operands;
past ~2400x3200 (f32) the state itself no longer fits and the framework
previously fell back to the XLA while_loop (~13 modelled HBM passes per
iteration, measured ~67% of HBM peak at 4096² — the north-star grid).
This kernel streams EVERYTHING — state and operands — through
double-buffered tile DMA, and restructures the iteration so the traffic
floor is lower than XLA's:

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

= ~12.08 HBM array-passes/iter vs the XLA loop's ~13, at a higher
achieved fraction of peak. Measured (bench chip, f32): 4096² = 4.22 s
vs 5.16 s XLA (1.22×, 3226 iterations exact, 75.5% of HBM peak);
8192² = 28.7 s / 5889 iterations at 81.3% of peak on ONE chip — a grid
the reference reaches only on a multi-node MPI cluster. All per-element
FP forms are shared with the streamed z-state regime (verified there to
preserve the published iteration-count oracles); reductions are
tile-sequential as in every Pallas engine.

Reference lineage: this is the stage4 decomposition taken to its
single-chip limit — where ``poisson_mpi_cuda2.cu:846-939`` launches six
kernels and ships scalars through the host each iteration, here the
whole solve is ONE kernel launch and the scalars never leave SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops.streamed_pcg import (
    _VMEM_LIMIT,
    _interpret_default,
    _round_up,
    _shift_cols_left,
    _shift_cols_right,
    streamed_operand_set,
)
from poisson_ellipse_tpu.solver.pcg import DENOM_GUARD, PCGResult
from poisson_ellipse_tpu.utils.device import scaled_vmem_budget

# Candidate row-tile heights for the default policy. Measured at 4096²
# the timings are flat across 64/96/128 (4.28-4.30 s) while 256 is ~3%
# slower and 384 overflows VMEM (the kernel holds ~25 tile slots), so
# the policy just minimises padded rows — at 4097 rows that picks 96
# (g1p = 4128 vs 4224 with 128), 2.3% less streamed work for free.
_TM_CANDIDATES = (64, 96, 128, 256)


class XLPlan:
    """Tiling of the XL solve (no residency choices: everything streams).

    ``dtype`` is accepted for interface parity with ``StreamPlan`` but
    does not influence the tiling: with no residency budget to fill,
    the tile choice is itemsize-independent (the ~16 tile buffers sit
    far below VMEM at every candidate size)."""

    def __init__(self, problem: Problem, dtype, tm: int | None = None):
        g1, g2 = problem.node_shape
        if tm is None:
            # least padded rows; larger tile breaks ties (fewer steps)
            tm = min(_TM_CANDIDATES, key=lambda t: (_round_up(g1, t), -t))
        if tm % 8 or tm < 8:
            raise ValueError(f"tm must be a positive multiple of 8, got {tm}")
        self.g2p = _round_up(g2, 128)
        self.tm = tm if g1 >= tm else _round_up(g1, 8)
        self.g1p = _round_up(g1, self.tm)
        self.n_tiles = self.g1p // self.tm

    def passes_per_iter(self) -> float:
        """Modelled HBM array-passes per iteration (roofline report)."""
        # AB: z r, p r, w r+w, pn w, ap w, a r (+8-row halo), b r;
        # C: z r+w, dinv r, ap r
        return 12.0 + 8.0 / self.tm


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


def _mega_kernel(problem: Problem, plan: XLPlan, weighted: bool,
                 # HBM inputs
                 dinv_hbm, a_hbm, b_hbm, r0_hbm,
                 # outputs (w is the result; z/p/ap are HBM scratch)
                 w_hbm, iters_out, diff_out, flags_out,
                 z_hbm, p_hbm, ap_hbm,
                 # VMEM tile buffers + SMEM accumulators
                 z_buf, p_buf, w_buf, wout_buf, ring, a_buf, b_buf,
                 ap_buf, zc_buf, zcout_buf, dv_buf, apc_buf, acc, sems):
    dtype = r0_hbm.dtype
    tm, g2p, n_tiles = plan.tm, plan.g2p, plan.n_tiles
    h1h2 = jnp.asarray(float(problem.h1) * float(problem.h2), dtype)
    delta = jnp.asarray(problem.delta, dtype)
    max_iter = problem.max_iterations
    M, N = problem.M, problem.N

    _HBM = {"z": z_hbm, "p": p_hbm, "w": w_hbm, "dv": dinv_hbm,
            "a": a_hbm, "b": b_hbm, "zc": z_hbm, "apc": ap_hbm,
            "r0": r0_hbm}
    _BUF = {"z": z_buf, "p": p_buf, "w": w_buf, "dv": dv_buf,
            "a": a_buf, "b": b_buf, "zc": zc_buf, "apc": apc_buf,
            "r0": zc_buf}
    _ROWS = {"a": tm + 8}

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
        rt = tile_of(zc_buf, 0)
        zt = rt * tile_of(dv_buf, 0)
        zcout_buf[pl.ds(0, tm), :] = zt
        for name, buf, hbm in (("zst", zcout_buf, z_hbm),
                               ("wst", w_buf, w_hbm),
                               ("pnst", w_buf, p_hbm)):
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
        # State loads (z/p/w) for tile t are prefetched at step t-1 into
        # slot t%2; a/b for stencil s are prefetched at step s into slot
        # s%2 and consumed at step s+1 — in-use and in-flight slots stay
        # disjoint for every stream.
        acc[0] = jnp.zeros((), dtype)   # dw2
        acc[1] = jnp.zeros((), dtype)   # denom partial
        for name in ("z", "p", "w"):
            load(name, 0, 0).start()

        def ab_step(t, carry):
            slot2 = lax.rem(t, 2)
            rslot = lax.rem(t, 3)

            @pl.when(t + 1 < n_tiles)
            def _():
                nslot = lax.rem(t + 1, 2)
                for name in ("z", "p", "w"):
                    load(name, t + 1, nslot).start()

            # ---- update phase for tile t
            @pl.when(t < n_tiles)
            def _():
                for name in ("z", "p", "w"):
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
                zt = tile_of(z_buf, slot2)
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
        for name in ("zc", "dv", "apc"):
            load(name, 0, 0).start()

        def c_step(t, carry):
            slot2 = lax.rem(t, 2)

            @pl.when(t + 1 < n_tiles)
            def _():
                nslot = lax.rem(t + 1, 2)
                for name in ("zc", "dv", "apc"):
                    load(name, t + 1, nslot).start()

            for name in ("zc", "dv", "apc"):
                load(name, t, slot2).wait()

            @pl.when(t >= 2)
            def _():
                store("zst", zcout_buf, z_hbm, t - 2, slot2).wait()

            dvt = tile_of(dv_buf, slot2)
            z_new = tile_of(zc_buf, slot2) - alpha_new * (
                dvt * tile_of(apc_buf, slot2)
            )
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
                    tm: int | None = None, _debug_raw: bool = False,
                    geometry=None, theta=None, storage_dtype=None):
    """(jitted whole-solve kernel, args) for state-beyond-VMEM grids.

    args = (dinv, a, b, r0): f64-assembled, rounded once — the shared
    operand fidelity contract (``fused_pcg.build_fused_solver``).
    _debug_raw returns the raw pallas outputs (w, iters, diff, flags,
    z, p, ap) — the HBM state scratch is inspectable for tests/debug.

    ``storage_dtype`` (``ops.precision``) streams the coefficient
    operands (dinv, a, b) at that width, upcast per tile inside the
    kernel (``tile_of``); the HBM state scratch stays at compute width —
    the operand share of this engine's ~12 passes/iter narrows, the
    state share keeps full precision (the conservative rung; the full
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

    kernel = functools.partial(
        _mega_kernel, problem, plan, problem.norm == "weighted"
    )
    anyspec = lambda: pl.BlockSpec(memory_space=pl.ANY)
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = lambda slots, rows=None, narrow=False: pltpu.VMEM(
        (slots * (rows if rows else tm), g2p),
        st if (narrow and st is not None) else dtype,
    )
    call = pl.pallas_call(
        kernel,
        in_specs=[anyspec()] * 4,
        out_specs=(anyspec(), smem(), smem(), smem(),
                   anyspec(), anyspec(), anyspec()),
        out_shape=(
            jax.ShapeDtypeStruct((g1p, g2p), dtype),       # w (result)
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), dtype),
            jax.ShapeDtypeStruct((2,), jnp.int32),
            jax.ShapeDtypeStruct((g1p, g2p), dtype),       # z scratch
            jax.ShapeDtypeStruct((g1p, g2p), dtype),       # p scratch
            jax.ShapeDtypeStruct((g1p, g2p), dtype),       # ap scratch
        ),
        scratch_shapes=[
            tile(2),            # z_buf
            tile(2),            # p_buf
            tile(2),            # w_buf
            tile(2),            # wout_buf
            tile(3),            # ring (pn)
            tile(2, tm + 8, narrow=True),    # a_buf
            tile(2, narrow=True),            # b_buf
            tile(2),            # ap_buf
            tile(2),            # zc_buf
            tile(2),            # zcout_buf
            tile(2, narrow=True),            # dv_buf
            tile(2),            # apc_buf
            pltpu.SMEM((3,), dtype),
            pltpu.SemaphoreType.DMA((_NSEMS,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=scaled_vmem_budget(_VMEM_LIMIT)
        ),
        interpret=interpret,
    )

    if _debug_raw:
        return jax.jit(call), args

    def solver(dinv, a, b, r0):
        w_pad, iters, diff, flags, _z, _p, _ap = call(dinv, a, b, r0)
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
    return jax.jit(solver), args


def solve_xl(problem: Problem, dtype=jnp.float32, interpret=None) -> PCGResult:
    solver, args = build_xl_solver(problem, dtype, interpret=interpret)
    return solver(*args)
