"""Tile-wise whole-solve kernel for grids beyond full VMEM residency.

``ops.resident_pcg`` holds every operand and iterate in VMEM, but its
whole-array expressions make Mosaic materialise full-size temporaries,
capping it at ~1000x1500. This kernel removes that cap two ways:

- **tile-wise compute**: every sweep walks row tiles, so temporaries are
  tile-sized and the only full-size VMEM consumers are the arrays we
  *choose* to keep resident;
- **per-operand residency**: the PCG state (w, r, p) always stays in
  VMEM scratch across the whole ``lax.while_loop`` (the entire point —
  state never touches HBM); each loop-invariant operand (Dinv, a, b) and
  the ap intermediate is either VMEM-resident too (loaded once) or
  streamed per tile from HBM into a 2-slot buffer, software-pipelined
  (the DMA for tile t+1 overlaps tile t's compute; ap stores lag two
  tiles), chosen greedily to fill the measured ~127 MB of VMEM.

Measured residency on the bench chip (``StreamPlan(...).resident``):
1600x2400 is **all-resident** — zero HBM bytes per iteration — while at
2400x3200 the state alone takes ~97 MB of the ~114 MB budget, so **all
four operands stream** (~5.1 array-passes/iter vs the ~13 the XLA
while_loop streams once the working set outgrows VMEM) behind the
double-buffered pipeline.

Per iteration, two tile sweeps inside one kernel (the two scalar sync
points of PCG — alpha needs the global denom, beta the global zr — set
the sweep-count floor):

  AB  p <- z + beta*p on tile t+1, then          (rotated p-update fused
      ap = A(p) on tile t; denom partial          with stencil + dot on a
                                                  one-tile lag)
  C   alpha; w += alpha*p; z/r update;
      ||dw||^2 and (z, r) partials               (fused updates)

In the dinv-resident regimes the state array holds r and z is formed on
the fly (z = r·Dinv, twice per iteration, both free — dinv is VMEM-
resident). In the all-streamed regime the state instead carries z
itself, which moves the single dinv stream entirely into pass C (the
z-update and the z²·(1/Dinv) inner product share it) and makes the AB
p-update operand-free — one dinv HBM pass per iteration instead of two,
with the published iteration counts preserved (see the z-state branch
in ``_mega_kernel``).

The stencil is the reference's algebraic form
(``stage0/Withoutopenmp1.cpp:75-88``) with the 1/h² factors hoisted into
the one-time f64 operand build (unmasked an = a/h1², bw = b/h2²; see
``stencil_tile``) — zero VPU divides per iteration, with the published
iteration-count oracles preserved in f32 (asserted by the bench on every
run). The preconditioner is a multiply by the precomputed guarded 1/D
(f64-rounded), as in ``ops.fused_pcg``.

p's scratch carries 8-row zero bands above and below the grid so the
stencil's row-neighbour reads are always in bounds; ring/padding output
rows are masked in-kernel (assembled coefficients are nonzero *adjacent*
to the ring, so masking inputs alone cannot zero the ring output —
same reason the reference's kernels guard on indices,
``poisson_mpi_cuda2.cu:512-516``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.ops import assembly
from poisson_ellipse_tpu.solver.pcg import DENOM_GUARD, PCGResult
from poisson_ellipse_tpu.utils.device import scaled_vmem_budget

# measured on the 128 MiB bench part; scaled to the actual device's
# capacity at the use sites (utils.device, device_kind-keyed)
_VMEM_LIMIT = 127 * 1024 * 1024
_VMEM_USABLE = 114 * 1024 * 1024  # leave headroom for Mosaic temps
_BAND = 8  # zero band rows above/below the p scratch


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class StreamPlan:
    """Which operands stay VMEM-resident, plus the tiling.

    tm — row-tile height override (multiple of 8). Default (None) picks
    128 when that plan streams no more HBM traffic per iteration than the
    64-row plan, else 64: larger tiles cut per-tile loop/DMA bookkeeping
    (measured ~12% per iteration at 1600x2400 all-resident) but eat VMEM
    that the greedy residency pass and Mosaic temporaries want; 256 was
    measured slower (it demotes an operand to streamed).

    device — whose VMEM capacity bounds the plan (default: the
    default-backend device); the measured 128 MiB-part budget is scaled
    to it via ``utils.device.scaled_vmem_budget``.
    """

    def __init__(self, problem: Problem, dtype, tm: int | None = None,
                 device=None):
        self.device = device
        if tm is None:
            self._compute(problem, dtype, 64)
            fits64 = self.fits
            passes64 = self.streamed_passes_per_iter()
            state64 = dict(self.__dict__)
            self._compute(problem, dtype, 128)
            # keep 128 only when it streams no more HBM traffic than 64 —
            # comparing resident *counts* could trade a cheap-to-stream
            # operand for an expensive one behind an equal count
            if not (
                self.fits
                and (
                    not fits64
                    or self.streamed_passes_per_iter() <= passes64
                )
            ):
                self.__dict__.update(state64)
        else:
            if tm % 8 or tm < 8:
                raise ValueError(
                    f"tm must be a positive multiple of 8, got {tm}"
                )
            self._compute(problem, dtype, tm)

    def _compute(self, problem: Problem, dtype, tm: int) -> None:
        g1, g2 = problem.node_shape
        self.g2p = _round_up(g2, 128)
        self.tm = tm if g1 >= tm else _round_up(g1, 8)
        self.g1p = _round_up(g1, self.tm)
        self.n_tiles = self.g1p // self.tm
        item = jnp.dtype(dtype).itemsize
        row = self.g2p * item
        budget = scaled_vmem_budget(_VMEM_USABLE, self.device)
        # state is always resident: w, r + p with its zero bands
        budget -= (3 * self.g1p + 2 * _BAND) * row
        # per-operand buffer rows: streamed operands get a double-buffered
        # 2-slot tile buffer (the single source of the scratch_shapes row
        # counts), resident ones hold the full padded array ("a" carries
        # an 8-row halo in both forms)
        self.tile_rows = {"dinv": 2 * self.tm, "ap": 2 * self.tm,
                          "a": 2 * (self.tm + 8), "b": 2 * self.tm}
        self.full_rows = {"dinv": self.g1p, "ap": self.g1p,
                          "a": self.g1p + 8, "b": self.g1p}
        tile_rows, full_rows = self.tile_rows, self.full_rows
        # the gate: state + the minimum (all-streamed) buffer set must fit
        self.min_stream_bytes = sum(tile_rows.values()) * row
        self.fits = budget >= self.min_stream_bytes
        # greedy residency, highest streamed-passes-saved first (ap is
        # written+read each iteration = 2 passes; dinv costs only 1 —
        # the z-state regime reads it once, in pass C); upgrading an
        # operand to resident swaps its tile buffer for the full array
        budget -= self.min_stream_bytes
        self.resident = {}
        for name in ("ap", "dinv", "a", "b"):
            extra = (full_rows[name] - tile_rows[name]) * row
            take = self.fits and extra <= budget
            self.resident[name] = take
            if take:
                budget -= extra

    def streamed_passes_per_iter(self) -> float:
        """HBM array-passes per iteration (for the roofline report)."""
        p = 0.0
        if not self.resident["dinv"]:
            # read once, in pass C only: the all-streamed regime carries
            # z (= Dinv·r) as the resident state, so the AB sweep's
            # p-update needs no operand at all (``_mega_kernel``)
            p += 1.0
        if not self.resident["ap"]:
            p += 2.0
        if not self.resident["a"]:
            p += 1.0 + 8.0 / self.tm
        if not self.resident["b"]:
            p += 1.0
        return p


def fits_streamed(problem: Problem, dtype=jnp.float32, device=None) -> bool:
    """True if the always-resident PCG state (w, r, banded p) plus the
    minimum double-buffered stream buffers fit the VMEM budget (scaled
    to ``device``'s capacity).

    The state itself cannot be streamed by THIS kernel (it is read and
    written every pass of every iteration), so grids past this gate —
    e.g. the 4097² node grid, whose state alone is ~201 MB — take the
    xl engine (``ops.xl_pcg``, which streams state too) or the sharded
    path.
    """
    return StreamPlan(problem, dtype, device=device).fits


def _shift_cols_right(x):
    zero = jnp.zeros((x.shape[0], 1), x.dtype)
    return jnp.concatenate([zero, x[:, :-1]], axis=1)


def _shift_cols_left(x):
    zero = jnp.zeros((x.shape[0], 1), x.dtype)
    return jnp.concatenate([x[:, 1:], zero], axis=1)


_NSLOT = 2  # double buffering: prefetch tile t+1 while computing tile t


def _mega_kernel(problem: Problem, plan: StreamPlan, weighted: bool,
                 # HBM / maybe-VMEM inputs
                 dinv_hbm, a_hbm, b_hbm, r0_hbm,
                 # outputs
                 w_out, iters_out, diff_out, flags_out, ap_hbm,
                 # scratch
                 w_s, r_s, p_s, dinv_buf, a_buf, b_buf, ap_buf, sems):
    dtype = r0_hbm.dtype
    tm, g2p, n_tiles = plan.tm, plan.g2p, plan.n_tiles
    h1 = float(problem.h1)
    h2 = float(problem.h2)
    h1h2 = jnp.asarray(h1 * h2, dtype)
    delta = jnp.asarray(problem.delta, dtype)
    max_iter = problem.max_iterations
    M, N = problem.M, problem.N
    res = plan.resident

    # -- streamed-operand machinery ---------------------------------------
    # Each streamed operand owns a 2-slot buffer and 2 semaphores; loads
    # are software-pipelined (start t+1, wait t, compute t) so the DMA for
    # the next tile overlaps the current tile's compute. Resident operands
    # hold the full array and read directly.
    _SEM = {"dinv": 0, "a": 2, "b": 4, "ap": 6}
    # rows per buffer slot
    _ALLOC = {k: v // _NSLOT for k, v in plan.tile_rows.items()}
    _BUF = {"dinv": dinv_buf, "a": a_buf, "b": b_buf, "ap": ap_buf}
    _HBM = {"dinv": dinv_hbm, "a": a_hbm, "b": b_hbm, "ap": ap_hbm}

    def _load_copy(name, t, slot):
        rows = _ALLOC[name]
        return pltpu.make_async_copy(
            _HBM[name].at[pl.ds(t * tm, rows), :],
            _BUF[name].at[pl.ds(slot * rows, rows), :],
            sems.at[_SEM[name] + slot],
        )

    def _loader(name):
        """(start, wait) pair for the pipelined loop; None if resident."""
        if res[name]:
            return None
        return (
            lambda t, slot: _load_copy(name, t, slot).start(),
            lambda t, slot: _load_copy(name, t, slot).wait(),
        )

    def _read(name, t, slot, rows):
        """Tile rows of a (possibly resident) operand after its wait.

        The single operand-consumption chokepoint — which is where the
        storage axis lands: operand buffers typed at storage width
        (``build_streamed_solver(storage_dtype=…)``) are upcast
        tile-locally here, so the DMA stream (HBM bytes) stays narrow
        and the VPU arithmetic stays at compute width.
        """
        if res[name]:
            out = _BUF[name][pl.ds(t * tm, rows), :]
        else:
            out = _BUF[name][pl.ds(slot * _ALLOC[name], rows), :]
        return out.astype(dtype) if out.dtype != dtype else out

    def _pipelined(loaders, compute, carry0):
        """fori_loop over tiles with all streamed loads double-buffered."""
        loaders = [ld for ld in loaders if ld is not None]
        for start, _ in loaders:
            start(0, 0)

        def body(t, carry):
            slot = lax.rem(t, _NSLOT)

            @pl.when(t + 1 < n_tiles)
            def _():
                nxt = lax.rem(t + 1, _NSLOT)
                for start, _ in loaders:
                    start(t + 1, nxt)

            for _, wait in loaders:
                wait(t, slot)
            return compute(t, slot, carry)

        return lax.fori_loop(0, n_tiles, body, carry0)

    def _ap_store_copy(t, slot):
        return pltpu.make_async_copy(
            ap_buf.at[pl.ds(slot * tm, tm), :],
            ap_hbm.at[pl.ds(t * tm, tm), :],
            sems.at[_SEM["ap"] + slot],
        )

    # -- one-time initialisation ------------------------------------------
    for name in ("dinv", "a", "b"):
        if res[name]:
            cp = pltpu.make_async_copy(
                _HBM[name], _BUF[name], sems.at[_SEM[name]]
            )
            cp.start()
            cp.wait()

    w_s[...] = jnp.zeros(w_s.shape, dtype)
    p_s[...] = jnp.zeros(p_s.shape, dtype)
    cp = pltpu.make_async_copy(r0_hbm, r_s, sems.at[0])
    cp.start()
    cp.wait()

    def _zr0_tile(t, slot, acc):
        rt = r_s[pl.ds(t * tm, tm), :]
        zt = rt * _read("dinv", t, slot, tm)
        if not res["dinv"]:
            # the all-streamed regime carries z = Dinv·r as its resident
            # state (see the body's z-state branch): convert r0 in place
            r_s[pl.ds(t * tm, tm), :] = zt
        return acc + jnp.sum(zt * rt)

    zr0 = _pipelined(
        [_loader("dinv")], _zr0_tile, jnp.zeros((), dtype)
    ) * h1h2

    # -- the stencil for one tile -----------------------------------------
    def stencil_tile(t, slot):
        """A(p) on tile t in the normalised-difference form, ring/padding
        masked.

        The operands are the *unmasked* h²-normalised coefficients
        (an = a/h1², bw = b/h2²; see build_streamed_solver), so the
        reference's algebraic form (``stage0/Withoutopenmp1.cpp:75-88``)

          ap = an·(pc−pu) + as·(pc−pd) + bw·(pc−pl) + be·(pc−pr)

        costs zero VPU divides per iteration (the divides are hoisted
        into the one-time f64 operand build, same trick as the resident/
        fused engines) and the south/east coefficients come from offset
        slices of the same streamed rows. Unmasked operands are what make
        that slicing valid; interior values are unchanged, and the output
        mask below zeroes the ring/padding exactly as before.

        Row neighbours come from aligned 8-row block loads + value-level
        concats: Mosaic requires dynamic VMEM loads at sublane multiples,
        so a tile shifted by one row is not directly loadable.
        """
        pc = p_s[pl.ds(_BAND + t * tm, tm), :]
        p_above = p_s[pl.ds(_BAND + t * tm - 8, 8), :]
        p_below = p_s[pl.ds(_BAND + (t + 1) * tm, 8), :]
        pu = jnp.concatenate([p_above[7:8, :], pc[:-1]], axis=0)
        pd = jnp.concatenate([pc[1:], p_below[0:1, :]], axis=0)
        aw = _read("a", t, slot, tm + 1)
        anc = aw[0:tm, :]          # an rows of the tile (north)
        ans = aw[1 : tm + 1, :]    # an rows shifted one down = as (south)
        bwc = _read("b", t, slot, tm)
        bec = _shift_cols_left(bwc)
        pl_ = _shift_cols_right(pc)
        pr = _shift_cols_left(pc)
        ax = anc * (pc - pu) + ans * (pc - pd)
        ay = bwc * (pc - pl_) + bec * (pc - pr)
        gi = t * tm + lax.broadcasted_iota(jnp.int32, (tm, g2p), 0)
        gj = lax.broadcasted_iota(jnp.int32, (tm, g2p), 1)
        interior = (gi >= 1) & (gi <= M - 1) & (gj >= 1) & (gj <= N - 1)
        apt = jnp.where(interior, ax + ay, jnp.zeros_like(pc))
        return apt, pc

    # -- the while loop ----------------------------------------------------
    carry0 = (
        jnp.asarray(0, jnp.int32), zr0,
        jnp.asarray(0.0, dtype),            # beta
        jnp.asarray(jnp.inf, dtype),        # diff
        jnp.asarray(False), jnp.asarray(False),
    )

    def cond(c):
        k, _zr, _b, _d, conv, bd = c
        return (k < max_iter) & ~conv & ~bd

    def body(c):
        k, zr, beta, diff, _cv, _bd = c

        def p_update(t, dv=None):
            # p <- z + beta*p on tile t; in the r-state regime z is formed
            # on the fly as r·Dinv (dv = that tile's dinv rows), in the
            # z-state regime the state array already holds z (dv=None)
            rows = pl.ds(_BAND + t * tm, tm)
            zt = r_s[pl.ds(t * tm, tm), :]
            if dv is not None:
                zt = zt * dv
            p_s[rows, :] = zt + beta * p_s[rows, :]

        def store_ap(t, slot, apt):
            # Streamed ap stores lag two tiles behind (same slot), so a
            # slot is only rewritten after its previous store has drained.
            if res["ap"]:
                ap_buf[pl.ds(t * tm, tm), :] = apt
            else:
                @pl.when(t >= _NSLOT)
                def _():
                    _ap_store_copy(t - _NSLOT, slot).wait()

                ap_buf[pl.ds(slot * tm, tm), :] = apt
                _ap_store_copy(t, slot).start()

        def drain_ap_stores():
            if not res["ap"]:
                # trailing stores (n_tiles is static: unrolls)
                for t_tail in range(max(n_tiles - _NSLOT, 0), n_tiles):
                    _ap_store_copy(t_tail, t_tail % _NSLOT).wait()

        # Fused passes A+B in ONE sweep on a one-tile lag: step t updates
        # p on tile t+1 then applies the stencil to tile t, whose
        # row-neighbour reads touch only tiles t-1..t+1 — all already
        # updated. The per-tile arithmetic and accumulation order are
        # identical to separate A-then-B sweeps (bitwise-same results);
        # what changes is one fewer walk of the VMEM-resident state and
        # one fewer DMA pipeline drain per iteration.
        #
        # The state-array regime decides what the p-update reads: with
        # dinv resident the state is r and z is formed on the fly
        # (dv_at(t)); in the streamed-dinv z-state regime the state
        # already holds z (dv_at is None) — see pass C below.
        dv_at = (
            (lambda t: _BUF["dinv"][pl.ds(t * tm, tm), :])
            if res["dinv"]
            else (lambda t: None)
        )
        p_update(0, dv_at(0))

        def pass_ab(t, slot, acc):
            @pl.when(t + 1 < n_tiles)
            def _():
                p_update(t + 1, dv_at(t + 1))

            apt, pc = stencil_tile(t, slot)
            store_ap(t, slot, apt)
            return acc + jnp.sum(apt * pc)

        denom = _pipelined(
            [_loader("a"), _loader("b")],
            pass_ab, jnp.zeros((), dtype),
        ) * h1h2
        drain_ap_stores()

        breakdown = denom < DENOM_GUARD
        alpha = zr / jnp.where(breakdown, jnp.ones_like(denom), denom)
        alpha = jnp.where(breakdown, jnp.zeros_like(alpha), alpha)

        if res["dinv"]:
            # -- r-state pass C: fused updates + both reductions (dinv
            # reads are free — it is VMEM-resident)
            def pass_c(t, slot, acc):
                dw2a, zra = acc
                rows = pl.ds(t * tm, tm)
                w = w_s[rows, :]
                w_new = w + alpha * p_s[pl.ds(_BAND + t * tm, tm), :]
                dw = w_new - w
                w_s[rows, :] = w_new
                r_new = r_s[rows, :] - alpha * _read("ap", t, slot, tm)
                r_s[rows, :] = r_new
                return (
                    dw2a + jnp.sum(dw * dw),
                    zra + jnp.sum((r_new * dv_at(t)) * r_new),
                )

            c_loaders = [_loader("ap")]
        else:
            # -- streamed-dinv z-state pass C. The resident state array
            # carries z = Dinv·r instead of r (converted at init —
            # ``_zr0_tile``), so the AB p-update above needed NO operand
            # stream, and here
            #   z <- z − alpha·(Dinv·ap) and the next inner product
            #   Σ z·r = Σ z²·(1/Dinv)
            # both come off the ONE dinv stream (the guarded per-element
            # reciprocal costs VPU divides, but pass C is bandwidth-bound
            # with slack). One dinv pass and one pipeline drain fewer per
            # iteration than the r-state form (6.06 -> 5.06 passes at
            # 2400x3200). The per-element z evolution rounds differently
            # from (r − alpha·ap)·Dinv, but — unlike the scalar zr
            # recurrence of pipelined-CG, which drifts the convergence
            # sequence — it preserves the published iteration-count
            # oracles exactly (176 @ 200x132, 546 @ 400x600 verified
            # elementwise on the host; 2449 @ 2400x3200 asserted by the
            # bench on hardware).
            def pass_c(t, slot, acc):
                dw2a, zra = acc
                rows = pl.ds(t * tm, tm)
                w = w_s[rows, :]
                w_new = w + alpha * p_s[pl.ds(_BAND + t * tm, tm), :]
                dw = w_new - w
                w_s[rows, :] = w_new
                dvt = _read("dinv", t, slot, tm)
                z_new = r_s[rows, :] - alpha * (
                    dvt * _read("ap", t, slot, tm)
                )
                r_s[rows, :] = z_new
                # guarded reciprocal: d = 1/Dinv on the interior, 0 off it
                dt = jnp.where(
                    dvt != 0.0,
                    1.0 / jnp.where(dvt != 0.0, dvt, jnp.ones_like(dvt)),
                    jnp.zeros_like(dvt),
                )
                return (
                    dw2a + jnp.sum(dw * dw),
                    zra + jnp.sum((z_new * z_new) * dt),
                )

            c_loaders = [_loader("ap"), _loader("dinv")]

        dw2, zr_raw = _pipelined(
            c_loaders, pass_c,
            (jnp.zeros((), dtype), jnp.zeros((), dtype)),
        )
        zr_new = zr_raw * h1h2

        ndiff = jnp.sqrt(dw2 * h1h2) if weighted else jnp.sqrt(dw2)
        conv = ~breakdown & (ndiff < delta)
        ndiff = jnp.where(breakdown, diff, ndiff)
        beta_new = jnp.where(breakdown, beta, zr_new / zr)
        zr_out = jnp.where(breakdown, zr, zr_new)
        return (k + 1, zr_out, beta_new, ndiff, conv, breakdown)

    out = lax.while_loop(cond, body, carry0)

    cp = pltpu.make_async_copy(w_s, w_out, sems.at[0])
    cp.start()
    cp.wait()
    iters_out[0] = out[0]
    diff_out[0] = out[3]
    flags_out[0] = out[4].astype(jnp.int32)
    flags_out[1] = out[5].astype(jnp.int32)


def streamed_operand_set(problem: Problem, dtype, g1p: int, g2p: int,
                         geometry=None, theta=None):
    """(dinv, an, bw, r0): f64-assembled, rounded once, zero-padded to
    (g1p, g2p) — the operand fidelity contract shared by the streamed
    and xl engines (one copy; see ``fused_pcg.build_fused_solver``).

    dinv is the guarded 1/D from the f64 diagonal; an/bw are the
    UNMASKED h²-normalised coefficients (identical values at interior
    points to the fused/resident operand set) so the tile stencils'
    south/east offset slices are valid — the in-kernel output mask
    zeroes the ring. ``an`` carries an extra 8 padded rows for the
    stencil's aligned (tm+8)-row DMA windows.
    """
    import numpy as np

    from poisson_ellipse_tpu.ops.fused_pcg import (
        interior_normalized,
        normalized_unmasked,
    )

    np_dtype = np.dtype(jnp.dtype(dtype).name)
    a64, b64, rhs64 = assembly.assemble_numpy(problem, geometry=geometry,
                                              theta=theta)
    dinv64 = interior_normalized(problem, a64, b64)[5]
    anu64, bwu64 = normalized_unmasked(problem, a64, b64)

    def padded(x, extra_rows=0):
        return jnp.asarray(
            np.pad(
                x, ((0, g1p + extra_rows - x.shape[0]), (0, g2p - x.shape[1]))
            ).astype(np_dtype)
        )

    return (padded(dinv64), padded(anu64, 8), padded(bwu64), padded(rhs64))


def build_streamed_solver(problem: Problem, dtype=jnp.float32,
                          interpret=None, tm: int | None = None,
                          geometry=None, theta=None, storage_dtype=None):
    """(jitted whole-solve kernel, args) for large grids.

    args = (dinv, a, b, r0), all f64-assembled and rounded once (same
    operand fidelity as ``fused_pcg.build_fused_solver``).
    tm — row-tile height (see StreamPlan).

    ``storage_dtype`` (``ops.precision``): the state (w, r, p) is
    VMEM-resident here, so the engine's per-iteration HBM traffic IS the
    streamed operand set — a narrow storage dtype stores dinv/a/b at
    that width and the kernel upcasts each tile after its DMA
    (``_read``), cutting the per-iteration bytes by the storage ratio.
    r0 stays at compute width (read once per solve, not per iteration).
    """
    from poisson_ellipse_tpu.ops.precision import resolve_storage_dtype

    if jnp.dtype(dtype).itemsize >= 8:
        raise ValueError("streamed solver supports f32/bf16")
    st = resolve_storage_dtype(storage_dtype, dtype)
    if interpret is None:
        interpret = _interpret_default()
    g1, g2 = problem.node_shape
    # the plan budgets buffers at compute width — conservative under a
    # narrow storage dtype (the operand buffers shrink, never grow)
    plan = StreamPlan(problem, dtype, tm=tm)
    if not plan.fits:
        raise ValueError(
            f"grid {problem.M}x{problem.N}: PCG state (w, r, p) alone "
            "exceeds the VMEM budget — the streamed engine cannot hold "
            "it on-chip; use the xl engine (auto's pick there) or the "
            "sharded solver"
        )
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
    res = plan.resident
    # resident operands hold the full padded array; streamed ones get a
    # 2-slot double buffer — row counts come from the plan (one source).
    # Operand buffers match the (possibly narrow) storage width; ap is
    # iteration state and stays at compute width.
    buf = lambda name: pltpu.VMEM(
        ((plan.full_rows if res[name] else plan.tile_rows)[name], g2p),
        st if (st is not None and name in ("dinv", "a", "b")) else dtype,
    )
    call = pl.pallas_call(
        kernel,
        in_specs=[anyspec()] * 4,
        out_specs=(anyspec(), smem(), smem(), smem(), anyspec()),
        out_shape=(
            jax.ShapeDtypeStruct((g1p, g2p), dtype),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), dtype),
            jax.ShapeDtypeStruct((2,), jnp.int32),
            # HBM scratch for ap when it is not VMEM-resident (an output
            # only because pallas scratch cannot live in HBM)
            jax.ShapeDtypeStruct(
                (8, g2p) if res["ap"] else (g1p, g2p), dtype
            ),
        ),
        scratch_shapes=[
            pltpu.VMEM((g1p, g2p), dtype),             # w
            pltpu.VMEM((g1p, g2p), dtype),             # r (z when streamed)
            pltpu.VMEM((g1p + 2 * _BAND, g2p), dtype),  # p with bands
            buf("dinv"),
            buf("a"),
            buf("b"),
            buf("ap"),
            pltpu.SemaphoreType.DMA((8,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=scaled_vmem_budget(_VMEM_LIMIT)
        ),
        interpret=interpret,
    )

    def solver(dinv, a, b, r0):
        w_pad, iters, diff, flags, _ap = call(dinv, a, b, r0)
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


def solve_streamed(problem: Problem, dtype=jnp.float32,
                   interpret=None) -> PCGResult:
    solver, args = build_streamed_solver(problem, dtype, interpret=interpret)
    return solver(*args)
