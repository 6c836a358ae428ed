"""Pallas TPU kernel for the fused BCPNN lazy cell update.

This kernel is the TPU analogue of the paper's per-cell FPU-set datapath
(§VI.C: <3 mul, 2 add, 2 exp> + log/div, two cells per cycle) combined with
its ping-pong buffering (EQ3, k=2):

  * the whole closed-form ZEP decay + Hebbian increment + Bayesian weight is
    ONE fused VPU pipeline — traces never round-trip to HBM between stages;
  * Pallas double-buffers HBM->VMEM tile DMA across grid steps, overlapping
    memory with compute exactly like the paper's ping-pong buffers mask
    T_DRAM behind T_row_comp;
  * blocks are (BS, 128)-shaped: the 128-lane dimension is the hardware
    analogue of the paper's "cell-level parallelism" (#FPU_sets).

Five entry points:
  row_update_kernel_call        : (S, C) row blocks, rank-1 counts x zj
  col_update_kernel_call        : a column viewed as (R/128, 128) lanes
  worklist_update_kernel_call   : scalar-prefetch grid over a network-global
                                  worklist of flat (H*R, Cp) plane rows
  fused_row_update_kernel_call  : the worklist row-phase MEGAKERNEL — same
                                  scalar-prefetch grid, but one grid step
                                  completes the whole row phase for its
                                  entry: the five ij planes AND the four
                                  i-vectors are rewritten in place, and the
                                  freshly recomputed weight row is emitted
                                  per entry for the WTA drive
  fused_col_update_kernel_call  : the worklist column-phase MEGAKERNEL —
                                  2-D scalar-prefetch grid over FIRED
                                  ENTRIES x ROW-BLOCKS: each step rewrites
                                  one (8, 128) lane tile of the fired
                                  column in place through an in-kernel
                                  lane mask (Tij `now` stamp emitted
                                  in-kernel); padding fired-batch entries
                                  are pinned onto a dedicated junk
                                  row-block

All alias the five state-plane inputs onto their outputs
(``input_output_aliases``), so the Zij/Eij/Pij/Wij/Tij planes are rewritten
in place — the paper's in-situ 192-bit cell rewrite — instead of allocating
five fresh planes per call.

These kernels are layout-oblivious: they always see a flat (rows, lanes)
plane. The PR 8 column-blocked storage (`core.layout.BlockedLayout`) feeds
them at its TPU degenerate point (Tc == 1, the (8, 128) tile) as a pure
reshape (`BlockedLayout.flat_view`) with the row-index stream remapped by
the engine — no BlockSpec/index-map variant needed here.

The two worklist ROW kernels are the TPU half of the O(touched rows) tick
runtime (`repro.core.worklist` + `repro.core.engine.WorklistBackend`; the
flat (H*R, C) planes they consume are the canonical STORED layout of
`NetworkState.hcus`). Mosaic takes no (1, C) row block and no (1, 1)
scalar block, so they work as follows:

  * the planes (and the fused kernel's i-vectors, viewed as (HR/128, 128))
    stay in HBM (`memory_space=pl.ANY`); grid step i copies the touched
    row rows[i] into VMEM scratch, updates it with the fused cell math and
    copies it back, with synchronous DMAs on the aliased output buffers.
    Two entries in one (8, 128) tile — which the worklist's slot order
    does not keep adjacent — therefore never see a stale copy of it;
  * the worklist row indices, the valid count and `now` arrive as
    scalar-prefetch operands, the per-entry scalars (counts, P_i, the new
    i-vector values) as whole arrays in SMEM, and the per-entry Zj/Pj rows
    as (8, Cp) VMEM blocks from which each step picks its sublane;
  * invalid steps (past the valid count, or a sentinel row) touch no
    plane.

Per tick the planes therefore cost O(worklist) row DMAs instead of
O(H*R*C) gather/scatter traffic — the memory-access shape of the paper's
lazy model (§VI.D: bandwidth scales with spikes, not synapses). Because
grid steps rewrite data-dependent rows in place, the worklist grids are
declared with ``("arbitrary", ...)`` dimension semantics — never
"parallel", which is reserved for the dense row/col kernels whose blocks
are disjoint.

Validated against `bcpnn_ref` in interpret mode (tests/test_kernels.py,
tests/test_worklist.py) and compiled for a described TPU v5e at rodent
and human widths (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.traces import DecayCoeffs

# Default VMEM tiling. Row updates arrive as (n_spikes<=40, 128-padded C);
# column updates as (R/128, 128). (8,128) is the f32 native tile; BS=8 keeps
# the working set (12 planes * 8*128*4B = 48 KiB) far under VMEM while giving
# the DMA engine whole tiles.
DEFAULT_BLOCK_S = 8
DEFAULT_BLOCK_L = 128


def _cell_math(z, e, p, dt, dz, p_pre, p_post, k: DecayCoeffs, eps: float):
    """Shared per-cell arithmetic; mirrors traces.decay_zep + bayesian_weight."""
    ez = jnp.exp(-dt * k.inv_tau_z)
    ee = jnp.exp(-dt * k.inv_tau_e)
    ep_ = jnp.exp(-dt * k.inv_tau_p)
    e1 = e * ee + z * (ez - ee) * k.c_ze
    p1 = (p * ep_
          + (e - z * k.c_ze) * (ee - ep_) * k.c_ep
          + z * k.c_ze * (ez - ep_) * k.c_zp)
    z1 = z * ez + dz
    w1 = jnp.log((p1 + eps * eps) / ((p_pre + eps) * (p_post + eps)))
    return z1, e1, p1, w1


def _row_kernel(now_ref, z_ref, e_ref, p_ref, w_ref, t_ref, counts_ref,
                zj_ref, pi_ref, pj_ref, zo_ref, eo_ref, po_ref, wo_ref,
                to_ref, *, k: DecayCoeffs, eps: float):
    # w_ref is never read: Wij is recomputed, but threading it through as an
    # input lets pallas_call alias it onto wo_ref (in-place plane rewrite).
    del w_ref
    now = now_ref[0, 0]
    dt = (now - t_ref[...]).astype(jnp.float32)
    dz = counts_ref[...] * zj_ref[...]          # (BS,1) * (1,BL) rank-1 bcast
    z1, e1, p1, w1 = _cell_math(z_ref[...], e_ref[...], p_ref[...], dt, dz,
                                pi_ref[...], pj_ref[...], k, eps)
    to_ref[...] = jnp.full_like(t_ref[...], now)
    zo_ref[...] = z1
    eo_ref[...] = e1
    po_ref[...] = p1
    wo_ref[...] = w1


def _col_kernel(now_ref, z_ref, e_ref, p_ref, w_ref, t_ref, zi_ref, pi_ref,
                pj_ref, zo_ref, eo_ref, po_ref, wo_ref, to_ref,
                *, k: DecayCoeffs, eps: float):
    del w_ref                                    # alias-only input (see above)
    now = now_ref[0, 0]
    dt = (now - t_ref[...]).astype(jnp.float32)
    z1, e1, p1, w1 = _cell_math(z_ref[...], e_ref[...], p_ref[...], dt,
                                zi_ref[...], pi_ref[...], pj_ref[...], k, eps)
    to_ref[...] = jnp.full_like(t_ref[...], now)
    zo_ref[...] = z1
    eo_ref[...] = e1
    po_ref[...] = p1
    wo_ref[...] = w1


def _compiler_params(semantics=("parallel", "parallel")):
    """TPU compiler params with explicit dimension semantics.

    The dense row/col kernels write disjoint (bs, bl) blocks, so their 2-D
    grids are genuinely ("parallel", "parallel"). The worklist kernels'
    grids are data-dependent — steps rewrite planes in place at prefetched
    rows, and one step must see the writes of the steps before it — so they
    MUST be ("arbitrary", ...): declaring them parallel would license
    Mosaic to reorder or overlap grid steps whose writes alias.
    """
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))


# Alias the five state planes onto the five outputs: Zij/Eij/Pij/Wij/Tij are
# rewritten in place (the TPU analogue of the paper's in-situ 192-bit cell
# rewrite, §VI.C) — per update the planes cost one HBM read + one write
# instead of read + write-to-fresh-allocation, halving traffic on the planes.
# Input indices: 0=now, 1=zij, 2=eij, 3=pij, 4=wij, 5=tij.
_PLANE_ALIASES = {1: 0, 2: 1, 3: 2, 4: 3, 5: 4}


@functools.partial(jax.jit, static_argnames=("k", "eps", "bs", "bl", "interpret"))
def row_update_kernel_call(zij, eij, pij, wij, tij, now, counts, zj, p_i, p_j,
                           k: DecayCoeffs, eps: float,
                           bs: int = DEFAULT_BLOCK_S, bl: int = DEFAULT_BLOCK_L,
                           interpret: bool = False):
    """Pallas row update over (S, C) blocks. S % bs == 0, C % bl == 0 required
    (ops.py pads). counts (S,), zj (C,), p_i (S,), p_j (C,). All five plane
    inputs are donated to the outputs via input_output_aliases."""
    S, C = zij.shape
    grid = (S // bs, C // bl)
    now_arr = jnp.asarray(now, jnp.int32).reshape(1, 1)
    sc = pl.BlockSpec((bs, bl), lambda i, j: (i, j))
    s1 = pl.BlockSpec((bs, 1), lambda i, j: (i, 0))
    c1 = pl.BlockSpec((1, bl), lambda i, j: (0, j))
    one = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((S, C), jnp.float32)] * 4 \
        + [jax.ShapeDtypeStruct((S, C), jnp.int32)]
    fn = pl.pallas_call(
        functools.partial(_row_kernel, k=k, eps=eps),
        grid=grid,
        in_specs=[one, sc, sc, sc, sc, sc, s1, c1, s1, c1],
        out_specs=[sc, sc, sc, sc, sc],
        out_shape=out_shape,
        input_output_aliases=_PLANE_ALIASES,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )
    return fn(now_arr, zij, eij, pij, wij, tij,
              counts.reshape(S, 1), zj.reshape(1, C),
              p_i.reshape(S, 1), p_j.reshape(1, C))


def _dma(pairs, sem):
    """Start one DMA per (src, dst) ref pair, then wait for all of them.

    The worklist kernels move every plane row through VMEM with these
    synchronous copies: a step's write-back has landed in HBM before the
    next step reads, so two entries that share an (8, 128) tile — or an
    i-vector lane group — never compute from or write back a stale copy,
    whatever their order in the worklist."""
    copies = [pltpu.make_async_copy(s, d, sem.at[n])
              for n, (s, d) in enumerate(pairs)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def _update_row_bufs(bz, be, bp, bw, bt, now, count, zj, p_i, pj,
                     k: DecayCoeffs, eps: float):
    """Fused cell math on one (1, Cp) plane row held in VMEM scratch:
    the staged Zij/Eij/Pij/Tij rows are rewritten in place, Wij recomputed
    into bw. Returns the new weight row."""
    dt = (now - bt[...]).astype(jnp.float32)
    z1, e1, p1, w1 = _cell_math(bz[...], be[...], bp[...], dt, count * zj,
                                p_i, pj, k, eps)
    bz[...] = z1
    be[...] = e1
    bp[...] = p1
    bw[...] = w1
    bt[...] = jnp.full_like(bt[...], now)
    return w1


def _row_scratch(cp: int):
    """VMEM staging rows for the five ij planes (Tij is int32)."""
    return [pltpu.VMEM((1, cp), jnp.float32)] * 4 \
        + [pltpu.VMEM((1, cp), jnp.int32)]


def _worklist_kernel(rows_ref, nv_ref, now_ref, counts_ref, pi_ref,
                     z_hbm, e_hbm, p_hbm, w_hbm, t_hbm, zj_ref, pj_ref,
                     zo, eo, po, wo, to, bz, be, bp, bw, bt, sem,
                     *, k: DecayCoeffs, eps: float):
    """One worklist entry per grid step. The planes stay in HBM: entry i
    (i < nv) DMAs its plane row rows_ref[i] into VMEM, updates it with the
    fused cell math and DMAs it back; entries at or past nv do nothing.
    Rows are read through the aliased OUTPUT refs (the same HBM buffers as
    the inputs), so every step sees the writes of the steps before it.
    Per-entry scalars come from SMEM; the per-entry Zj/Pj rows arrive as
    (8, Cp) blocks and the entry picks its sublane."""
    del z_hbm, e_hbm, p_hbm, w_hbm, t_hbm          # aliased onto zo..to
    i = pl.program_id(0)

    @pl.when(i < nv_ref[0])
    def _():
        row = pl.ds(rows_ref[i], 1)
        _dma([(zo.at[row], bz), (eo.at[row], be), (po.at[row], bp),
              (to.at[row], bt)], sem)
        s = pl.ds(i % 8, 1)
        _update_row_bufs(bz, be, bp, bw, bt, now_ref[0], counts_ref[i],
                         zj_ref[s, :], pi_ref[i], pj_ref[s, :], k, eps)
        _dma([(bz, zo.at[row]), (be, eo.at[row]), (bp, po.at[row]),
              (bw, wo.at[row]), (bt, to.at[row])], sem)


# With PrefetchScalarGridSpec the alias indices count the scalar-prefetch
# operands first: 0=rows, 1=nv, 2=now, 3=counts, 4=p_i, then 5=zij ... 9=tij.
_WORKLIST_ALIASES = {5: 0, 6: 1, 7: 2, 8: 3, 9: 4}


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _hbm():
    return pl.BlockSpec(memory_space=pl.ANY)


@functools.partial(jax.jit, static_argnames=("k", "eps", "interpret"))
def worklist_update_kernel_call(zij, eij, pij, wij, tij, rows, nv, now,
                                counts, zj, p_i, pj, k: DecayCoeffs,
                                eps: float, interpret: bool = False):
    """Scalar-prefetch Pallas worklist update over flat (HR, Cp) planes.

    rows (W,) int32 — flat plane row index per worklist entry, compacted
    valid-first and in [0, HR) for entries < nv (entries >= nv are skipped
    whatever they hold); nv (1,) int32 — valid-entry count; counts/p_i (W,)
    per-entry scalars (SMEM); zj/pj (W, Cp) per-entry rows. Cp % 128 == 0
    and W % 8 == 0 required (ops.py pads). The five plane inputs alias the
    outputs and stay in HBM: each valid step rewrites only its touched row —
    O(worklist) DMA per call.
    """
    HR, Cp = zij.shape
    W = rows.shape[0]
    ent = pl.BlockSpec((8, Cp), lambda i, *_: (i // 8, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(W,),
        in_specs=[_smem(), _smem()] + [_hbm()] * 5 + [ent, ent],
        out_specs=[_hbm()] * 5,
        scratch_shapes=_row_scratch(Cp) + [pltpu.SemaphoreType.DMA((5,))],
    )
    out_shape = [jax.ShapeDtypeStruct((HR, Cp), jnp.float32)] * 4 \
        + [jax.ShapeDtypeStruct((HR, Cp), jnp.int32)]
    fn = pl.pallas_call(
        functools.partial(_worklist_kernel, k=k, eps=eps),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=_WORKLIST_ALIASES,
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=interpret,
    )
    return fn(rows.astype(jnp.int32), jnp.asarray(nv, jnp.int32).reshape(1),
              jnp.asarray(now, jnp.int32).reshape(1),
              counts, p_i, zij, eij, pij, wij, tij, zj, pj)


def _fused_row_kernel(rows_ref, now_ref, counts_ref, piv_ref, zin_ref,
                      ein_ref, pin_ref, z_hbm, e_hbm, p_hbm, w_hbm, t_hbm,
                      zi_hbm, ei_hbm, pi_hbm, ti_hbm, zj_ref, pj_ref,
                      zo, eo, po, wo, to, zio, eio, pio, tio, wrow_ref,
                      bz, be, bp, bw, bt, bzi, bei, bpi, bti, sem,
                      *, k: DecayCoeffs, eps: float, hr: int):
    """One worklist entry per grid step, the WHOLE row phase fused:

      * the entry's ij-plane rows are DMA'd from HBM, updated with the
        fused cell math and DMA'd back in place (aliased);
      * the entry's i-vector cells are rewritten in place from the
        post-decay values in SMEM (the i-vector math runs once in the
        engine prologue — same sealed `ivec_decay` island as every other
        path — so the kernel only moves the results). The i-vectors are
        viewed as (HR/128, 128): the cell is lane r % 128 of row r // 128,
        which is read, patched under a lane mask and written back;
      * the recomputed weight row is emitted to the per-entry `wrow`
        output, which IS the WTA drive input — no post-kernel re-gather of
        Wij.

    Validity is per entry, not a compacted prefix: `rows` is slot-ordered
    and the caller marks invalid slots with a row >= hr. Such a step
    touches no plane and emits a zero weight row."""
    del z_hbm, e_hbm, p_hbm, w_hbm, t_hbm, zi_hbm, ei_hbm, pi_hbm, ti_hbm
    i = pl.program_id(0)
    r = rows_ref[i]
    valid = r < hr
    s = pl.ds(i % 8, 1)

    @pl.when(valid)
    def _():
        now = now_ref[0]
        row, ivrow = pl.ds(r, 1), pl.ds(r // 128, 1)
        _dma([(zo.at[row], bz), (eo.at[row], be), (po.at[row], bp),
              (to.at[row], bt), (zio.at[ivrow], bzi), (eio.at[ivrow], bei),
              (pio.at[ivrow], bpi), (tio.at[ivrow], bti)], sem)
        w1 = _update_row_bufs(bz, be, bp, bw, bt, now, counts_ref[i],
                              zj_ref[s, :], piv_ref[i], pj_ref[s, :], k, eps)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1) == r % 128
        bzi[...] = jnp.where(lane, zin_ref[i], bzi[...])
        bei[...] = jnp.where(lane, ein_ref[i], bei[...])
        bpi[...] = jnp.where(lane, pin_ref[i], bpi[...])
        bti[...] = jnp.where(lane, now, bti[...])
        _dma([(bz, zo.at[row]), (be, eo.at[row]), (bp, po.at[row]),
              (bw, wo.at[row]), (bt, to.at[row]), (bzi, zio.at[ivrow]),
              (bei, eio.at[ivrow]), (bpi, pio.at[ivrow]),
              (bti, tio.at[ivrow])], sem)
        wrow_ref[s, :] = w1

    @pl.when(jnp.logical_not(valid))
    def _():
        wrow_ref[s, :] = jnp.zeros((1, wrow_ref.shape[1]), jnp.float32)


# Megakernel aliases (prefetch operands count first): 0=rows, 1=now,
# 2..6 = the SMEM per-entry scalars, 7=zij..11=tij -> plane outputs 0..4;
# 12=zi..15=ti -> i-vector outputs 5..8. Output 9 (the per-entry weight
# row) is the one fresh allocation.
_FUSED_ALIASES = {7: 0, 8: 1, 9: 2, 10: 3, 11: 4, 12: 5, 13: 6, 14: 7,
                  15: 8}


@functools.partial(jax.jit, static_argnames=("k", "eps", "hr", "interpret"))
def fused_row_update_kernel_call(zij, eij, pij, wij, tij, zi, ei, pi, ti,
                                 rows, now, counts, zj, p_i, pj,
                                 zi_new, ei_new, pi_new, k: DecayCoeffs,
                                 eps: float, hr: int, interpret: bool = False):
    """Scalar-prefetch Pallas megakernel for the fused worklist row phase.

    Planes (HR, Cp) f32/int32 with Cp % 128 == 0; i-vectors viewed as
    (HRq, 128) with HRq * 128 >= hr; rows (W,) int32 SLOT-ordered flat row
    indices, >= hr on padding/duplicate slots (``hr`` is the logical H*R
    row count). counts/p_i/zi_new/ei_new/pi_new (W,) per-entry scalars
    (SMEM) and zj/pj (W, Cp) per-entry rows, W % 8 == 0 (ops.py pads). The
    nine state inputs alias the nine state outputs and stay in HBM
    (in-place rewrite); the tenth output is the (W, Cp) weight-row buffer
    consumed by the WTA drive.
    """
    HR, Cp = zij.shape
    HRq = zi.shape[0]
    W = rows.shape[0]
    ent = pl.BlockSpec((8, Cp), lambda i, *_: (i // 8, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(W,),
        in_specs=[_smem()] * 5 + [_hbm()] * 9 + [ent, ent],
        out_specs=[_hbm()] * 9 + [ent],
        scratch_shapes=_row_scratch(Cp)
        + [pltpu.VMEM((1, 128), jnp.float32)] * 3
        + [pltpu.VMEM((1, 128), jnp.int32), pltpu.SemaphoreType.DMA((9,))],
    )
    out_shape = [jax.ShapeDtypeStruct((HR, Cp), jnp.float32)] * 4 \
        + [jax.ShapeDtypeStruct((HR, Cp), jnp.int32)] \
        + [jax.ShapeDtypeStruct((HRq, 128), jnp.float32)] * 3 \
        + [jax.ShapeDtypeStruct((HRq, 128), jnp.int32)] \
        + [jax.ShapeDtypeStruct((W, Cp), jnp.float32)]
    fn = pl.pallas_call(
        functools.partial(_fused_row_kernel, k=k, eps=eps, hr=hr),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=_FUSED_ALIASES,
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=interpret,
    )
    return fn(rows.astype(jnp.int32), jnp.asarray(now, jnp.int32).reshape(1),
              counts, p_i, zi_new, ei_new, pi_new,
              zij, eij, pij, wij, tij, zi, ei, pi, ti, zj, pj)


def _fused_col_kernel(rbase_ref, rstep_ref, jt_ref, jl_ref, now_ref, z_ref,
                      e_ref, p_ref, w_ref, t_ref, zi_ref, pi_ref, pj_ref,
                      zo_ref, eo_ref, po_ref, wo_ref, to_ref,
                      *, k: DecayCoeffs, eps: float, bs: int, bl: int):
    """Grid step (entry e, row-block rb) of the fused column phase: the
    (bs, bl) lane tile of the five ij planes containing rows
    [h*R + rb*bs, ...) of the entry's fired column (rbase_ref[e] and the
    tile index jt_ref[e] selected the block) is DMA'd in, the fused cell
    math runs on every lane, and ONLY the fired column's lane (jl_ref[e],
    an in-kernel iota mask) is replaced — every other lane is written back
    bit-unchanged. Lane tiles are 128 wide, so Mosaic's lane-dimension
    alignment rules are satisfied without data-dependent sub-lane offsets
    (a (R, 1) block at a prefetched lane offset would not lower).

    The per-entry presynaptic traces arrive as the (bs, bl) tile holding
    entry e's lane (lane tile e // bl of the (R, K') buffers, K' the
    capacity rounded up to whole lane tiles); the entry's own lane, e % bl,
    is selected with a second mask and a lane reduce. The postsynaptic P
    scalar comes from SMEM. Validity arrives as
    rstep_ref[e] (1 = valid): the caller pins every one of a padding
    entry's grid steps onto the dedicated junk row-block past the logical
    plane (rbase = HR/bs, rstep = 0), so a padding step can only ever
    rewrite junk — which matters beyond defense in depth: the block
    pipeline hands each step the block contents as of its own DMA, so a
    padding step sharing a tile with an already-updated valid column
    would write the STALE tile back. Valid entries never collide with
    each other (fired-batch HCU indices are unique, so their (h, jt)
    tiles differ); padding entries share only the junk block."""
    e = pl.program_id(0)
    valid = rstep_ref[e] == 1
    jl = jl_ref[e]
    now = now_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bs, bl), 1)
    hit = valid & (lane == jl)                              # (bs, bl) mask
    # select the entry's presynaptic lane out of its (bs, bl) trace tiles;
    # bl is a power of two, so e % bl is a mask (a remainder costs the
    # scalar core a sequence of ops every grid step)
    sel = (lane == (e & (bl - 1))).astype(jnp.float32)
    zi = jnp.sum(zi_ref[...] * sel, axis=1, keepdims=True)  # (bs, 1)
    p_i = jnp.sum(pi_ref[...] * sel, axis=1, keepdims=True)
    dt = (now - t_ref[...]).astype(jnp.float32)
    z1, e1, p1, w1 = _cell_math(z_ref[...], e_ref[...], p_ref[...], dt,
                                zi, p_i, pj_ref[e], k, eps)
    zo_ref[...] = jnp.where(hit, z1, z_ref[...])
    eo_ref[...] = jnp.where(hit, e1, e_ref[...])
    po_ref[...] = jnp.where(hit, p1, p_ref[...])
    wo_ref[...] = jnp.where(hit, w1, w_ref[...])
    to_ref[...] = jnp.where(hit, jnp.full_like(t_ref[...], now), t_ref[...])


# Column-megakernel aliases (prefetch operands count first): 0=row_base,
# 1=row_step, 2=j_tile, 3=j_lane, 4=now, 5=zij ... 9=tij -> outputs 0..4.
_FUSED_COL_ALIASES = {5: 0, 6: 1, 7: 2, 8: 3, 9: 4}


@functools.partial(jax.jit, static_argnames=("k", "eps", "r", "bs",
                                             "interpret"))
def fused_col_update_kernel_call(zij, eij, pij, wij, tij, row_base, row_step,
                                 j_tile, j_lane, now, zi_cols, pi_cols, pj_e,
                                 k: DecayCoeffs, eps: float, r: int,
                                 bs: int = DEFAULT_BLOCK_S,
                                 interpret: bool = False):
    """Scalar-prefetch Pallas megakernel for the fused worklist column phase.

    Planes (H*r + bs, Cp) f32/int32 with Cp % 128 == 0 and r % bs == 0
    (ops.py pads; the trailing bs rows are the junk row-block). Per
    fired-batch entry, four prefetched (K,) int32 arrays select the column
    as lane ``j_lane`` of the (bs, 128) tiles at block
    (row_base + rb * row_step, j_tile): valid entries carry
    (h*r/bs, 1, j//128, j%128); padding entries carry (H*r/bs, 0, 0, 0) so
    every one of their grid steps lands on the junk row-block (they must
    never share a tile with a valid entry — see the kernel docstring). The
    grid is 2-D (entry, row-block), so VMEM holds only (bs, 128) tiles
    regardless of R (a human-scale R=10000 column does NOT fit VMEM as one
    block). zi_cols/pi_cols (r, K') are the per-entry presynaptic
    traces at `now`, column-major and lane-padded to whole lane tiles;
    step (e, rb) reads their (bs, 128) tile at block (rb, e // 128), so
    each step DMAs two trace tiles whatever K is. pj_e (K,) the per-entry
    postsynaptic P scalar (SMEM). The five plane inputs alias the five
    outputs: each grid step rewrites one (bs, 128) tile of the fired column
    in place — O(fired columns x R/bs) tile DMAs per call, the minimum the
    128-lane tile granularity allows (the paper's §VI.D column budget, at
    hardware tile resolution). Data-dependent in-place tiles ->
    ("arbitrary", "arbitrary") dimension semantics, like the row worklist
    kernels.
    """
    HRp, Cp = zij.shape
    K = row_base.shape[0]
    R_BS = r // bs
    L = DEFAULT_BLOCK_L
    lane_bits = L.bit_length() - 1          # e // L as a shift, L = 2**7
    tile = pl.BlockSpec((bs, L),
                        lambda e, rb, rbase, rstep, jt, jl, now:
                        (rbase[e] + rb * rstep[e], jt[e]))
    ent_tile = pl.BlockSpec((bs, L), lambda e, rb, *_: (rb, e >> lane_bits))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(K, R_BS),
        in_specs=[tile] * 5 + [ent_tile, ent_tile, _smem()],
        out_specs=[tile] * 5,
    )
    out_shape = [jax.ShapeDtypeStruct((HRp, Cp), jnp.float32)] * 4 \
        + [jax.ShapeDtypeStruct((HRp, Cp), jnp.int32)]
    fn = pl.pallas_call(
        functools.partial(_fused_col_kernel, k=k, eps=eps, bs=bs, bl=L),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=_FUSED_COL_ALIASES,
        compiler_params=_compiler_params(("arbitrary", "arbitrary")),
        interpret=interpret,
    )
    return fn(row_base.astype(jnp.int32), row_step.astype(jnp.int32),
              j_tile.astype(jnp.int32), j_lane.astype(jnp.int32),
              jnp.asarray(now, jnp.int32).reshape(1),
              zij, eij, pij, wij, tij, zi_cols, pi_cols, pj_e)


@functools.partial(jax.jit, static_argnames=("k", "eps", "bs", "bl", "interpret"))
def col_update_kernel_call(zij, eij, pij, wij, tij, now, zi_t, p_i, p_j_scalar,
                           k: DecayCoeffs, eps: float,
                           bs: int = DEFAULT_BLOCK_S, bl: int = DEFAULT_BLOCK_L,
                           interpret: bool = False):
    """Pallas column update; the (R,) column is pre-reshaped to (R/bl, bl).
    Plane inputs alias the outputs (in-place update, see _PLANE_ALIASES)."""
    S, C = zij.shape
    grid = (S // bs, C // bl)
    now_arr = jnp.asarray(now, jnp.int32).reshape(1, 1)
    sc = pl.BlockSpec((bs, bl), lambda i, j: (i, j))
    one = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((S, C), jnp.float32)] * 4 \
        + [jax.ShapeDtypeStruct((S, C), jnp.int32)]
    fn = pl.pallas_call(
        functools.partial(_col_kernel, k=k, eps=eps),
        grid=grid,
        in_specs=[one, sc, sc, sc, sc, sc, sc, sc, one],
        out_specs=[sc, sc, sc, sc, sc],
        out_shape=out_shape,
        input_output_aliases=_PLANE_ALIASES,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )
    return fn(now_arr, zij, eij, pij, wij, tij, zi_t, p_i,
              jnp.asarray(p_j_scalar, jnp.float32).reshape(1, 1))
