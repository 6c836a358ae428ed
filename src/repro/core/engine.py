"""Unified TickEngine: one tick pipeline behind pluggable plane backends.

The paper maps every BCU onto the *same* tiled compute fabric regardless of
scale (§III, §VI) — one update pipeline, parameterized by layout. This module
is that pipeline's software form. A network tick always has the same skeleton

    consume delay bucket -> plane update (rows / WTA / columns) -> fan out

and only the *plane update* differs by regime. The regimes are captured by
the `TickBackend` protocol with two implementations:

  * `DenseBackend`    — toy sizes: per-HCU `jax.vmap` over the batched
                        (H, R, C) view, with the fused dense write forms
                        (modes: "lazy", "eager" golden reference, "merged").
  * `WorklistBackend` — rodent/human scales: a network-global deduplicated
                        worklist over the canonical flat (H*R, C) planes.
                        The lazy row AND column phases are FUSED by
                        default: one stage+compute loop over the valid
                        entries (`worklist.fused_stage_compute` rows /
                        `worklist.fused_col_stage_compute` columns) + the
                        in-place writeback loop on CPU, or the
                        `ops.fused_row_update` / `ops.fused_col_update`
                        scalar-prefetch megakernels on TPU (`fused=` /
                        `fused_cols=` force either form, see
                        `hcu.use_fused_rows` / `hcu.use_fused_cols`); the
                        merged row phase uses the three-phase loops
                        (modes: "lazy", "merged"; docs/NUMERICS.md explains
                        why merged stays three-phase).

`select_backend(p, ...)` picks by the `hcu.use_worklist` size guard (the
`worklist=` runtime argument forces either); both backends produce
bitwise-identical trajectories (tests/test_worklist.py,
tests/test_engine_fixtures.py).

Canonical state layout
----------------------
`NetworkState.hcus` STORES the flat layout (`repro.core.layout`): ij planes
(H*R, C), i-vectors (H*R,), j-vectors (H, C). The WorklistBackend consumes it
natively — its scan carry is the stored layout, so the per-tick
flatten/unflatten round-trips of the previous runtime are gone. The
DenseBackend adapts once per compiled region via `carry_in`/`carry_out`
(zero-copy reshapes at the jit/scan boundary, never inside the tick body), so
its per-tick compute graph is exactly the historical per-HCU one — which is
what keeps trajectories bitwise-identical across the refactor (XLA:CPU fused
codegen is context-sensitive at 1 ulp; same-code-same-shape is the only safe
discipline).

One deliberate exception: the merged-mode overflow column flush runs on a
batched view *inside* the worklist tick. That flush is already a documented
O(H*R) per-tick trade (see `_merged_worklist_update`), and reusing the
per-HCU `column_flush_merged` graph verbatim is what keeps merged worklist
trajectories bitwise-identical to the vmapped path.

Execution drivers — `network_tick` / `network_run` (core/network.py) and
`make_dist_tick` / `make_dist_run` (core/distributed.py) — are thin wrappers:
they pick a backend, adapt the carry, and call `tick`. The sharded drivers
reuse the SAME `tick` body with a custom spike `route` (pack + all_to_all)
and a global-HCU-id RNG base, so the sharded worklist path needs no code of
its own. eBrainII correspondence: a `TickBackend` is the BCU tile's update
datapath; `tick` is the per-ms schedule (§II.A.2's three atomic sub-threads);
the `route` hook is the spike NoC port.

`Simulator` is the user-facing facade: init / run / run_sharded / save /
load (with the legacy-layout checkpoint migration shim) in a few lines.
"""
from __future__ import annotations

from typing import NamedTuple, Protocol

import jax
import jax.numpy as jnp

from repro.core import hcu as H
from repro.core import layout as L
from repro.core import network as N
from repro.core import reference
from repro.core import worklist as WL
from repro.core.params import BCPNNParams
from repro.core.traces import ZEP, decay_zep
from repro.kernels import ops


# ---------------------------------------------------------------------------
# shared plane-update building blocks
# ---------------------------------------------------------------------------

def _fired_mask(h_idx, j_idx, n: int, cols: int):
    """(H, C) mask of this tick's fired (hcu, column) cells; padding
    h_idx == n never matches arange(n)."""
    return jnp.any(
        (h_idx[:, None, None] == jnp.arange(n)[None, :, None])
        & (j_idx[:, None, None] == jnp.arange(cols)[None, None, :]),
        axis=0)


def _bump_zj(zj, h_idx, j_idx, n: int, p: BCPNNParams):
    """Postsynaptic Z increment for the compacted fired batch — the same
    two bitwise-identical branches (fused where below DENSE_CELLS_MAX,
    scatter-add above) shared by `column_updates_batched` and
    `_column_worklist`, so the worklist/vmap equivalence contract cannot
    silently diverge through an edit to one copy."""
    if n * p.rows * p.cols <= H.DENSE_CELLS_MAX:
        return jnp.where(_fired_mask(h_idx, j_idx, n, zj.shape[1]),
                         zj + 1.0, zj)
    return zj.at[h_idx, j_idx].add(1.0, mode="drop")


def column_updates_batched(hcus: H.HCUState, h_idx, j_idx, now,
                           p: BCPNNParams, backend=None) -> H.HCUState:
    """Lazy column updates for the compacted fired batch (network level).

    Operates on the BATCHED (H, R, C) view. h_idx: (K,) HCU indices (== H
    for padding -> scatter-dropped); j_idx: (K,) fired MCU column per slot.

    Gathers exactly the K (R,)-columns that fired (plus the K i-vectors) —
    never whole HCU states — so the cost is K*R cells, matching the paper's
    column-update traffic budget.
    """
    n = hcus.zij.shape[0]
    R = p.rows
    safe_h = jnp.minimum(h_idx, n - 1)
    h_ix = h_idx[:, None]                     # (K,1): padding == n -> dropped
    sh_ix = safe_h[:, None]
    r_ix = jnp.arange(R)[None, :]
    j_ix = j_idx[:, None]

    gcol = lambda plane: plane[sh_ix, r_ix, j_ix]             # (K, R)
    # i-vector traces brought to `now` (values only, no writeback)
    zep_i = H.ivec_decay(hcus.zi[safe_h], hcus.ei[safe_h],
                         hcus.pi[safe_h], hcus.ti[safe_h], now, p)
    pj_sc = hcus.pj[safe_h, j_idx]                            # (K,)

    z1, e1, p1, w1, t1 = jax.vmap(
        lambda z, e, pp, t, w, zi, pi, pj: H.ops.col_update(
            z, e, pp, t, now, zi, pi, pj, H.coeffs_ij(p), p.eps,
            backend=backend, w_col=w)
    )(gcol(hcus.zij), gcol(hcus.eij), gcol(hcus.pij), gcol(hcus.tij),
      gcol(hcus.wij), zep_i.z, zep_i.p, pj_sc)

    put = lambda plane, val: plane.at[h_ix, r_ix, j_ix].set(val, mode="drop")
    hcus = hcus._replace(
        zij=put(hcus.zij, z1), eij=put(hcus.eij, e1), pij=put(hcus.pij, p1),
        wij=put(hcus.wij, w1))
    if n * R * p.cols <= H.DENSE_CELLS_MAX:
        # fused where beats scatter for the constant-valued Tij write and
        # the +1.0 Zj bump (XLA CPU scatter has a high fixed per-op cost);
        # bitwise-identical to the scatter branch.
        fired_hc = _fired_mask(h_idx, j_idx, n, hcus.zj.shape[1])
        return hcus._replace(
            tij=jnp.where(fired_hc[:, None, :], now, hcus.tij),
            zj=_bump_zj(hcus.zj, h_idx, j_idx, n, p))
    return hcus._replace(
        tij=put(hcus.tij, t1),
        zj=_bump_zj(hcus.zj, h_idx, j_idx, n, p))


def _column_batched_on_flat(hcus: H.HCUState, h_idx, j_idx, now,
                            p: BCPNNParams, backend, n: int,
                            layout=None) -> H.HCUState:
    """Run `column_updates_batched` against canonical flat planes through a
    zero-copy batched view (used by the worklist path's Pallas branch, whose
    column step has always been the batched kernel). Under a blocked layout
    the planes round-trip through the canonical flat form (pure data
    movement — bitwise) so the batched graph itself is unchanged."""
    hb = column_updates_batched(
        L.batched_state(L.load_hcus(hcus, layout), n), h_idx, j_idx, now,
        p, backend=backend)
    return L.store_hcus(L.flat_state(hb), layout)


def _row_worklist_common(hcus: H.HCUState, rows, t, p: BCPNNParams):
    """Shared lazy/merged worklist prologue on the CANONICAL FLAT layout:
    j-vector decay, per-HCU dedup, i-vector decay (identical math to
    `hcu.row_updates`) and worklist build. Returns a dict of intermediates;
    the i-vector write values are h-major flat (H*A,) arrays indexed by
    worklist slot."""
    n, A = rows.shape
    R = p.rows
    zep_j = decay_zep(ZEP(hcus.zj, hcus.ej, hcus.pj), p.dt_ms, H.coeffs_j(p))
    hcus = hcus._replace(zj=zep_j.z, ej=zep_j.e, pj=zep_j.p)
    rows_u, counts = jax.vmap(lambda r: H.dedup_rows(r, R))(rows)
    safe = jnp.minimum(rows_u, R - 1)
    # gather i-vector entries by GLOBAL flat row index (the canonical layout
    # needs no (H, R) regrouping); values are sealed by ivec_decay's barriers
    g_safe = jnp.arange(n, dtype=jnp.int32)[:, None] * R + safe   # (H, A)
    take = lambda v: v[g_safe]
    zi_g, ti_g = take(hcus.zi), take(hcus.ti)
    zep_i = H.ivec_decay(zi_g, take(hcus.ei), take(hcus.pi), ti_g, t, p)
    zi_new = zep_i.z + counts
    g_row, order, nv = WL.build_worklist(rows_u, R)
    return dict(
        hcus=hcus, n=n, A=A, rows_u=rows_u, counts=counts,
        zep_i=zep_i, zi_new=zi_new, zi_g=zi_g, ti_g=ti_g,
        g_row=g_row, order=order, nv=nv,
        iv_vals=(zi_new.reshape(-1), zep_i.e.reshape(-1),
                 zep_i.p.reshape(-1)))


def _ij_flats(hcus: H.HCUState):
    return (hcus.zij, hcus.eij, hcus.pij, hcus.wij, hcus.tij)


def _put_flats(hcus: H.HCUState, flats) -> H.HCUState:
    return hcus._replace(zij=flats[0], eij=flats[1], pij=flats[2],
                         wij=flats[3], tij=flats[4])


def _wta(hcus: H.HCUState, w_rows, counts, t, keys, p: BCPNNParams):
    """Vmapped periodic update (support integration + soft WTA) on the raw
    (H, C) support/prior planes — layout-independent, same RNG stream as
    the per-HCU `hcu.periodic_update`."""
    h_new, fired = jax.vmap(
        lambda hv, pj, w, cnt, k: H.periodic_math(hv, pj, w, cnt, t, k, p)
    )(hcus.h, hcus.pj, w_rows, counts, keys)
    return hcus._replace(h=h_new), fired


def _in_scope(name: str, fn):
    """`fn`, traced under the named scope `name` wherever it is called (the
    column pass is built in one phase and run later by `tick`)."""
    def scoped(*args):
        with jax.named_scope(name):
            return fn(*args)
    return scoped


def _col_worklist_prologue(hcus: H.HCUState, h_idx, j_idx, now,
                           p: BCPNNParams, n: int):
    """Shared fused/staged column prologue: per-entry presynaptic traces
    brought to `now` (the sealed `ivec_decay` island on the (K, R) gathered
    i-vectors — identical graph in both forms, which is what keeps them
    bitwise-interchangeable) and the per-entry postsynaptic P."""
    R = p.rows
    safe_h = jnp.minimum(h_idx, n - 1)
    ivr = lambda v: v.reshape(n, R)[safe_h]                   # (K, R)
    zep_i = H.ivec_decay(ivr(hcus.zi), ivr(hcus.ei), ivr(hcus.pi),
                         ivr(hcus.ti), now, p)
    pj_sc = hcus.pj[safe_h, j_idx]                            # (K,)
    return zep_i, pj_sc


def _column_worklist(hcus: H.HCUState, h_idx, j_idx, now, p: BCPNNParams,
                     backend=None, fused: bool = True, layout=None):
    """Worklist twin of `column_updates_batched`: same compacted fired batch,
    same per-cell compute graph (bitwise-identical values), but the (R, 1)
    column blocks are read and rewritten in place through dynamic slices on
    the canonical flat planes instead of batched gather/scatter.

    ``fused`` (default, `hcu.use_fused_cols`) fuses staging and compute into
    one loop over the n_fired valid entries (`worklist.fused_col_stage_
    compute` + the in-place `write_cols` loop) — the PR 4 row recipe applied
    to columns. fused=False keeps the three-phase stage/compute/writeback
    form — bitwise-identical, kept as the A/B reference
    (tests/test_worklist.py).
    """
    n = hcus.zj.shape[0]
    R = p.rows
    n_fired = jnp.sum(h_idx < n)
    zep_i, pj_sc = _col_worklist_prologue(hcus, h_idx, j_idx, now, p, n)
    flats = _ij_flats(hcus)
    if fused:
        # fused stage+compute loop: per valid entry, read the (R, 1) column
        # block and run the SAME cell formulas the vmapped compute runs
        # (ops.col_update "ref" dispatch at (R,) — bitwise-identical to the
        # (K, R) vmapped form, pinned by the head fixtures) in the same
        # iteration — compute on n_fired entries instead of every fired-
        # batch slot. The writeback stays the separate in-place write_cols
        # loop (one-direction loop rule, docs/NUMERICS.md).
        zi_all, pi_all = zep_i.z, zep_i.p                     # (K, R)

        def col_math(e, z, ee, pp, tt):
            row = lambda v: jax.lax.dynamic_slice(v, (e, 0), (1, R)) \
                .reshape(R)
            pj_e = jax.lax.dynamic_slice(pj_sc, (e,), (1,))[0]
            z1, e1, p1, w1, _ = H.ops.col_update(
                z, ee, pp, tt, now, row(zi_all), row(pi_all), pj_e,
                H.coeffs_ij(p), p.eps, backend=backend)
            return z1, e1, p1, w1

        vals = WL.fused_col_stage_compute(
            (flats[0], flats[1], flats[2], flats[4]),
            h_idx, j_idx, n_fired, R, col_math, layout=layout)
    else:
        zb, eb, pb, tb = WL.read_cols(
            (flats[0], flats[1], flats[2], flats[4]),
            h_idx, j_idx, n_fired, R, layout=layout)
        # same vmap-of-col_update graph as column_updates_batched, fed from
        # the staged buffers (padding slots read zeros instead of clipped
        # gathers; their results are never written back)
        z1, e1, p1, w1, _ = jax.vmap(
            lambda z, e, pp, t, zi, pi, pj: H.ops.col_update(
                z, e, pp, t, now, zi, pi, pj, H.coeffs_ij(p), p.eps,
                backend=backend)
        )(zb, eb, pb, tb, zep_i.z, zep_i.p, pj_sc)
        vals = (z1, e1, p1, w1)
    flats = WL.write_cols(flats, h_idx, j_idx, n_fired, vals, now, R,
                          layout=layout)
    hcus = _put_flats(hcus, flats)
    # tij is already stamped by write_cols; only the Zj bump remains
    return hcus._replace(zj=_bump_zj(hcus.zj, h_idx, j_idx, n, p))


def _column_worklist_megakernel(hcus: H.HCUState, h_idx, j_idx, now,
                                p: BCPNNParams, backend, n: int, lay=None):
    """TPU half of the fused column phase: one scalar-prefetch Pallas
    megakernel launch (`ops.fused_col_update`) rewrites every fired (R, 1)
    column block of the five ij planes in place — Tij stamped in-kernel,
    padding fired-batch entries routed onto the junk lane. Replaces the
    batched-view kernel + gather/scatter tail the non-fused Pallas column
    step pays (`_column_batched_on_flat`).

    ``lay`` (a TPU-degenerate `layout.BlockedLayout`, Tc == 1) runs the SAME
    kernel on the row-padded flat view of the blocked planes — a pure
    reshape, since a (H*Tr, 1, xr, xc) block store is row-major (H*Pr, Pc)
    byte-for-byte. Only the engine-side indices change: each HCU spans
    `padded_rows` view rows and the presynaptic vectors are transiently
    zero-padded to match (the pad rows' outputs land on pad cells, which are
    outside the logical plane)."""
    R = p.rows
    zep_i, pj_sc = _col_worklist_prologue(hcus, h_idx, j_idx, now, p, n)
    if lay is not None:
        Pr = lay.padded_rows
        pad = (lambda v: jnp.pad(v, ((0, 0), (0, Pr - R)))) if Pr != R \
            else (lambda v: v)
        planes = tuple(lay.flat_view(f) for f in _ij_flats(hcus))
        flats = ops.fused_col_update(
            *planes, h_idx=h_idx, j_idx=j_idx, now=now,
            zi_t=pad(zep_i.z), p_i=pad(zep_i.p), pj_sc=pj_sc,
            coeffs=H.coeffs_ij(p), eps=p.eps, n_hcu=n, rows=Pr,
            backend=backend)
        flats = tuple(lay.from_flat_view(f) for f in flats)
    else:
        flats = ops.fused_col_update(
            *_ij_flats(hcus), h_idx=h_idx, j_idx=j_idx, now=now,
            zi_t=zep_i.z, p_i=zep_i.p, pj_sc=pj_sc,
            coeffs=H.coeffs_ij(p), eps=p.eps, n_hcu=n, rows=R,
            backend=backend)
    hcus = _put_flats(hcus, flats)
    return hcus._replace(zj=_bump_zj(hcus.zj, h_idx, j_idx, n, p))


def worklist_col_dispatch(kernel, fused_cols, h_idx, j_idx, t,
                          p: BCPNNParams, n: int, layout=None):
    """Pick the worklist backend's lazy column-phase implementation for the
    resolved kernel backend: the in-place loops (`_column_worklist`,
    fused or staged) on "ref", the `ops.fused_col_update` megakernel or
    the batched-view kernel on the Pallas backends. Returns a
    hcus -> hcus' closure.

    ``layout`` (a `layout.BlockedLayout` or None) selects the storage order
    the closures address. The Pallas megakernel only speaks the flat view,
    so a blocked layout off the TPU-degenerate point (col_tiles > 1) routes
    to the batched-view kernel, whose wrapper round-trips through canonical
    flat."""
    kb = kernel or ops.default_backend()
    lay = L.as_blocked(layout)
    if kb == "ref":
        return lambda hc: _column_worklist(hc, h_idx, j_idx, t, p,
                                           backend=kernel, fused=fused_cols,
                                           layout=lay)
    # the column megakernel selects the per-entry presynaptic lane out of
    # one 128-wide tile, so a fired batch larger than a lane tile falls
    # back to the batched-view kernel (n_hcu >= ~366 at the default
    # cap_fire formula) instead of tracing an unsatisfiable kernel
    if fused_cols and h_idx.shape[0] <= ops.bcpnn_update.DEFAULT_BLOCK_L \
            and (lay is None or lay.tpu_degenerate):
        return lambda hc: _column_worklist_megakernel(hc, h_idx, j_idx, t,
                                                      p, kb, n, lay=lay)
    return lambda hc: _column_batched_on_flat(hc, h_idx, j_idx, t, p,
                                              kernel, n, layout=lay)


def worklist_lazy_rows(hcus: H.HCUState, rows, t, p: BCPNNParams,
                       kernel: str | None = None, fused: bool = True,
                       layout=None):
    """Lazy worklist row phase on canonical flat planes: dedup + worklist
    build, in-place row rewrites (ds/dus loops on CPU, scalar-prefetch Pallas
    kernel on TPU) and the i-vector writeback. Returns (hcus', w_rows,
    common) where common carries the prologue intermediates (counts etc.).

    ``fused`` (default, `hcu.use_fused_rows`) fuses staging and compute into
    one loop over the nv valid entries (`worklist.fused_stage_compute` +
    the in-place writeback loop) on CPU, or runs the whole phase as the
    `ops.fused_row_update` megakernel on TPU (ij planes + i-vectors aliased
    in place, weight rows emitted for the WTA). fused=False keeps the
    three-phase stage/compute/writeback form — bitwise-identical, kept as
    the A/B reference (tests/test_worklist.py).

    ``layout`` (a `layout.BlockedLayout` or None): the CPU loops address the
    blocked planes directly through the layout accessors; the Pallas kernels
    run on the row-padded flat view when the layout is TPU-degenerate
    (Tc == 1 — a pure reshape) with the worklist's global row indices
    remapped onto the padded row pitch, and fall back to a canonical-flat
    round-trip otherwise.

    Exposed (not underscored) because `benchmarks/fig10_rowmerge.py`
    rebuilds the worklist tick around it.
    """
    lay = L.as_blocked(layout)
    kb = kernel or ops.default_backend()
    if lay is not None and kb in ("pallas", "pallas_interpret") \
            and not lay.tpu_degenerate:
        # off the degenerate point the kernels' flat BlockSpecs can't
        # address the tile store; round-trip through canonical flat
        hcus, w_rows, c = worklist_lazy_rows(
            L.load_hcus(hcus, lay), rows, t, p, kernel=kernel, fused=fused)
        return L.store_hcus(hcus, lay), w_rows, c
    c = _row_worklist_common(hcus, rows, t, p)
    hcus = c["hcus"]
    n, A = c["n"], c["A"]
    if kb in ("pallas", "pallas_interpret") and fused:
        # megakernel: one scalar-prefetch grid pass over SLOT-ordered
        # entries (g_row already carries the H*R sentinel on padding slots,
        # which the kernel skips) updates ij planes AND i-vectors in place
        # and emits the h-major weight rows directly
        W = n * A
        h_of = jnp.arange(W, dtype=jnp.int32) // A
        if lay is not None:
            # degenerate blocked planes == row-padded flat view (reshape);
            # remap worklist rows onto the padded pitch (sentinel included)
            # and pad the i-vectors to match — pad rows only ever receive
            # pad-cell writes, never feed a valid row's compute
            planes = tuple(lay.flat_view(f) for f in _ij_flats(hcus))
            ivin = tuple(lay.pad_ivec(v, n)
                         for v in (hcus.zi, hcus.ei, hcus.pi, hcus.ti))
            g_rows = lay.pad_row_index(c["g_row"], n)
        else:
            planes = _ij_flats(hcus)
            ivin = (hcus.zi, hcus.ei, hcus.pi, hcus.ti)
            g_rows = c["g_row"]
        flats, ivecs, w_flat = ops.fused_row_update(
            *planes, *ivin,
            rows=g_rows, now=t, counts=c["counts"].reshape(-1),
            zj=hcus.zj[h_of], p_i=c["zep_i"].p.reshape(-1),
            pj=hcus.pj[h_of],
            zi_new=c["zi_new"].reshape(-1), ei_new=c["zep_i"].e.reshape(-1),
            pi_new=c["zep_i"].p.reshape(-1),
            coeffs=H.coeffs_ij(p), eps=p.eps, backend=kb)
        if lay is not None:
            w_flat = w_flat[:, :p.cols]
            flats = tuple(lay.from_flat_view(f) for f in flats)
            ivecs = tuple(lay.unpad_ivec(v, n) for v in ivecs)
        hcus = _put_flats(hcus, flats)._replace(
            zi=ivecs[0], ei=ivecs[1], pi=ivecs[2], ti=ivecs[3])
        w_rows = w_flat.reshape(n, A, p.cols)
    elif kb in ("pallas", "pallas_interpret"):
        # scalar-prefetch Pallas kernel: grid over worklist entries, planes
        # aliased in place (interpret mode on CPU)
        order = c["order"]
        h_of = order // A
        # padding entries get the H*R sentinel explicitly (order pads with
        # 0, which aliases a real row); the kernel skips every entry at or
        # past nv, so they can never clobber a touched row
        W = order.shape[0]
        if lay is not None:
            planes = tuple(lay.flat_view(f) for f in _ij_flats(hcus))
            g_map = lay.pad_row_index(c["g_row"], n)
            sent = n * lay.padded_rows
        else:
            planes = _ij_flats(hcus)
            g_map = c["g_row"]
            sent = n * p.rows
        rows_k = jnp.where(jnp.arange(W) < c["nv"], g_map[order], sent)
        flats = ops.worklist_row_update(
            *planes, rows=rows_k, nv=c["nv"], now=t,
            counts=c["counts"].reshape(-1)[order],
            zj=hcus.zj[h_of], p_i=c["zep_i"].p.reshape(-1)[order],
            pj=hcus.pj[h_of], coeffs=H.coeffs_ij(p), eps=p.eps, backend=kb)
        w_view = flats[3]
        if lay is not None:
            flats = tuple(lay.from_flat_view(f) for f in flats)
        hcus = _put_flats(hcus, flats)
        # i-vector writeback: the O(touched) scatter forms on the flat
        # vectors (padding rows routed to the H*R sentinel -> dropped)
        g_put = jnp.where(
            c["rows_u"] < p.rows,
            jnp.arange(n, dtype=jnp.int32)[:, None] * p.rows + c["rows_u"],
            n * p.rows).reshape(-1)
        put = lambda v, val: v.at[g_put].set(val.reshape(-1), mode="drop")
        hcus = hcus._replace(
            zi=put(hcus.zi, c["zi_new"]), ei=put(hcus.ei, c["zep_i"].e),
            pi=put(hcus.pi, c["zep_i"].p),
            ti=put(hcus.ti, jnp.full(c["rows_u"].shape, t, hcus.ti.dtype)))
        w_g = w_view[jnp.minimum(g_map, sent - 1)]                # (W, C)
        if lay is not None:
            w_g = w_g[:, :p.cols]
        w_rows = jnp.where((c["g_row"] < n * p.rows)[:, None], w_g, 0.0) \
            .reshape(n, A, p.cols)
    elif fused:
        # fused stage+compute loop: per valid entry, read the (1, C) row
        # blocks and run the SAME cell formulas the vmapped compute runs
        # (ops.row_update "ref" dispatch at (1, C) — bitwise-identical to
        # the (H, A, C) fusion, pinned by the head fixtures) in the same
        # iteration — compute on nv entries instead of every staged slot.
        # The writeback stays the separate in-place write_rows loop: a loop
        # that reads AND writes the same carried plane forces a full-plane
        # copy per iteration on XLA:CPU (docs/NUMERICS.md).
        counts_f = c["counts"].reshape(-1)
        pi_f = c["zep_i"].p.reshape(-1)
        zj_all, pj_all = hcus.zj, hcus.pj
        Cc = p.cols

        def row_math(slot, z, e, pp, tt):
            h = slot // A
            one = lambda v: jax.lax.dynamic_slice(v, (slot,), (1,))
            vec = lambda v: jax.lax.dynamic_slice(
                v, (h, 0), (1, Cc)).reshape(Cc)
            z1, e1, p1, w1, _ = ops.row_update(
                z, e, pp, tt, t, one(counts_f), vec(zj_all), one(pi_f),
                vec(pj_all), H.coeffs_ij(p), p.eps, backend=kernel)
            return z1, e1, p1, w1

        flats = _ij_flats(hcus)
        ivecs = (hcus.zi, hcus.ei, hcus.pi, hcus.ti)
        vals = WL.fused_stage_compute(
            (flats[0], flats[1], flats[2], flats[4]),
            c["g_row"], c["order"], c["nv"], row_math, layout=lay)
        flats, ivecs = WL.write_rows(flats, ivecs, c["g_row"], c["order"],
                                     c["nv"], vals, c["iv_vals"], t,
                                     layout=lay)
        hcus = _put_flats(hcus, flats)._replace(
            zi=ivecs[0], ei=ivecs[1], pi=ivecs[2], ti=ivecs[3])
        w_rows = vals[3].reshape(n, A, p.cols)
    else:
        flats = _ij_flats(hcus)
        ivecs = (hcus.zi, hcus.ei, hcus.pi, hcus.ti)
        bufs = WL.read_rows((flats[0], flats[1], flats[2], flats[4]),
                            c["g_row"], c["order"], c["nv"], layout=lay)
        # the per-HCU path's exact vmapped compute graph, fed from the
        # staged buffers (bitwise-identical values; padding slots read
        # zeros, their outputs are dropped / zero-count drive terms)
        sh = lambda b: b.reshape(n, A, p.cols)
        z1, e1, p1, w1, _ = jax.vmap(
            lambda z, e, pp, tt, cnt, zj, pi, pj: H.ops.row_update(
                z, e, pp, tt, t, cnt, zj, pi, pj, H.coeffs_ij(p), p.eps,
                backend=kernel)
        )(sh(bufs[0]), sh(bufs[1]), sh(bufs[2]), sh(bufs[3]),
          c["counts"], hcus.zj, c["zep_i"].p, hcus.pj)
        w_rows = w1
        vals = tuple(v.reshape(n * A, p.cols) for v in (z1, e1, p1, w1))
        flats, ivecs = WL.write_rows(flats, ivecs, c["g_row"], c["order"],
                                     c["nv"], vals, c["iv_vals"], t,
                                     layout=lay)
        hcus = _put_flats(hcus, flats)
        hcus = hcus._replace(zi=ivecs[0], ei=ivecs[1], pi=ivecs[2],
                             ti=ivecs[3])
    return hcus, w_rows, c


def worklist_merged_rows(hcus: H.HCUState, jring, rows, t, p: BCPNNParams,
                         fused: bool = True, layout=None):
    """Merged worklist row phase (piecewise ring integration) on canonical
    flat planes. Returns (hcus', w_rows, common).

    ``fused`` is accepted for driver-API symmetry with the lazy phase but is
    DELIBERATELY inert here: the merged row phase always runs the
    three-phase stage/compute/writeback form. The fused single-pass form was
    built and A/B-measured for this path too, and it diverges from the
    vmapped compute at 1 ulp in Zij: `merged_row_math`'s ring-integration
    island is large enough that XLA:CPU's fusion emitter contracts the tail
    ``z*ez + dz`` into an FMA in the big vmapped compilation, and NO
    loop-embedded compilation of the same sealed island — per-entry (1, C)
    or per-HCU (A, C) blocks alike — reproduces that contraction. Since the
    head fixtures pin the vmapped semantics bit-for-bit, merged keeps the
    staged compute. Full story: docs/NUMERICS.md (the lazy island is small
    enough to compile identically in both contexts, which is why
    `worklist_lazy_rows` CAN fuse)."""
    from repro.core import merged as M
    del fused
    lay = L.as_blocked(layout)
    c = _row_worklist_common(hcus, rows, t, p)
    hcus = c["hcus"]
    n, A = c["n"], c["A"]
    flats = _ij_flats(hcus)
    ivecs = (hcus.zi, hcus.ei, hcus.pi, hcus.ti)
    bufs = WL.read_rows((flats[0], flats[1], flats[2], flats[4]),
                        c["g_row"], c["order"], c["nv"], layout=lay)
    # vmapped merged_row_math: the exact compute graph of the per-HCU path
    sh = lambda b: b.reshape(n, A, p.cols)
    z1, e1, p1, w1 = jax.vmap(
        lambda z, e, pp, tt, g, zi, ti, cnt, zj, pi, pj: M.merged_row_math(
            z, e, pp, tt, g, zi, ti, cnt, zj, pi, pj, t, p)
    )(sh(bufs[0]), sh(bufs[1]), sh(bufs[2]), sh(bufs[3]), jring,
      c["zi_g"], c["ti_g"], c["counts"], hcus.zj, c["zep_i"].p, hcus.pj)
    w_rows = w1
    vals = tuple(v.reshape(n * A, p.cols) for v in (z1, e1, p1, w1))
    flats, ivecs = WL.write_rows(flats, ivecs, c["g_row"], c["order"],
                                 c["nv"], vals, c["iv_vals"], t, layout=lay)
    hcus = _put_flats(hcus, flats)
    hcus = hcus._replace(zi=ivecs[0], ei=ivecs[1], pi=ivecs[2], ti=ivecs[3])
    return hcus, w_rows, c


def _merged_worklist_update(hcus: H.HCUState, jring, rows, t, keys,
                            p: BCPNNParams, fused: bool = True, layout=None):
    """Worklist twin of `jax.vmap(merged.hcu_tick_merged)`: merged row
    updates (piecewise ring integration; `fused` threads through but the
    merged row phase stays three-phase — see `worklist_merged_rows`), WTA,
    overflow column flush, same-tick cell patch, ring push and Zj bump — all
    row-plane traffic through the in-place flat-plane loops.
    Bitwise-identical trajectories to the vmapped path
    (tests/test_worklist.py). Returns (hcus', jring', fired)."""
    from repro.core import merged as M
    n = rows.shape[0]
    R = p.rows
    lay = L.as_blocked(layout)
    hcus, w_rows, c = worklist_merged_rows(hcus, jring, rows, t, p,
                                           fused=fused, layout=lay)
    hcus, fired = _wta(hcus, w_rows, c["counts"], t, keys, p)

    active = fired >= 0
    safe_j = jnp.maximum(fired, 0)
    overflow = active & (jring[jnp.arange(n), safe_j, 0] != M.RING_EMPTY)

    # overflow path: amortized classic column flush (fire applied, no push).
    # Kept on the per-HCU vmapped code verbatim — run through a zero-copy
    # batched view — rather than a worklist twin: XLA:CPU's
    # libm-vs-vectorized transcendental codegen is sensitive to the
    # surrounding program, so only the *same code at the same spot*
    # guarantees bitwise identity with the vmap path. This keeps the flush's
    # O(H*R) column gathers/puts on every merged tick (not just overflow
    # ticks) — a deliberate trade: cond-gating or worklist-rewriting it
    # would change its fusion context and break the 1-ulp identity, and the
    # lazy path (the perf-gated one) has no flush at all.
    hb = jax.vmap(lambda s, g, j, ov: M.column_flush_merged(
        s, g, j, t, ov, p))(L.batched_state(L.load_hcus(hcus, lay), n),
                            jring, safe_j, overflow)
    hcus = L.store_hcus(L.flat_state(hb), lay)
    jring = jax.vmap(
        lambda g, sj, ov: g.at[sj].set(
            jnp.where(ov, jnp.full((M.RING_DEPTH,), M.RING_EMPTY, jnp.int32),
                      g[sj]))
    )(jring, safe_j, overflow)

    # normal path: defer via ring; patch only this tick's touched rows
    pa_idx, n_patch = WL.compact_mask(active & ~overflow)
    zf = WL.patch_cells(hcus.zij, pa_idx, n_patch, c["rows_u"],
                        c["zi_new"], fired, R, layout=lay)
    hcus = hcus._replace(zij=zf)
    jring = jax.vmap(lambda g, j: M.push_ring(g, j, t))(
        jring, jnp.where(overflow, -1, fired))
    zj = jax.vmap(
        lambda z, sj, a: z.at[sj].add(jnp.where(a, 1.0, 0.0))
    )(hcus.zj, safe_j, active)
    return hcus._replace(zj=zj), jring, fired


# ---------------------------------------------------------------------------
# the TickBackend protocol and its two implementations
# ---------------------------------------------------------------------------

class TickBackend(Protocol):
    """A plane-update strategy pluggable into `tick`.

    Backends are hashable value objects (NamedTuples) so the jit drivers can
    treat them as static arguments. `carry_in`/`carry_out` convert between
    the canonical flat storage layout and whatever layout the backend wants
    threaded through a compiled region (jit call or scan carry); both must
    be zero-copy value-preserving views. `plane_update` consumes the
    carry-layout state and performs the row / WTA / column phases of one
    tick, returning (state', fired, h_idx, j_idx, n_dropped).

    `plane_update_split` is the same tick with the column phase DEFERRED:
    it returns (state', fired, h_idx, j_idx, n_dropped, col) where `col` is
    an hcus -> hcus closure holding the (already cond-gated) column pass, or
    None when the mode cannot split (eager / merged run everything up
    front). The sharded driver uses the split form to issue the spike
    all_to_all between WTA and columns, so the collective's latency hides
    behind the column plane traffic (`tick`'s split-route path); applying
    `col` immediately is bitwise `plane_update`."""

    def carry_in(self, state, p: BCPNNParams): ...

    def carry_out(self, state, p: BCPNNParams): ...

    def plane_update(self, state, rows, t, keys, p: BCPNNParams, cap: int,
                     cond_columns: bool): ...

    def plane_update_split(self, state, rows, t, keys, p: BCPNNParams,
                           cap: int, cond_columns: bool): ...


class DenseBackend(NamedTuple):
    """Per-HCU vmapped plane updates on the batched (H, R, C) view.

    The right regime below `hcu.DENSE_CELLS_MAX` cells per HCU, where the
    fused dense write forms beat scatters and whole-plane traffic is cheap.
    mode: "lazy" (timestamped row/column updates), "eager" (the dense golden
    reference) or "merged" (eBrainIII ring-deferred columns).
    kernel: ops backend override ("ref" | "pallas" | "pallas_interpret").
    layout: plane storage order (`layout.BlockedLayout` or None for flat).
    A blocked layout converts to/from canonical flat once per compiled
    region in `carry_in`/`carry_out` (pure data movement), so the per-tick
    dense graph stays exactly the historical batched one.
    """
    mode: str = "lazy"
    kernel: str | None = None
    layout: "L.BlockedLayout | None" = None

    def carry_in(self, state, p: BCPNNParams):
        n = state.delay_rows.shape[0]
        return state._replace(
            hcus=L.batched_state(L.load_hcus(state.hcus, self.layout), n))

    def carry_out(self, state, p: BCPNNParams):
        return state._replace(
            hcus=L.store_hcus(L.flat_state(state.hcus), self.layout))

    def plane_update(self, state, rows, t, keys, p: BCPNNParams, cap: int,
                     cond_columns: bool):
        state, fired, h_idx, j_idx, n_drop, col = self.plane_update_split(
            state, rows, t, keys, p, cap, cond_columns)
        if col is not None:
            state = state._replace(hcus=col(state.hcus))
        return state, fired, h_idx, j_idx, n_drop

    def plane_update_split(self, state, rows, t, keys, p: BCPNNParams,
                           cap: int, cond_columns: bool):
        n = state.delay_rows.shape[0]
        # each dense mode updates rows and runs the WTA in one vmapped call
        # (eager and merged also their columns): it reads as the row phase
        if self.mode == "eager":
            with jax.named_scope("rows"):
                hcus, fired = jax.vmap(
                    lambda s, r, k: reference.eager_tick(s, r, t, k, p)
                )(state.hcus, rows, keys)
            with jax.named_scope("wta"):
                h_idx, j_idx, n_drop = N.select_fired(fired, cap)
            return (state._replace(hcus=hcus), fired, h_idx, j_idx, n_drop,
                    None)
        if self.mode == "merged":
            from repro.core import merged as M
            with jax.named_scope("rows"):
                hcus, jring, fired = jax.vmap(
                    lambda s, g, r, k: M.hcu_tick_merged(s, g, r, t, k, p)
                )(state.hcus, state.jring, rows, keys)
            with jax.named_scope("wta"):
                h_idx, j_idx, n_drop = N.select_fired(fired, cap)
            return (state._replace(hcus=hcus, jring=jring), fired,
                    h_idx, j_idx, n_drop, None)
        with jax.named_scope("rows"):
            hcus, fired = jax.vmap(
                lambda s, r, k: H.hcu_tick_pre(s, r, t, k, p,
                                               backend=self.kernel)
            )(state.hcus, rows, keys)
        with jax.named_scope("wta"):
            h_idx, j_idx, n_drop = N.select_fired(fired, cap)
        col = lambda hc: column_updates_batched(hc, h_idx, j_idx, t, p,
                                                backend=self.kernel)
        if cond_columns:
            # the "power gating" of the lazy model: silent ticks skip the
            # column pass entirely
            colfn = lambda hc: jax.lax.cond(jnp.any(h_idx < n), col,
                                            lambda hc_: hc_, hc)
        else:
            colfn = col
        return (state._replace(hcus=hcus), fired, h_idx, j_idx, n_drop,
                _in_scope("cols", colfn))


class WorklistBackend(NamedTuple):
    """Network-global worklist plane updates on the canonical flat planes.

    The rodent/human-scale regime: one deduplicated (cap_total,) worklist of
    (hcu, row) entries per tick; all row-plane traffic through in-place
    dynamic-slice loops (CPU) or the scalar-prefetch Pallas kernel (TPU) —
    O(touched rows) per tick, the paper's §VI.D guarantee. The scan carry IS
    the stored flat layout: no per-tick reshapes.
    mode: "lazy" or "merged"; kernel as in DenseBackend.
    fused: fuse the lazy row phase's staging and compute into one
    valid-entries-only loop (`worklist.fused_stage_compute`; the
    `ops.fused_row_update` megakernel on TPU) instead of the three-phase
    stage/compute/writeback form — default on (`hcu.use_fused_rows`),
    bitwise-identical either way.
    fused_cols: the same fusion for the lazy column phase
    (`worklist.fused_col_stage_compute`; the `ops.fused_col_update`
    megakernel on TPU) — default on (`hcu.use_fused_cols`),
    bitwise-identical either way; inert in merged mode (the merged column
    flush keeps the shared `merged_col_math` island).
    layout: plane storage order (`layout.BlockedLayout` or None for flat).
    Unlike the dense backend, the worklist loops address the blocked tiles
    DIRECTLY through the layout accessors — this is where the Row-Merge
    column-locality win lives (a fired column touches ceil(R/xr) tile
    stripes instead of R strided cache lines).
    """
    mode: str = "lazy"
    kernel: str | None = None
    fused: bool = True
    fused_cols: bool = True
    layout: "L.BlockedLayout | None" = None

    def carry_in(self, state, p: BCPNNParams):
        return state

    def carry_out(self, state, p: BCPNNParams):
        return state

    def plane_update(self, state, rows, t, keys, p: BCPNNParams, cap: int,
                     cond_columns: bool):
        state, fired, h_idx, j_idx, n_drop, col = self.plane_update_split(
            state, rows, t, keys, p, cap, cond_columns)
        if col is not None:
            state = state._replace(hcus=col(state.hcus))
        return state, fired, h_idx, j_idx, n_drop

    def plane_update_split(self, state, rows, t, keys, p: BCPNNParams,
                           cap: int, cond_columns: bool):
        n = state.delay_rows.shape[0]
        if self.mode == "merged":
            # rows, WTA and the merged column flush are one call: it reads
            # as the row phase
            with jax.named_scope("rows"):
                hcus, jring, fired = _merged_worklist_update(
                    state.hcus, state.jring, rows, t, keys, p,
                    fused=self.fused, layout=self.layout)
            with jax.named_scope("wta"):
                h_idx, j_idx, n_drop = N.select_fired(fired, cap)
            return (state._replace(hcus=hcus, jring=jring), fired,
                    h_idx, j_idx, n_drop, None)
        with jax.named_scope("rows"):
            hcus, w_rows, c = worklist_lazy_rows(state.hcus, rows, t, p,
                                                 kernel=self.kernel,
                                                 fused=self.fused,
                                                 layout=self.layout)
        with jax.named_scope("wta"):
            hcus, fired = _wta(hcus, w_rows, c["counts"], t, keys, p)
            h_idx, j_idx, n_drop = N.select_fired(fired, cap)
        col = worklist_col_dispatch(self.kernel, self.fused_cols,
                                    h_idx, j_idx, t, p, n,
                                    layout=self.layout)
        if cond_columns:
            colfn = lambda hc: jax.lax.cond(jnp.any(h_idx < n), col,
                                            lambda hc_: hc_, hc)
        else:
            colfn = col
        return (state._replace(hcus=hcus), fired, h_idx, j_idx, n_drop,
                _in_scope("cols", colfn))


def select_backend(p: BCPNNParams, *, eager: bool = False,
                   merged: bool = False, worklist: bool | None = None,
                   kernel: str | None = None,
                   fused: bool | None = None,
                   fused_cols: bool | None = None,
                   layout=None) -> "TickBackend":
    """Map the historical mode flags onto a TickBackend.

    Keeps `hcu.use_worklist`'s size-guard semantics (R*C > DENSE_CELLS_MAX
    switches to the worklist engine) and the `worklist=` override; `fused=`
    likewise forces the worklist backend's single-pass row phase on/off
    (`hcu.use_fused_rows`) and `fused_cols=` its single-pass column phase
    (`hcu.use_fused_cols`) — both default on, both no-ops for the dense
    backends. The eager golden reference is dense by definition (it touches
    every cell anyway).

    ``layout`` selects the plane storage order (`layout.resolve_layout`
    spec: None/"flat" for canonical flat, "blocked"/"blocked_tpu"/a
    `BlockedLayout` for column-blocked tiles); it is normalized here so the
    backends — which are static jit arguments — only ever carry None or a
    concrete `BlockedLayout`.
    """
    layout = L.resolve_layout(layout, p)
    if eager:
        return DenseBackend(mode="eager", kernel=kernel, layout=layout)
    mode = "merged" if merged else "lazy"
    if H.use_worklist(p, worklist):
        return WorklistBackend(mode=mode, kernel=kernel,
                               fused=H.use_fused_rows(p, fused),
                               fused_cols=H.use_fused_cols(p, fused_cols),
                               layout=layout)
    return DenseBackend(mode=mode, kernel=kernel, layout=layout)


# ---------------------------------------------------------------------------
# the one tick body
# ---------------------------------------------------------------------------

def tick(state, conn, ext_rows, p: BCPNNParams, be: "TickBackend",
         cap_fire: int | None = None, *, gid_base=0, route=None,
         cond_columns: bool = True):
    """Advance the network one 1 ms tick (state in the backend's carry
    layout). THE single tick body: every driver — per-tick jit, scan chunk,
    sharded per-device — runs this exact function, which is what makes all
    trajectories bitwise-comparable.

      gid_base      — global id of local HCU 0 (sharded: dev * h_local), so
                      the RNG stream is invariant to device count;
      route         — spike routing hook route(state, dest_h, dest_r, delay,
                      valid, p, n) -> state'; defaults to the local
                      `network.enqueue_spikes`, sharded drivers pass the
                      pack + all_to_all exchange. A route exposing
                      `send`/`recv` (`distributed.SparseExchange`) is run
                      SPLIT: the collective is issued right after WTA and
                      its result consumed only after the column plane
                      update, so spike latency hides behind column traffic.
                      Neither phase reads what the other writes (the
                      exchange touches delay queues + drop counters, the
                      column pass touches the ij planes), so the split
                      trajectory is bitwise the sequential one;
      cond_columns  — gate the lazy column pass behind "anything fired?"
                      (the historical local-tick behavior; sharded ticks run
                      it unconditionally).
    Returns (state', fired) with fired[h] = MCU index or -1.
    """
    n = state.delay_rows.shape[0]
    cap = cap_fire or max(2, int(0.35 * n) + 1)
    split = route is not None and hasattr(route, "send")

    # named scopes only label the HLO ops (metadata op_name) for the device
    # trace: queue / rows / wta / cols partition the tick, and the backends
    # open rows, wta and cols where they make those calls
    with jax.named_scope("tick"):
        # 1. consume this tick's delay bucket and merge with external input
        with jax.named_scope("queue"):
            t = state.t + 1
            state, bucket = N.consume_bucket(state, t, p, n)
            rows = jnp.concatenate([bucket, ext_rows], axis=1)

        # 2. plane update (rows + WTA + columns), identical RNG in all
        # drivers
        with jax.named_scope("wta"):
            k_t = jax.random.fold_in(state.base_key, t)
            gids = gid_base + jnp.arange(n)
            keys = jax.vmap(lambda g: jax.random.fold_in(k_t, g))(gids)
        if split:
            # split-phase route: defer the column pass so the spike
            # collective can be issued between WTA and columns (overlap
            # window)
            state, fired, h_idx, j_idx, n_drop, col = be.plane_update_split(
                state, rows, t, keys, p, cap, cond_columns)
        else:
            state, fired, h_idx, j_idx, n_drop = be.plane_update(
                state, rows, t, keys, p, cap, cond_columns)
            col = None
        with jax.named_scope("wta"):
            state = state._replace(drops_fire=state.drops_fire + n_drop, t=t)

        # 3. fan out spikes from the fired batch into delay queues
        with jax.named_scope("queue"):
            safe_h = jnp.minimum(h_idx, n - 1)
            dest_h = conn.dest_hcu[safe_h, j_idx].reshape(-1)      # (K*F,)
            dest_r = conn.dest_row[safe_h, j_idx].reshape(-1)
            dly = conn.delay[safe_h, j_idx].reshape(-1)
            valid = jnp.repeat(h_idx < n, p.fanout)
            if split:
                # 3a. compact + issue the all_to_all
                state, inflight = route.send(state, dest_h, dest_r, dly,
                                             valid, p, n)
            else:
                state = (route or N.enqueue_spikes)(state, dest_h, dest_r,
                                                    dly, valid, p, n)
        if split:
            # 2b. columns run while the exchange is in flight; 3b. enqueue
            # the delivered spikes
            if col is not None:
                state = state._replace(hcus=col(state.hcus))
            with jax.named_scope("queue"):
                state = route.recv(state, inflight, p, n)
    return state, fired


# ---------------------------------------------------------------------------
# Simulator facade
# ---------------------------------------------------------------------------

class Simulator:
    """End-to-end facade over the TickEngine: init / run / run_sharded /
    save / load in a few lines, without hand-wiring `init_network` +
    `network_run` + `make_dist_run`.

        sim = Simulator(p, key=0)
        fired = sim.run(ext)                   # staged scan runtime
        sim.save("ckpt")                       # NetworkState checkpoint
        sim.load("ckpt")                       # incl. legacy-layout shim

    The held `state` is always in the canonical flat layout; `hcus()` gives
    the batched (H, R, C) view and `flushed()` a fully-current copy for
    inspection. Drivers donate `self.state` and the Simulator rebinds it, so
    never hold your own reference across a run.
    """

    def __init__(self, p: BCPNNParams, key=0, *, n_hcu: int | None = None,
                 merged: bool = False, eager: bool = False,
                 worklist: bool | None = None, kernel: str | None = None,
                 fused: bool | None = None, fused_cols: bool | None = None,
                 cap_fire: int | None = None, chunk: int = 128,
                 layout=None):
        self.p = p
        self.n_hcu = n_hcu or p.n_hcu
        self.merged, self.eager = merged, eager
        self.worklist, self.kernel, self.fused = worklist, kernel, fused
        self.fused_cols = fused_cols
        self.cap_fire, self.chunk = cap_fire, chunk
        # normalized once: None (canonical flat) or a concrete BlockedLayout
        # ("blocked" -> the CPU tile, "blocked_tpu" -> the (8, 128) tile)
        self.layout = L.resolve_layout(layout, p)
        self._dist_cache = None
        self._key = jax.random.PRNGKey(key) if isinstance(key, int) else key
        self.conn = N.make_connectivity(p, jax.random.fold_in(self._key, 1),
                                        n_hcu)
        self.state = N.init_network(p, self._key, n_hcu=n_hcu, merged=merged,
                                    layout=self.layout)

    # -- mode plumbing -------------------------------------------------------
    def _kw(self):
        return dict(eager=self.eager, merged=self.merged,
                    worklist=self.worklist, backend=self.kernel,
                    fused=self.fused, fused_cols=self.fused_cols,
                    cap_fire=self.cap_fire, layout=self.layout)

    @property
    def backend(self) -> "TickBackend":
        return select_backend(self.p, eager=self.eager, merged=self.merged,
                              worklist=self.worklist, kernel=self.kernel,
                              fused=self.fused, fused_cols=self.fused_cols,
                              layout=self.layout)

    def reset(self, key=None) -> "Simulator":
        """Re-init the network state (same connectivity unless key given)."""
        if key is not None:
            self._key = (jax.random.PRNGKey(key) if isinstance(key, int)
                         else key)
            self.conn = N.make_connectivity(
                self.p, jax.random.fold_in(self._key, 1), self.n_hcu)
        self.state = N.init_network(self.p, self._key, n_hcu=self.n_hcu,
                                    merged=self.merged, layout=self.layout)
        self._dist_cache = None      # fresh state is host-resident again
        return self

    # -- drivers -------------------------------------------------------------
    def tick(self, ext_rows):
        """One 1 ms tick (per-tick jit driver). Returns fired (H,)."""
        self.state, fired = N.network_tick(self.state, self.conn, ext_rows,
                                           self.p, **self._kw())
        return fired

    def run(self, ext, n_ticks: int | None = None, chunk: int | None = None):
        """Scan-compiled run. `ext` is a staged (T, H, A_ext) tensor, an
        iterable of (H, A_ext) frames, or a callable ext_fn(t) (then pass
        n_ticks). Returns fired history (T, H)."""
        if callable(ext) or not hasattr(ext, "ndim"):
            ext = N.stage_external(ext, n_ticks, t0=int(self.state.t))
        if n_ticks is not None:
            ext = ext[:n_ticks]
        self.state, fired = N.network_run(self.state, self.conn, ext, self.p,
                                          chunk=chunk or self.chunk,
                                          **self._kw())
        return fired

    def run_host(self, ext_fn, n_ticks: int):
        """Per-tick host-loop driver (the dispatch-bound baseline)."""
        self.state, fired = N.run(self.state, self.conn, ext_fn, n_ticks,
                                  self.p, **self._kw())
        return fired

    def run_sharded(self, ext, mesh=None, axis: str = "hcu", rc=None):
        """Scan-compiled sharded run over an HCU mesh (defaults to all local
        devices). Shards state/conn on first use; the held state stays
        sharded afterwards. Returns fired history (T, H)."""
        from repro.core import distributed as DD
        if self.merged:
            # the sharded runtime has no jring shard specs yet; silently
            # running the lazy backend would diverge from sim.run()
            raise NotImplementedError(
                "merged mode is not supported by the sharded runtime")
        if self.layout is not None:
            # the sharded drivers carry canonical flat planes; silently
            # dropping the blocked layout would diverge from sim.run()
            raise NotImplementedError(
                "blocked plane layouts are not supported by the sharded "
                "runtime (run with layout=None/'flat')")
        if mesh is None:
            mesh = jax.make_mesh((jax.device_count(),), (axis,))
        if rc is None:
            rc = DD.default_route_config(self.p, self.n_hcu // mesh.size,
                                         mesh.size)
        # cache the compiled sharded driver and the sharding step: rebuilding
        # make_dist_run per call would retrace the whole T-tick shard_map scan
        cache_key = (mesh, axis, rc)
        if getattr(self, "_dist_cache", None) is None \
                or self._dist_cache[0] != cache_key:
            self.state, self.conn = DD.shard_network(mesh, self.state,
                                                     self.conn, axis=axis)
            fn = DD.make_dist_run(mesh, self.p, rc, axis=axis,
                                  eager=self.eager, backend=self.kernel,
                                  worklist=self.worklist, fused=self.fused,
                                  fused_cols=self.fused_cols)
            self._dist_cache = (cache_key, fn)
        self.state, fired = self._dist_cache[1](self.state, self.conn,
                                                jnp.asarray(ext))
        return fired

    # -- inspection ----------------------------------------------------------
    def drops(self) -> dict:
        """Cumulative spike-drop counters: {'in': delay-queue overflows,
        'fire': fired-batch overflows, 'route': inter-device fabric
        overflows} — the paper's Fig 7 failure currency, surfaced so health
        monitors need not reach into NetworkState."""
        return N.drop_counters(self.state)

    def hcus(self) -> H.HCUState:
        """Batched (H, R, C) view of the held state (layout-aware: blocked
        planes are unpacked to canonical order first)."""
        return N.hcu_view(self.state, layout=self.layout)

    def flushed(self) -> H.HCUState:
        """Batched HCU state with every lazy trace brought current — the
        directly inspectable/comparable form (mode-aware: merged states
        flush their rings)."""
        now = self.state.t
        hb = self.hcus()
        if self.merged:
            from repro.core import merged as M
            return jax.vmap(lambda s, g: M.flush_merged(s, g, now, self.p))(
                hb, self.state.jring)
        return jax.vmap(lambda s: H.flush(s, now, self.p))(hb)

    # -- persistence ---------------------------------------------------------
    def save(self, ckpt_dir: str, step: int | None = None) -> str:
        """Checkpoint the held NetworkState (atomic, numpy container). The
        manifest records the plane layout (`layout.layout_tag`) so a later
        load under a different layout knows to convert."""
        from repro.checkpoint import save as ckpt_save
        return ckpt_save(ckpt_dir, int(self.state.t) if step is None
                         else step, self.state,
                         extra_meta={"layout": L.layout_tag(self.layout)})

    def load(self, ckpt_dir: str, step: int | None = None) -> "Simulator":
        """Restore the latest (or given) step into this Simulator.

        One-call migration, two shims:
        * legacy layout — checkpoints written by the pre-engine runtime
          stored the batched (H, R, C)/(H, R) layout; reshaped to canonical
          flat on load (`checkpoint.restore_network`);
        * plane layout — a checkpoint saved under one plane layout restores
          under any other: the manifest's layout tag (absent == flat) picks
          a template in the SAVED layout, and the loaded planes are
          converted to this Simulator's layout (`layout.convert_hcus` —
          pure data movement, bitwise).
        """
        from repro.checkpoint import latest_step, manifest, restore_network
        if step is None:
            step = latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
        meta = manifest(ckpt_dir, step) or {}
        saved = L.layout_from_tag(meta.get("layout", "flat"), self.p)
        if L.layout_tag(saved) == L.layout_tag(self.layout):
            self.state = restore_network(ckpt_dir, step, self.state)
        else:
            tmpl = self.state._replace(
                hcus=L.convert_hcus(self.state.hcus, self.layout, saved))
            st = restore_network(ckpt_dir, step, tmpl)
            self.state = st._replace(
                hcus=L.convert_hcus(st.hcus, saved, self.layout))
        self._dist_cache = None      # restored state is host-resident
        return self
