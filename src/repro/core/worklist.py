"""Worklist-driven in-place plane updates: the O(touched rows) tick core.

The paper's lazy model guarantees that per-tick synaptic traffic scales with
*spikes*, not synapses (§VI.D) — 36 row updates + 1 column update per HCU per
ms, never the whole (R, C) matrix. The scan-compiled runtime of PR 1 broke
that guarantee on the implementation side: every per-HCU vmapped
gather->update->scatter made XLA materialize a copy of the full scan-carried
`(H, R, C)` plane per scatter (XLA:CPU cannot alias a scatter whose operand
has other uses), so per-tick memory traffic was O(planes).

This module restores the paper's property with a network-global *worklist*
over the flat `(H*R, C)` planes (`repro.core.layout`) — which, since the
TickEngine refactor, are the CANONICAL STORED layout of `NetworkState.hcus`
(no per-tick flatten/unflatten: the scan carry is the flat layout itself,
consumed by `engine.WorklistBackend`):

  * one deduplicated `(cap_total,)` worklist of global row indices is built
    per tick (`build_worklist`), compacted valid-first exactly the way
    `cap_fire` compacts fired columns;
  * plane reads/writes happen ONLY through `lax.dynamic_slice` /
    `lax.dynamic_update_slice` inside `while_loop` bodies, the one access
    pattern XLA buffer assignment keeps in place on a scan carry (measured:
    a fancy gather next to a loop forces full-plane copies; ds/dus loops do
    not), and the loops early-exit at the valid-entry count — traffic and
    trip count are O(touched rows);
  * the trace math itself is NOT reimplemented here. Two loop forms exist:
    the FUSED form (`fused_stage_compute` + `write_rows`, the lazy default
    since PR 4) inlines the engine-supplied row math into the staging loop
    and computes ONLY the nv valid entries; the three-phase form
    (`read_rows` -> vmapped compute -> `write_rows`) stages touched rows
    into dense h-major buffers and runs the *identical* vmapped compute
    graph the per-HCU path runs over every slot. Both are bitwise-identical
    to the dense path where pinned — but NOT automatically: XLA:CPU codegen
    (exp lowering, FMA contraction) is context-sensitive at the 1-ulp
    level, which is why the merged mode keeps the three-phase form (see
    docs/NUMERICS.md for the measured FMA case). A further hard rule: a
    loop body must access each carried buffer in ONE direction only —
    read-only or write-only. A body that both dynamic-slices and
    dynamic-update-slices the same carried plane forces XLA:CPU to copy the
    full plane PER ITERATION (measured ~200x at rodent16), which is why the
    writeback is a separate loop rather than folded into the compute loop.

On TPU the same worklist drives the scalar-prefetch Pallas kernels
(`repro.kernels.bcpnn_update.fused_row_update_kernel_call` and
`worklist_update_kernel_call`), whose grids iterate worklist entries and
DMA only the touched plane rows, in place. `repro.core.engine` orchestrates both (size-guarded like
`hcu.DENSE_CELLS_MAX`, see `hcu.use_worklist`); this module holds the
backend-independent loop primitives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.layout import FlatLayout, as_blocked, global_row


def _lay(layout, n_rows: int | None = None):
    """Resolve the per-call PlaneLayout: the flat accessors (exactly the
    historical inline dynamic-slice expressions — flat graphs are unchanged
    by the seam) unless a BlockedLayout is passed."""
    return as_blocked(layout) or FlatLayout(rows=n_rows)


def build_worklist(rows_u: jnp.ndarray, n_rows: int):
    """Build the network-global worklist from per-HCU deduped row slots.

    rows_u: (H, A) per-HCU deduplicated row indices (padding == n_rows).
    Returns (g_row, order, nv):
      g_row (H*A,) int32 — global flat row index h*R + r per slot, h-major
                           slot order; padding slots == H*R (sentinel);
      order (H*A,) int32 — stable compaction permutation, valid slots first
                           (same idiom as network.select_fired);
      nv    ()     int32 — number of valid entries (= loop trip count).

    Rows are already unique network-wide: `dedup_rows` dedups within each
    HCU and rows of different HCUs map to disjoint global indices.
    """
    n_hcu, A = rows_u.shape
    valid = rows_u < n_rows
    g = jnp.where(valid,
                  global_row(jnp.arange(n_hcu, dtype=jnp.int32)[:, None],
                             rows_u, n_rows),
                  n_hcu * n_rows)
    order, nv = compact_mask(valid.reshape(-1))
    return g.reshape(-1).astype(jnp.int32), order, nv


def compact_mask(mask: jnp.ndarray):
    """Stable valid-first compaction of a boolean mask WITHOUT a sort.

    Returns (order, count): order (N,) int32 with order[e] = index of the
    (e+1)-th True entry for e < count (padding positions hold 0, never read
    by the early-exiting loops). True entry i lands at position
    cumsum(mask)[i] - 1 — a scatter, not an argsort: XLA:CPU's sort has
    shown compilation-context-sensitive miscompilation next to the in-place
    while-loop machinery, and a prefix sum is cheaper anyway.
    """
    N = mask.shape[0]
    pos = jnp.cumsum(mask) - 1
    order = jnp.zeros((N,), jnp.int32).at[
        jnp.where(mask, pos, N)].set(jnp.arange(N, dtype=jnp.int32),
                                     mode="drop")
    return order, jnp.sum(mask).astype(jnp.int32)


# ----------------------------- row worklist ---------------------------------

def read_rows(flats, g_row, order, nv, layout=None):
    """Stage worklist rows into dense h-major (H*A, C) buffers.

    flats: tuple of stored planes in `layout`'s order — flat (H*R, C) by
    default (read-only here). For each valid worklist entry
    (slot = order[e], e < nv), buffer position `slot` receives the logical
    plane row `g_row[slot]`; padding slots stay zero (their values feed only
    computations whose results are dropped or zero-masked). One
    dynamic_slice per plane per entry — no fancy gather, so the planes stay
    in-place-aliasable for the write loop.
    """
    lay = _lay(layout)
    C = lay.cols if as_blocked(layout) else flats[0].shape[1]
    cap_total = g_row.shape[0]
    bufs = tuple(jnp.zeros((cap_total, C), f.dtype) for f in flats)

    def body(s):
        e, bufs = s
        slot = order[e]
        r = g_row[slot]
        bufs = tuple(
            jax.lax.dynamic_update_slice(b, lay.read_row(f, r), (slot, 0))
            for b, f in zip(bufs, flats))
        return e + 1, bufs

    return jax.lax.while_loop(lambda s: s[0] < nv, body,
                              (jnp.asarray(0, jnp.int32), bufs))[1]


def write_rows(flats, ivecs, g_row, order, nv, vals, iv_vals, now,
               layout=None):
    """Write the row worklist back in place.

    flats:  (zij, eij, pij, wij, tij) stored planes (flat (H*R, C) default);
    ivecs:  (zi, ei, pi, ti) flat (H*R,) i-vectors (layout-independent);
    vals:   (z1, e1, p1, w1) h-major (H*A, C) value buffers;
    iv_vals:(zi', ei', pi') h-major (H*A,) i-vector values.
    Entry e < nv rewrites the logical plane row g_row[order[e]] from value
    slot order[e] and its i-vector cell; Tij/ti are stamped to `now`. Every
    write is a dynamic_update_slice on a while_loop carry — the in-place
    pattern — and only touched rows are visited (the per-HCU path's
    `mode="drop"` scatters wrote exactly this set).
    """
    lay = _lay(layout)
    C = lay.cols if as_blocked(layout) else flats[0].shape[1]

    def body(s):
        e, flats, ivecs = s
        slot = order[e]
        r = g_row[slot]
        row = lambda v: jax.lax.dynamic_slice(v, (slot, 0), (1, C))
        zf, ef, pf, wf, tf = flats
        vz, ve, vp, vw = vals
        zf = lay.write_row(zf, r, row(vz))
        ef = lay.write_row(ef, r, row(ve))
        pf = lay.write_row(pf, r, row(vp))
        wf = lay.write_row(wf, r, row(vw))
        tf = lay.stamp_row(tf, r, now)
        one = lambda v: jax.lax.dynamic_slice(v, (slot,), (1,))
        zv, ev, pv, tv = ivecs
        zv = jax.lax.dynamic_update_slice(zv, one(iv_vals[0]), (r,))
        ev = jax.lax.dynamic_update_slice(ev, one(iv_vals[1]), (r,))
        pv = jax.lax.dynamic_update_slice(pv, one(iv_vals[2]), (r,))
        tv = jax.lax.dynamic_update_slice(
            tv, jnp.full((1,), now, tv.dtype), (r,))
        return e + 1, (zf, ef, pf, wf, tf), (zv, ev, pv, tv)

    out = jax.lax.while_loop(lambda s: s[0] < nv, body,
                             (jnp.asarray(0, jnp.int32), flats, ivecs))
    return out[1], out[2]


def fused_stage_compute(flats, g_row, order, nv, row_math, layout=None):
    """Fused stage+compute pass: one loop that reads each touched row and
    runs the row math on it IN THE SAME ITERATION, writing the results to
    compact h-major value buffers.

    Replaces the first two of the three phases (`read_rows` staging +
    vmapped compute): the old form staged every slot and then computed the
    WHOLE (cap_total, C) buffer — padding slots included — where this loop
    computes exactly the nv valid entries. The writeback stays the separate
    `write_rows` loop: XLA:CPU keeps a while-loop carry in place only when
    each carried buffer is accessed in ONE direction per loop (read-only or
    write-only); a body that dynamic-slices and dynamic-update-slices the
    same carried plane forces a full-plane copy PER ITERATION (measured:
    ~200x slower at rodent16 — see docs/NUMERICS.md). Here the planes are
    read-only and the value buffers write-only, so everything stays in
    place.

      flats:    (zij, eij, pij, tij) flat (H*R, C) planes (read-only; note
                Wij is not needed — it is recomputed);
      row_math: row_math(slot, z, e, p, t) -> (z1, e1, p1, w1) on (1, C)
                blocks — MUST be the same cell formulas the vmapped compute
                runs (the engine passes closures over `bcpnn_ref` math;
                bitwise identity across the block-shape change is pinned by
                tests/test_worklist.py and the head fixtures);

    Returns (z1, e1, p1, w1) value buffers, each (cap_total, C) h-major,
    zeros at padding slots (their WTA drive terms are zero-count, and
    `write_rows` never reads them).
    """
    lay = _lay(layout)
    C = lay.cols if as_blocked(layout) else flats[0].shape[1]
    cap_total = g_row.shape[0]
    vals = tuple(jnp.zeros((cap_total, C), jnp.float32) for _ in range(4))
    dus = jax.lax.dynamic_update_slice

    def body(s):
        e, vals = s
        slot = order[e]
        r = g_row[slot]
        ds = lambda f: lay.read_row(f, r)
        z1, e1, p1, w1 = row_math(slot, ds(flats[0]), ds(flats[1]),
                                  ds(flats[2]), ds(flats[3]))
        vals = (dus(vals[0], z1, (slot, 0)), dus(vals[1], e1, (slot, 0)),
                dus(vals[2], p1, (slot, 0)), dus(vals[3], w1, (slot, 0)))
        return e + 1, vals

    return jax.lax.while_loop(lambda s: s[0] < nv, body,
                              (jnp.asarray(0, jnp.int32), vals))[1]


# ----------------------------- column worklist -------------------------------

def fused_col_stage_compute(flats, h_idx, j_idx, n_fired, n_rows: int,
                            col_math, layout=None):
    """Fused column stage+compute pass: one loop that reads each fired
    (R, 1) column block and runs the column math on it IN THE SAME
    ITERATION, writing the results to compact (K, R) value buffers.

    The column twin of `fused_stage_compute` (the PR 4 row recipe): it
    replaces the first two of the three column phases (`read_cols` staging +
    vmapped compute) — the old form staged every fired-batch slot and then
    computed the WHOLE (K, R) buffer, padding slots included, where this
    loop computes exactly the n_fired valid entries. The writeback stays the
    separate `write_cols` loop, per the one-direction loop rule
    (docs/NUMERICS.md): here the planes are read-only and the value buffers
    write-only, so everything stays in place.

      flats:    (zij, eij, pij, tij) flat (H*R, C) planes (read-only; Wij
                is not needed — it is recomputed);
      h_idx/j_idx: (K,) compacted fired batch (valid prefix of length
                n_fired, as produced by network.select_fired);
      col_math: col_math(e, z, ee, pp, tt) -> (z1, e1, p1, w1) on (R,)
                columns — MUST be the same cell formulas the vmapped
                compute runs (the engine passes closures over `bcpnn_ref`
                math; bitwise identity across the block-shape change is
                pinned by tests/test_worklist.py and the head fixtures).

    Returns (z1, e1, p1, w1) value buffers, each (K, R), zeros at padding
    slots (`write_cols` never reads them).
    """
    lay = _lay(layout, n_rows)
    K = h_idx.shape[0]
    vals = tuple(jnp.zeros((K, n_rows), jnp.float32) for _ in range(4))
    dus = jax.lax.dynamic_update_slice

    def body(s):
        e, vals = s
        ds = lambda f: lay.read_col(f, h_idx[e], j_idx[e])
        z1, e1, p1, w1 = col_math(e, ds(flats[0]), ds(flats[1]),
                                  ds(flats[2]), ds(flats[3]))
        vals = tuple(dus(v, val.reshape(1, n_rows), (e, 0))
                     for v, val in zip(vals, (z1, e1, p1, w1)))
        return e + 1, vals

    return jax.lax.while_loop(lambda s: s[0] < n_fired, body,
                              (jnp.asarray(0, jnp.int32), vals))[1]


def read_cols(flats, h_idx, j_idx, n_fired, n_rows: int, layout=None):
    """Stage fired columns into compact (K, R) buffers.

    h_idx/j_idx: (K,) compacted fired batch (valid prefix of length n_fired,
    as produced by network.select_fired). In the flat plane, HCU h's column
    j is the (R, 1) block at (h*R, j) — one dynamic_slice each; the blocked
    layout reads the Tr (xr, 1) tile fragments instead (`layout.read_col`).
    """
    lay = _lay(layout, n_rows)
    K = h_idx.shape[0]
    bufs = tuple(jnp.zeros((K, n_rows), f.dtype) for f in flats)

    def body(s):
        e, bufs = s
        bufs = tuple(
            jax.lax.dynamic_update_slice(
                b, lay.read_col(f, h_idx[e], j_idx[e]).reshape(1, n_rows),
                (e, 0))
            for b, f in zip(bufs, flats))
        return e + 1, bufs

    return jax.lax.while_loop(lambda s: s[0] < n_fired, body,
                              (jnp.asarray(0, jnp.int32), bufs))[1]


def write_cols(flats, h_idx, j_idx, n_fired, vals, now, n_rows: int,
               layout=None):
    """Write updated columns back in place ((R, 1) blocks; Tij stamped)."""
    lay = _lay(layout, n_rows)

    def body(s):
        e, flats = s
        h, j = h_idx[e], j_idx[e]
        col = lambda v: jax.lax.dynamic_slice(v, (e, 0), (1, n_rows))
        zf, ef, pf, wf, tf = flats
        zf = lay.write_col(zf, h, j, col(vals[0]))
        ef = lay.write_col(ef, h, j, col(vals[1]))
        pf = lay.write_col(pf, h, j, col(vals[2]))
        wf = lay.write_col(wf, h, j, col(vals[3]))
        tf = lay.stamp_col(tf, h, j, now)
        return e + 1, (zf, ef, pf, wf, tf)

    return jax.lax.while_loop(lambda s: s[0] < n_fired, body,
                              (jnp.asarray(0, jnp.int32), flats))[1]


def patch_cells(zf, pa_idx, n_patch, rows_u, ziv, fired, n_rows: int,
                layout=None):
    """Merged-mode same-tick patch: add Zi(now) to cell (row, fired_j) for
    every row touched THIS tick in every fired (non-overflow) HCU, in place.

    pa_idx: (H,) compacted HCU indices (valid prefix n_patch); rows_u (H, A)
    this tick's deduped rows; ziv (H, A) post-increment Zi values. Mirrors
    `merged.hcu_tick_merged`'s `zij.at[rows_u, safe_j].add(...)` — unique
    rows, so add order is immaterial; padding rows are skipped exactly where
    `mode="drop"` dropped them.
    """
    lay = _lay(layout, n_rows)
    A = rows_u.shape[1]

    def body(s):
        e, zf = s
        h = pa_idx[e]
        j = jnp.maximum(fired[h], 0)

        def inner(a, zf):
            r = rows_u[h, a]
            add = lambda zf: lay.add_cell(zf, h, r, j, ziv[h, a])
            return jax.lax.cond(r < n_rows, add, lambda z: z, zf)

        return e + 1, jax.lax.fori_loop(0, A, inner, zf)

    return jax.lax.while_loop(lambda s: s[0] < n_patch, body,
                              (jnp.asarray(0, jnp.int32), zf))[1]
