"""Backend-dispatching jit wrappers around the BCPNN update kernels.

Backends:
  "ref"               pure-jnp oracle (fast on CPU; default off-TPU)
  "pallas"            compiled Pallas kernel (TPU target)
  "pallas_interpret"  Pallas interpret mode (kernel-body semantics on CPU —
                      used by tests to validate the kernel against the oracle)

Selected via REPRO_KERNEL_BACKEND or the explicit ``backend=`` argument.
The wrappers own all shape plumbing (padding to (8,128) tiles, column
reshape), so callers deal only in logical (S, C) / (R,) shapes. That
padding, and the slice back to the logical shape, is traced under the
named scope `lane_pad` (`LANE_PAD`), so a device trace can tell its copies
from the kernels' own time.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core.traces import DecayCoeffs
from repro.kernels import bcpnn_ref, bcpnn_update

LANE_PAD = "lane_pad"


def default_backend() -> str:
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _pad2(x, s_to: int, c_to: int, fill=0):
    S, C = x.shape
    if S == s_to and C == c_to:
        return x
    return jnp.pad(x, ((0, s_to - S), (0, c_to - C)), constant_values=fill)


def _pad1(x, n_to: int, fill=0):
    n = x.shape[0]
    if n == n_to:
        return x
    return jnp.pad(x, (0, n_to - n), constant_values=fill)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def row_update(zij, eij, pij, tij, now, counts, zj, p_i, p_j,
               coeffs: DecayCoeffs, eps: float, backend: str | None = None,
               wij=None):
    """Fused lazy row update on an (S, C) block of gathered rows.

    Returns (zij', eij', pij', wij', tij'), logical shapes preserved.
    ``wij`` (optional) is the current weight plane block: it is never read,
    but passing it lets the Pallas path alias all five planes in place
    (callers on the hot path should always pass it).
    """
    backend = backend or default_backend()
    if backend == "ref":
        return bcpnn_ref.row_update_ref(zij, eij, pij, tij, now, counts, zj,
                                        p_i, p_j, coeffs, eps)
    S, C = zij.shape
    if wij is None:
        wij = jnp.zeros_like(zij)
    bs = min(bcpnn_update.DEFAULT_BLOCK_S, _round_up(S, 8))
    Sp, Cp = _round_up(S, bs), _round_up(C, bcpnn_update.DEFAULT_BLOCK_L)
    interp = backend == "pallas_interpret"
    with jax.named_scope(LANE_PAD):
        planes = (_pad2(zij, Sp, Cp), _pad2(eij, Sp, Cp), _pad2(pij, Sp, Cp),
                  _pad2(wij, Sp, Cp), _pad2(tij, Sp, Cp, fill=0))
        vecs = (_pad1(counts, Sp), _pad1(zj, Cp), _pad1(p_i, Sp),
                _pad1(p_j, Cp))
    out = bcpnn_update.row_update_kernel_call(
        *planes, now, *vecs, k=coeffs, eps=eps, bs=bs, interpret=interp)
    with jax.named_scope(LANE_PAD):
        return tuple(o[:S, :C] for o in out)


def worklist_row_update(zij, eij, pij, wij, tij, rows, nv, now, counts, zj,
                        p_i, pj, coeffs: DecayCoeffs, eps: float,
                        backend: str | None = None):
    """Worklist row update over the canonical flat (H*R, C) planes (Pallas
    backends only; the "ref" worklist path lives in `repro.core.worklist` as
    in-place dynamic-slice loops — this wrapper is the TPU/interpret
    dispatch). The flat planes are `NetworkState.hcus`'s STORED layout
    (`core.layout.flat_state`), so the engine passes them here directly.

    rows (W,): compacted-valid-first flat row indices (entries >= nv are
    skipped by the kernel whatever they hold); counts/p_i (W,); zj/pj
    (W, C) per-entry operands. Planes are lane-padded to a multiple of 128
    — the one remaining per-call copy, a no-op when C already is one (the
    TPU-degenerate `core.layout.BlockedLayout` view); the per-entry operands
    are padded to a multiple of 8 entries.
    """
    backend = backend or default_backend()
    HR, C = zij.shape
    W = rows.shape[0]
    Wp = _round_up(W, 8)
    Cp = _round_up(C, bcpnn_update.DEFAULT_BLOCK_L)
    interp = backend == "pallas_interpret"
    with jax.named_scope(LANE_PAD):
        planes = (_pad2(zij, HR, Cp), _pad2(eij, HR, Cp), _pad2(pij, HR, Cp),
                  _pad2(wij, HR, Cp), _pad2(tij, HR, Cp))
        rows_p = _pad1(jnp.clip(rows, 0, HR - 1), Wp)
        counts_p = _pad1(counts, Wp)
        entries = (_pad2(zj, Wp, Cp), _pad1(p_i, Wp), _pad2(pj, Wp, Cp))
    out = bcpnn_update.worklist_update_kernel_call(
        *planes, rows_p, nv, now, counts_p, *entries,
        k=coeffs, eps=eps, interpret=interp)
    with jax.named_scope(LANE_PAD):
        return tuple(o[:, :C] for o in out)


def _ivec_view(v, hrq: int):
    """(HR,) i-vector as the (HRq, 128) lane-row view the row megakernel
    patches in place (zero-padded to HRq * 128 cells)."""
    return _pad1(v, hrq * 128).reshape(hrq, 128)


def fused_row_update(zij, eij, pij, wij, tij, zi, ei, pi, ti, rows, now,
                     counts, zj, p_i, pj, zi_new, ei_new, pi_new,
                     coeffs: DecayCoeffs, eps: float,
                     backend: str | None = None):
    """Fused worklist row phase over the canonical flat planes — Pallas
    megakernel dispatch (the "ref" fused path is
    `worklist.fused_stage_compute` + `worklist.write_rows`;
    this wrapper is the TPU/interpret half of `engine.worklist_lazy_rows`'
    fused branch).

    One kernel launch completes the whole row phase: the five (H*R, C) ij
    planes AND the four (H*R,) i-vectors are rewritten in place (aliased),
    and the per-entry recomputed weight rows come back as a (W, C) buffer
    for the WTA drive — replacing the old three-op tail (worklist kernel +
    four i-vector scatters + a Wij re-gather).

    rows (W,): SLOT-ordered flat row indices, one per worklist slot, with
    the H*R sentinel on padding/duplicate slots (no compaction: the grid is
    W steps either way, and slot order is what makes the weight-row output
    land h-major for free). counts/p_i/zi_new/ei_new/pi_new (W,);
    zj/pj (W, C) per-entry operands. Sentinel slots touch no plane and
    emit a zero weight row.
    Returns ((zij', eij', pij', wij', tij'), (zi', ei', pi', ti'), w_rows).
    """
    backend = backend or default_backend()
    HR, C = zij.shape
    W = rows.shape[0]
    Wp = _round_up(W, 8)
    Cp = _round_up(C, bcpnn_update.DEFAULT_BLOCK_L)
    HRq = -(-HR // 128)
    interp = backend == "pallas_interpret"
    rows_eff = jnp.where((rows >= 0) & (rows < HR), rows, HR)
    with jax.named_scope(LANE_PAD):
        planes = (_pad2(zij, HR, Cp), _pad2(eij, HR, Cp), _pad2(pij, HR, Cp),
                  _pad2(wij, HR, Cp), _pad2(tij, HR, Cp),
                  *(_ivec_view(v, HRq) for v in (zi, ei, pi, ti)))
        rows_p = _pad1(rows_eff, Wp, fill=HR)
        entries = (_pad1(counts, Wp), _pad2(zj, Wp, Cp), _pad1(p_i, Wp),
                   _pad2(pj, Wp, Cp), _pad1(zi_new, Wp), _pad1(ei_new, Wp),
                   _pad1(pi_new, Wp))
    out = bcpnn_update.fused_row_update_kernel_call(
        *planes, rows_p, now, *entries, k=coeffs, eps=eps, hr=HR,
        interpret=interp)
    with jax.named_scope(LANE_PAD):
        flats = tuple(o[:, :C] for o in out[:5])
        ivecs = tuple(o.reshape(-1)[:HR] for o in out[5:9])
        return flats, ivecs, out[9][:W, :C]


def fused_col_update(zij, eij, pij, wij, tij, h_idx, j_idx, now, zi_t, p_i,
                     pj_sc, coeffs: DecayCoeffs, eps: float, n_hcu: int,
                     rows: int, backend: str | None = None):
    """Fused worklist column phase over the canonical flat planes — Pallas
    megakernel dispatch (the "ref" fused path is
    `worklist.fused_col_stage_compute` + `worklist.write_cols`; this wrapper
    is the TPU/interpret half of `engine._column_worklist`'s fused branch).

    One kernel launch completes the whole column phase except the Zj bump:
    for each valid fired-batch entry the (rows, 1) column block at
    (h_idx*rows, j_idx) of the five (H*rows, C) ij planes is rewritten in
    place (aliased), Tij stamped to `now` in-kernel.

    h_idx/j_idx (K,): the compacted fired batch as produced by
    `network.select_fired` (padding entries carry h_idx == n_hcu and are
    rerouted onto the junk row-block appended by the alignment padding, so
    a padding grid step can never clobber — or stale-overwrite — a fired
    column). zi_t/p_i (K, rows): per-entry presynaptic traces at `now`
    (transposed here to column-major (rows, K), lane-padded to a multiple
    of 128, so K may exceed one lane tile: each grid step reads the tile
    holding its entry); pj_sc (K,): per-entry postsynaptic P.
    Returns the five updated (H*rows, C) planes.
    """
    backend = backend or default_backend()
    HR, C = zij.shape
    K = h_idx.shape[0]
    L = bcpnn_update.DEFAULT_BLOCK_L
    # lane-align C and add one junk ROW-BLOCK (bs rows) for padding
    # entries. The pad + unpad copies per call are the same accepted
    # per-call trade as the row megakernel's — storing the planes
    # pre-aligned is the next layout step if TPU profiles show the pad
    # dominating.
    Cp = _round_up(C, L)
    bs = next(b for b in (bcpnn_update.DEFAULT_BLOCK_S, 4, 2, 1)
              if rows % b == 0)
    HRp = HR + bs
    interp = backend == "pallas_interpret"
    valid = h_idx < n_hcu
    r_bs = rows // bs
    row_base = jnp.where(valid, jnp.clip(h_idx, 0, n_hcu - 1) * r_bs,
                         HR // bs)
    row_step = valid.astype(jnp.int32)
    j_eff = jnp.where(valid, jnp.clip(j_idx, 0, C - 1), 0)
    with jax.named_scope(LANE_PAD):
        planes = (_pad2(zij, HRp, Cp), _pad2(eij, HRp, Cp),
                  _pad2(pij, HRp, Cp), _pad2(wij, HRp, Cp),
                  _pad2(tij, HRp, Cp, fill=0))
    lane_tile, lane = j_eff // L, j_eff % L
    with jax.named_scope(LANE_PAD):
        kp = _round_up(K, L)
        presyn = (_pad2(zi_t.T, rows, kp), _pad2(p_i.T, rows, kp))
    out = bcpnn_update.fused_col_update_kernel_call(
        *planes, row_base, row_step, lane_tile, lane, now, *presyn,
        pj_sc, k=coeffs, eps=eps, r=rows, bs=bs, interpret=interp)
    with jax.named_scope(LANE_PAD):
        return tuple(o[:HR, :C] for o in out)


def col_update(z_col, e_col, p_col, t_col, now, zi_t, p_i, p_j_scalar,
               coeffs: DecayCoeffs, eps: float, backend: str | None = None,
               w_col=None):
    """Fused lazy column update on an (R,) column (paper: 100 row-sized chunks).

    All column args are (R,); returns (z', e', p', w', t') each (R,).
    ``w_col`` (optional) is aliased in place by the Pallas path (never read).
    """
    backend = backend or default_backend()
    if backend == "ref":
        return bcpnn_ref.col_update_ref(z_col, e_col, p_col, t_col, now,
                                        zi_t, p_i, p_j_scalar, coeffs, eps)
    (R,) = z_col.shape
    if w_col is None:
        w_col = jnp.zeros_like(z_col)
    L = bcpnn_update.DEFAULT_BLOCK_L
    bs = bcpnn_update.DEFAULT_BLOCK_S
    Rp = _round_up(R, L * bs)

    def shp(x, fill=0):
        return _pad1(x, Rp, fill).reshape(Rp // L, L)

    interp = backend == "pallas_interpret"
    with jax.named_scope(LANE_PAD):
        cols = (shp(z_col), shp(e_col), shp(p_col), shp(w_col), shp(t_col))
        presyn = (shp(zi_t), shp(p_i))
    out = bcpnn_update.col_update_kernel_call(
        *cols, now, *presyn, p_j_scalar, k=coeffs, eps=eps, bs=bs,
        interpret=interp)
    with jax.named_scope(LANE_PAD):
        return tuple(o.reshape(Rp)[:R] for o in out)
