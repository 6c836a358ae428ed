"""Hyper Column Unit (HCU) state and the three BCPNN update types.

Per the paper (§II.A.2) an HCU services three atomic sub-threads each 1 ms tick:
  * row updates     — one per incoming spike (lazy, touches one (C,) row)
  * column update   — on output spike (lazy, touches one (R,) column)
  * periodic update — support integration + soft winner-take-all

State is structure-of-arrays (TPU-friendly planes) instead of the ASIC's
192-bit AoS cells; the field set is identical: Zij, Eij, Pij, Wij, Tij.
The j-vector is always kept current (decayed every tick) — it is the paper's
"stored locally in SRAM, excluded from synaptic bandwidth" structure. The
i-vector and the ij-matrix are lazy (timestamped).

All functions are pure and per-HCU; `repro.core.network` vmaps them over the
local HCU batch and shard_maps across devices.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.params import BCPNNParams
from repro.core.traces import ZEP, bias, decay_zep, make_coeffs
from repro.kernels import ops

# Below this many cells the scatter-free write paths (fused where / one-hot
# reduce) win on XLA CPU's fixed per-scatter cost; above it they would touch
# O(cells) per tick and break the lazy-traffic property (paper EQ2), so the
# O(touched) scatter forms are kept for rodent/human scales.
DENSE_CELLS_MAX = 1 << 16


def use_worklist(p: "BCPNNParams", override: bool | None = None) -> bool:
    """Size guard for the network-global worklist tick runtime.

    Above DENSE_CELLS_MAX cells per HCU the per-HCU vmapped
    gather->update->scatter forms make XLA copy the full scan-carried
    (H, R, C) planes per scatter, so rodent/human scales switch to the flat
    (H*R, C) worklist path (`repro.core.worklist`): in-place dynamic-slice
    loops (CPU) or the scalar-prefetch Pallas kernel (TPU) that touch only
    O(worklist) rows per tick. Below the threshold the toy sizes keep their
    current fused dense forms (same guard philosophy as DENSE_CELLS_MAX).
    ``override`` (the `worklist=` runtime argument) forces either path —
    tests use it to A/B the two on small sizes; both are bitwise-identical.
    """
    if override is not None:
        return bool(override)
    return p.rows * p.cols > DENSE_CELLS_MAX


def use_fused_rows(p: "BCPNNParams", override: bool | None = None) -> bool:
    """Guard for the fused (single-pass) worklist row phase.

    The fused row phase replaces the worklist backend's three-phase row
    update — staging gather loop, vmapped compute over every staged slot,
    writeback loop — with a fused stage+compute loop over the valid entries
    only (`worklist.fused_stage_compute` + the in-place writeback loop on
    CPU, `ops.fused_row_update`'s scalar-prefetch megakernel on TPU). It only
    ever applies inside `engine.WorklistBackend`, so `use_worklist`'s
    R*C > DENSE_CELLS_MAX size guard is its size guard too: the dense forms
    at small scale are untouched. ``override`` (the `fused=` runtime
    argument) forces either form — tests use it to A/B the fused pass
    against the split loops; both are bitwise-identical
    (tests/test_worklist.py, tests/test_engine_fixtures.py).
    """
    if override is not None:
        return bool(override)
    return True


def use_fused_cols(p: "BCPNNParams", override: bool | None = None) -> bool:
    """Guard for the fused (single-pass) worklist column phase.

    The column twin of `use_fused_rows`: replaces the worklist backend's
    three-phase lazy column update — `worklist.read_cols` staging loop,
    vmapped compute over every fired-batch slot, `worklist.write_cols`
    writeback — with a fused stage+compute loop over the n_fired valid
    entries only (`worklist.fused_col_stage_compute` + the in-place
    writeback loop on CPU, `ops.fused_col_update`'s scalar-prefetch
    megakernel on TPU). Applies only inside `engine.WorklistBackend`'s LAZY
    mode — the merged column flush keeps its shared `merged_col_math` island
    untouched — so `use_worklist`'s size guard is its size guard too.
    ``override`` (the `fused_cols=` runtime argument) forces either form —
    tests use it to A/B the fused pass against the staged loops; both are
    bitwise-identical (tests/test_worklist.py, tests/test_engine_fixtures.py).
    """
    if override is not None:
        return bool(override)
    return True


class HCUState(NamedTuple):
    # synaptic ij-matrix planes, (R, C)
    zij: jnp.ndarray
    eij: jnp.ndarray
    pij: jnp.ndarray
    wij: jnp.ndarray
    tij: jnp.ndarray      # int32 timestamps (ms)
    # presynaptic i-vector, (R,) each — lazy, timestamped
    zi: jnp.ndarray
    ei: jnp.ndarray
    pi: jnp.ndarray
    ti: jnp.ndarray       # int32
    # postsynaptic j-vector, (C,) each — always current
    zj: jnp.ndarray
    ej: jnp.ndarray
    pj: jnp.ndarray
    # support membrane, (C,)
    h: jnp.ndarray


def coeffs_ij(p: BCPNNParams):
    return make_coeffs(p.tau_z_ij, p.tau_e, p.tau_p)


def coeffs_i(p: BCPNNParams):
    return make_coeffs(p.tau_zi, p.tau_e, p.tau_p)


def coeffs_j(p: BCPNNParams):
    return make_coeffs(p.tau_zj, p.tau_e, p.tau_p)


def init_hcu_state(p: BCPNNParams, dtype=jnp.float32) -> HCUState:
    R, C = p.rows, p.cols
    z0 = jnp.zeros((R, C), dtype)
    pij0 = jnp.full((R, C), p.p_init * p.p_init, dtype)
    pi0 = jnp.full((R,), p.p_init, dtype)
    pj0 = jnp.full((C,), p.p_init, dtype)
    w0 = jnp.log((pij0 + p.eps**2) / ((pi0[:, None] + p.eps) * (pj0[None, :] + p.eps)))
    return HCUState(
        zij=z0, eij=jnp.zeros((R, C), dtype), pij=pij0, wij=w0.astype(dtype),
        tij=jnp.zeros((R, C), jnp.int32),
        zi=jnp.zeros((R,), dtype), ei=jnp.zeros((R,), dtype), pi=pi0,
        ti=jnp.zeros((R,), jnp.int32),
        zj=jnp.zeros((C,), dtype), ej=jnp.zeros((C,), dtype), pj=pj0,
        h=jnp.zeros((C,), dtype),
    )


def init_hcu_batch(p: BCPNNParams, n_hcu: int, dtype=jnp.float32) -> HCUState:
    """Network HCU batch in the CANONICAL FLAT layout (`repro.core.layout`):
    ij planes (H*R, C), i-vectors (H*R,), j-vectors/support (H, C).

    This is the layout `NetworkState.hcus` stores and the worklist tick
    engine consumes natively; per-HCU vmapped code gets the (H, R, C) view
    via `layout.batched_state`. The initial values are identical to tiling
    `init_hcu_state` n_hcu times (the init has no per-HCU variation).
    """
    s = init_hcu_state(p, dtype)
    tile2 = lambda x: jnp.tile(x, (n_hcu, 1))          # (R, C) -> (H*R, C)
    tile1 = lambda x: jnp.tile(x, n_hcu)               # (R,)   -> (H*R,)
    rep = lambda x: jnp.broadcast_to(x, (n_hcu,) + x.shape).copy()
    return HCUState(
        zij=tile2(s.zij), eij=tile2(s.eij), pij=tile2(s.pij),
        wij=tile2(s.wij), tij=tile2(s.tij),
        zi=tile1(s.zi), ei=tile1(s.ei), pi=tile1(s.pi), ti=tile1(s.ti),
        zj=rep(s.zj), ej=rep(s.ej), pj=rep(s.pj), h=rep(s.h),
    )


def dedup_rows(rows: jnp.ndarray, n_rows: int):
    """Aggregate duplicate row indices in a fixed-size spike slot array.

    rows: (A,) int32, padding slots == n_rows (out of range).
    Returns (unique_rows, counts): duplicates are merged into the first
    occurrence (count = multiplicity); non-first duplicates and padding become
    index n_rows with count 0, which gathers clipped (harmless) and scatters
    dropped (JAX OOB-scatter drop semantics).
    """
    # O(A log A) sort + segment bounds via cummax/cummin (replaces the old
    # all-pairs O(A^2) comparison matrix; scatter-free — each segment's
    # count is its end bound minus its start bound)
    A = rows.shape[0]
    a = jnp.sort(rows)
    idx = jnp.arange(A)
    brk = a[1:] != a[:-1]
    first = jnp.concatenate([jnp.array([True]), brk])
    last = jnp.concatenate([brk, jnp.array([True])])
    start = jax.lax.cummax(jnp.where(first, idx, 0))
    end = jax.lax.cummin(jnp.where(last, idx + 1, A), reverse=True)
    counts = (end - start).astype(jnp.float32)             # multiplicity per slot
    keep = first & (a < n_rows)
    rows_u = jnp.where(keep, a, n_rows)
    counts_u = jnp.where(keep, counts, 0.0)
    return rows_u, counts_u


def _decay_jvec(st: HCUState, p: BCPNNParams) -> HCUState:
    """Per-tick exact decay of the locally-held j-vector."""
    zep = decay_zep(ZEP(st.zj, st.ej, st.pj), p.dt_ms, coeffs_j(p))
    return st._replace(zj=zep.z, ej=zep.e, pj=zep.p)


def ivec_decay(zi_g, ei_g, pi_g, ti_g, now, p: BCPNNParams) -> ZEP:
    """Lazy decay of gathered i-vector traces to `now`, as a sealed fusion
    island (optimization barriers on inputs and outputs).

    Shared by the per-HCU vmap paths (`row_updates`,
    `engine.column_updates_batched`, merged) and the worklist paths: the
    seal keeps XLA from contracting the decay's mul+add chains into FMAs
    differently depending on the fused producer/consumer (plane gather vs
    staged buffer), which would diverge the two paths at the 1-ulp level.
    """
    zi_g, ei_g, pi_g, ti_g = jax.lax.optimization_barrier(
        (zi_g, ei_g, pi_g, ti_g))
    d_i = (now - ti_g).astype(zi_g.dtype)
    zep = decay_zep(ZEP(zi_g, ei_g, pi_g), d_i, coeffs_i(p))
    return ZEP(*jax.lax.optimization_barrier(tuple(zep)))


def row_updates(st: HCUState, rows: jnp.ndarray, now, p: BCPNNParams,
                backend: str | None = None):
    """Apply lazy row updates for incoming spikes.

    rows: (A,) int32 row indices, padding == p.rows. `now` int32 scalar (ms).
    Assumes the j-vector has already been decayed to `now` this tick.
    Returns (state', w_rows, counts, rows_u) — w_rows are the freshly updated
    Bayesian weight rows used by the periodic support computation.
    """
    R = p.rows
    rows_u, counts = dedup_rows(rows, R)
    safe = jnp.minimum(rows_u, R - 1)

    # --- i-vector lazy decay + spike increment for the touched rows --------
    zep_i = ivec_decay(st.zi[safe], st.ei[safe], st.pi[safe], st.ti[safe],
                       now, p)
    zi_new = zep_i.z + counts
    # --- ij-matrix row update (the fused kernel) ---------------------------
    g = lambda plane: plane[safe]            # (A, C) gathered rows
    z1, e1, p1, w1, t1 = ops.row_update(
        g(st.zij), g(st.eij), g(st.pij), g(st.tij), now,
        counts, st.zj, zep_i.p, st.pj, coeffs_ij(p), p.eps, backend=backend,
        wij=g(st.wij))

    st = write_rows(st, rows_u, now, p, z1, e1, p1, w1,
                    zi_new, zep_i.e, zep_i.p)
    return st, w1, counts, rows_u


def write_rows(st: HCUState, rows_u, now, p: BCPNNParams,
               zij, eij, pij, wij, zi, ei, pi) -> HCUState:
    """Write back a row update: (A, C) plane rows + (A,) i-vector entries at
    `rows_u` (padding == p.rows dropped), stamping Tij/ti to `now`.

    Two bitwise-identical branches (shared by lazy and merged row updates):
    below DENSE_CELLS_MAX the timestamp writes are fused wheres and the
    i-vector writes are fused one-hot reduces (exactly one hit per touched
    row, so the select is bit-exact) — XLA CPU scatters carry a high fixed
    per-op cost, and these were 5 of the 9 scatters on the tick hot path.
    At scale the O(touched)-traffic scatter forms are kept (paper EQ2).
    """
    R = p.rows
    scat = lambda plane, val: plane.at[rows_u].set(val, mode="drop")
    if R * p.cols <= DENSE_CELLS_MAX:
        onehot = (rows_u[:, None] == jnp.arange(R)[None, :])   # (A, R)
        touched = jnp.any(onehot, axis=0)
        ohf = onehot.astype(st.zi.dtype)
        # sum-of-products (not a matvec: a fused bcast-mul + reduce avoids
        # the tiny-matmul fixed cost on CPU); one nonzero per column
        blendv = lambda vec, val: jnp.where(
            touched, jnp.sum(val[:, None] * ohf, axis=0), vec)
        return st._replace(
            zij=scat(st.zij, zij), eij=scat(st.eij, eij),
            pij=scat(st.pij, pij), wij=scat(st.wij, wij),
            tij=jnp.where(touched[:, None], now, st.tij),
            zi=blendv(st.zi, zi), ei=blendv(st.ei, ei),
            pi=blendv(st.pi, pi),
            ti=jnp.where(touched, now, st.ti),
        )
    return st._replace(
        zij=scat(st.zij, zij), eij=scat(st.eij, eij), pij=scat(st.pij, pij),
        wij=scat(st.wij, wij),
        tij=scat(st.tij, jnp.full((rows_u.shape[0], p.cols), now, jnp.int32)),
        zi=st.zi.at[rows_u].set(zi, mode="drop"),
        ei=st.ei.at[rows_u].set(ei, mode="drop"),
        pi=st.pi.at[rows_u].set(pi, mode="drop"),
        ti=st.ti.at[rows_u].set(jnp.full(rows_u.shape, now, st.ti.dtype),
                                mode="drop"),
    )


def periodic_math(h_vec, pj, w_rows, counts, now, key, p: BCPNNParams):
    """Support integration + soft WTA on the raw (C,) leaves.

    The leaf-level form of `periodic_update`: the engine vmaps THIS over
    (h, pj) network planes so the flat canonical state never has to be
    regrouped into per-HCU NamedTuples just to run the WTA. Same ops, same
    RNG stream as the per-HCU wrapper.
    Returns (h', fired_j).
    """
    decay_m = jnp.exp(-p.dt_ms / p.tau_m)
    drive = jnp.sum(counts[:, None] * w_rows, axis=0)          # (C,)
    h = h_vec * decay_m + drive
    s = h + bias(pj, p.eps)
    # soft WTA: fire with prob out_rate*dt; winner ~ softmax(s / T)
    k_gate, k_win = jax.random.split(key)
    fire = jax.random.uniform(k_gate) < p.out_rate * p.dt_ms
    winner = jax.random.categorical(k_win, s / p.wta_temp)
    fired_j = jnp.where(fire, winner, -1).astype(jnp.int32)
    return h, fired_j


def periodic_update(st: HCUState, w_rows, counts, now, key, p: BCPNNParams):
    """Support integration + soft WTA (paper's 'periodic update', every ms).

    w_rows (A, C): freshly recomputed weight rows of this tick's spikes.
    Returns (state', fired_j) with fired_j == -1 when the HCU stays silent.
    """
    h, fired_j = periodic_math(st.h, st.pj, w_rows, counts, now, key, p)
    return st._replace(h=h), fired_j


def column_update(st: HCUState, j: jnp.ndarray, now, p: BCPNNParams,
                  backend: str | None = None) -> HCUState:
    """Apply the lazy column update for output spike at MCU column ``j``.

    Always computes (static shapes); masked to a no-op when j < 0. The paper
    splits the column into 100 row-sized chunks — here the kernel grid does.
    """
    active = j >= 0
    safe_j = jnp.maximum(j, 0)
    # presynaptic traces brought to `now` on the fly (no writeback: values
    # only, i-vector stays lazy — avoids a (R,) scatter per output spike)
    d_i = (now - st.ti).astype(st.zi.dtype)
    zep_i = decay_zep(ZEP(st.zi, st.ei, st.pi), d_i, coeffs_i(p))

    # gather/scatter along the last axis directly — the transpose round trip
    # (`plane.T.at[j].set(..).T`) materialized two full (R, C) copies per call
    g = lambda plane: jax.lax.dynamic_index_in_dim(plane, safe_j, 1, False)
    z1, e1, p1, w1, t1 = ops.col_update(
        g(st.zij), g(st.eij), g(st.pij), g(st.tij), now,
        zep_i.z, zep_i.p, st.pj[safe_j], coeffs_ij(p), p.eps, backend=backend,
        w_col=g(st.wij))

    def put(plane, val):
        col = jax.lax.dynamic_index_in_dim(plane, safe_j, 1, False)
        new = jnp.where(active, val, col)
        return plane.at[:, safe_j].set(new)

    st = st._replace(zij=put(st.zij, z1), eij=put(st.eij, e1),
                     pij=put(st.pij, p1), wij=put(st.wij, w1),
                     tij=put(st.tij, t1))
    # postsynaptic Z increment AFTER the column used pre-increment zj
    zj = st.zj.at[safe_j].add(jnp.where(active, 1.0, 0.0))
    return st._replace(zj=zj)


def hcu_tick_pre(st: HCUState, rows, now, key, p: BCPNNParams,
                 backend: str | None = None):
    """j-vector decay + row updates + periodic/WTA (vmap-able part of a tick).

    The column update is batched across HCUs at network level (only fired
    HCUs pay for it) — see engine.column_updates_batched.
    """
    st = _decay_jvec(st, p)
    st, w_rows, counts, _ = row_updates(st, rows, now, p, backend=backend)
    st, fired_j = periodic_update(st, w_rows, counts, now, key, p)
    return st, fired_j


def flush(st: HCUState, now, p: BCPNNParams) -> HCUState:
    """Bring every lazy trace current to `now` (checkpoint/inspection/tests).

    Equivalent to the paper's implicit end-of-run synchronization; after a
    flush, lazy and eager states are directly comparable plane-by-plane.
    """
    kij, ki = coeffs_ij(p), coeffs_i(p)
    d_ij = (now - st.tij).astype(st.zij.dtype)
    zep = decay_zep(ZEP(st.zij, st.eij, st.pij), d_ij, kij)
    d_i = (now - st.ti).astype(st.zi.dtype)
    zi = decay_zep(ZEP(st.zi, st.ei, st.pi), d_i, ki)
    w = jnp.log((zep.p + p.eps**2)
                / ((zi.p[:, None] + p.eps) * (st.pj[None, :] + p.eps)))
    return st._replace(
        zij=zep.z, eij=zep.e, pij=zep.p, wij=w,
        tij=jnp.full_like(st.tij, now),
        zi=zi.z, ei=zi.e, pi=zi.p, ti=jnp.full_like(st.ti, now))
