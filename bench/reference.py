"""Plain BCPNN reference for the benchmark's correctness check.

A straightforward `jax.numpy` implementation of one 1 ms BCPNN tick, written
from the model's equations (eBrainII sec. II.A; Tully, Hennig & Lansner 2014)
and importing nothing of the system under test:

  1. consume this tick's delay-queue bucket and merge it with the external
     spike rows;
  2. decay the postsynaptic (j) traces by one tick;
  3. for every distinct incoming row (with its multiplicity): bring the
     presynaptic (i) traces and the row's synaptic cells to `t` in closed
     form, add the Hebbian increment, and recompute the row's weights;
  4. integrate the support and run the soft winner-take-all;
  5. for every fired (HCU, column), bring the column's cells to `t` and add
     the presynaptic Z increment, then bump the fired column's Zj;
  6. fan the spikes out into the delay queues (capacity per bucket, overflow
     counted as a drop). The exchange between devices is not modelled: a
     spike that a route of the system drops shows as a state error.

The Z -> E -> P cascade between events has the exact solution used in steps
3 and 5 (`decay`). The synaptic state is kept as (H*R, Cp) planes, row
h*R + r for row r of HCU h, with a last-update time per cell; only the
touched rows and fired columns are rewritten each tick. Cp is C rounded up
to the chip's 128 lanes, whose surplus lanes are never read: a scatter into
a plane of any other width makes the TPU compiler copy the whole plane.

The reference is teacher-forced: it follows the fired history the system
produced, so one rounding-level near-tie in the WTA cannot make the two
trajectories part. That also lets it run as shares: share d of n replays
HCUs [d*H/n, (d+1)*H/n) alone, on a chip of its own. What a share needs of
the other HCUs is global and cheap, and each share computes it over all
HCUs from the fired history: the fired batch (each system device's first
`cap_fire` fired HCUs) and its fan-out, in fired-batch then fan-out order.
It keeps only its own destinations of that fan-out, its own fired columns,
its own delay queues and planes; one share is the whole network.

What it checks of the WTA is the gap by which each fired column's Gumbel-perturbed support lies below the best one (`jax.random
.categorical` is argmax(logits + Gumbel)), drawn from the same seeded key
chain: key(seed) -> fold_in(0x5EED) -> fold_in(t) -> fold_in(global HCU id)
-> split into (gate, winner) keys. The gate (fire with probability
out_rate * dt) depends on the keys alone, so a fired set that differs from
it is counted exactly.

`dtype` sets the precision of every floating-point operation; the state is
stored in float32 containers holding values rounded to `dtype`, so that the
bfloat16 control (the precision the comparison has to reject) computes in
bfloat16 without scattering 16-bit words.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

SEED_SALT = 0x5EED


class Model(NamedTuple):
    """The static sizes and constants of one configuration (hashable)."""
    n_hcu: int
    rows: int
    cols: int
    fanout: int
    tau_zi: float
    tau_zj: float
    tau_e: float
    tau_p: float
    tau_m: float
    dt_ms: float
    out_rate: float
    active_queue: int
    max_delay: int
    mean_delay: float
    eps: float
    p_init: float
    wta_temp: float
    n_dev: int = 1

    @property
    def tau_zij(self) -> float:
        return self.tau_zi * self.tau_zj / (self.tau_zi + self.tau_zj)

    @property
    def lanes(self) -> int:
        """Stored width of a synaptic plane: C rounded up to 128."""
        return -(-self.cols // 128) * 128

    @property
    def cap_fire(self) -> int:
        """Fired-batch capacity per device: 0.35 of its HCUs, plus one."""
        return max(2, int(0.35 * (self.n_hcu // self.n_dev)) + 1)


def model_from_params(params: dict, n_dev: int = 1) -> Model:
    return Model(n_dev=n_dev, **{f: params[f] for f in Model._fields
                                 if f != "n_dev"})


class State(NamedTuple):
    z: jnp.ndarray      # (H*R, Cp) synaptic Z, E, P at their last update
    e: jnp.ndarray
    p: jnp.ndarray
    t: jnp.ndarray      # (H*R, Cp) int32 last-update tick
    zi: jnp.ndarray     # (H*R,) presynaptic traces and their tick
    ei: jnp.ndarray
    pi: jnp.ndarray
    ti: jnp.ndarray
    zj: jnp.ndarray     # (H, C) postsynaptic traces, always current
    ej: jnp.ndarray
    pj: jnp.ndarray
    h: jnp.ndarray      # (H, C) support
    q_rows: jnp.ndarray   # (H, D, A) int32 delay queue, empty slot == R
    q_count: jnp.ndarray  # (H, D) int32
    now: jnp.ndarray      # () int32 ticks done
    drops: jnp.ndarray    # () int32 delay-queue overflows


def init_state(m: Model, dtype=jnp.float32, parts: int = 1) -> State:
    """One of `parts` equal shares of the network before its first tick
    (every HCU alike), its values rounded to `dtype`."""
    H, R, C, D, A = (m.n_hcu // parts, m.rows, m.cols, m.max_delay,
                     m.active_queue)
    Cp = m.lanes
    full = lambda s, v: jnp.full(s, jnp.asarray(v, dtype), jnp.float32)
    return State(
        z=full((H * R, Cp), 0), e=full((H * R, Cp), 0),
        p=full((H * R, Cp), m.p_init * m.p_init),
        t=jnp.zeros((H * R, Cp), jnp.int32),
        zi=full((H * R,), 0), ei=full((H * R,), 0),
        pi=full((H * R,), m.p_init),
        ti=jnp.zeros((H * R,), jnp.int32),
        zj=full((H, C), 0), ej=full((H, C), 0), pj=full((H, C), m.p_init),
        h=full((H, C), 0),
        q_rows=jnp.full((H, D, A), R, jnp.int32),
        q_count=jnp.zeros((H, D), jnp.int32),
        now=jnp.zeros((), jnp.int32), drops=jnp.zeros((), jnp.int32))


def decay(z, e, p, dt, tau_z, tau_e, tau_p):
    """Exact Z -> E -> P solution over a silent gap of `dt` ms:
    tau_z Z' = -Z, tau_e E' = Z - E, tau_p P' = E - P."""
    a = tau_z / (tau_z - tau_e)
    b = tau_e / (tau_e - tau_p)
    c = tau_z / (tau_z - tau_p)
    ez = jnp.exp(-dt / tau_z)
    ee = jnp.exp(-dt / tau_e)
    ep = jnp.exp(-dt / tau_p)
    e1 = e * ee + z * a * (ez - ee)
    p1 = p * ep + (e - z * a) * b * (ee - ep) + z * a * c * (ez - ep)
    return z * ez, e1, p1


def weight(p_ij, p_i, p_j, eps):
    """Bayesian weight log(P_ij / (P_i P_j)), regularised by eps."""
    return jnp.log((p_ij + eps * eps) / ((p_i + eps) * (p_j + eps)))


def wta_draws(base_key, t, first: int, n_hcu: int, cols: int):
    """(gate uniform (n,), Gumbel noise (n, C)) of tick t for the `n_hcu`
    HCUs from global id `first` on."""
    k_t = jax.random.fold_in(base_key, t)

    def one(g):
        k_gate, k_win = jax.random.split(jax.random.fold_in(k_t, g))
        return (jax.random.uniform(k_gate),
                jax.random.gumbel(k_win, (cols,), jnp.float32))

    return jax.vmap(one)(first + jnp.arange(n_hcu, dtype=jnp.int32))


def base_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed), SEED_SALT)


def _dedup(rows, R: int):
    """Distinct rows per HCU and their multiplicity: (H, S) sorted rows,
    counts (0 on repeats and padding) and a validity mask."""
    a = jnp.sort(rows, axis=1)
    first = jnp.concatenate(
        [jnp.ones_like(a[:, :1], bool), a[:, 1:] != a[:, :-1]], axis=1)
    counts = jnp.sum(a[:, :, None] == a[:, None, :], axis=2)
    valid = first & (a < R)
    return a, jnp.where(valid, counts, 0), valid


def _rank_within(key):
    """Each entry's rank among the entries of its key, in their order."""
    order = jnp.argsort(key, stable=True)
    ks = key[order]
    pos = jnp.arange(ks.shape[0])
    start = jax.lax.cummax(jnp.where(
        jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]]), pos, 0))
    return jnp.zeros_like(pos).at[order].set(pos - start)


def _tick(m: Model, dtype, st: State, conn, ext_t, forced_t, probes_t, bkey,
          part):
    """One tick of share `part` = (d, n), HCUs [o, o + nh) with nh = H/n
    and o = d*nh. `st`, `ext_t` and `probes_t` are the share's own; `conn`
    and the fired history `forced_t` (H,) are the whole network's."""
    H, R, C, D, A, F = (m.n_hcu, m.rows, m.cols, m.max_delay,
                        m.active_queue, m.fanout)
    d, n = part
    nh = H // n
    o = d * nh
    f = lambda x: jnp.asarray(x, dtype)
    lo = lambda x: x.astype(dtype)                 # stored f32 -> working
    f32 = lambda x: x.astype(jnp.float32)
    t = st.now + 1
    eps = f(m.eps)
    hh = jnp.arange(nh, dtype=jnp.int32)

    # 1. this tick's bucket, cleared, plus the external rows
    b = t % D
    rows = jnp.concatenate([st.q_rows[:, b, :], ext_t], axis=1)
    q_rows = st.q_rows.at[:, b, :].set(R)
    q_count = st.q_count.at[:, b].set(0)

    # 2. postsynaptic traces, one tick
    zj, ej, pj = decay(lo(st.zj), lo(st.ej), lo(st.pj), f(m.dt_ms),
                       m.tau_zj, m.tau_e, m.tau_p)

    # 3. row updates on the distinct incoming rows
    a, cnt, valid = _dedup(rows, R)
    n_rows = jnp.sum(valid)
    gr = hh[:, None] * R + jnp.where(valid, a, 0)          # (H, S) rows
    gw = jnp.where(valid, gr, nh * R)                # out of range: dropped
    cntf = cnt.astype(dtype)
    zi, ei, pi = decay(lo(st.zi[gr]), lo(st.ei[gr]), lo(st.pi[gr]),
                       (t - st.ti[gr]).astype(dtype), m.tau_zi,
                       m.tau_e, m.tau_p)
    zi = zi + cntf
    z, e, p = decay(lo(st.z[gr]), lo(st.e[gr]), lo(st.p[gr]),
                    (t - st.t[gr]).astype(dtype), m.tau_zij,
                    m.tau_e, m.tau_p)
    lanes = lambda v: jnp.pad(v, ((0, 0), (0, m.lanes - C)))[:, None, :]
    z = z + cntf[..., None] * lanes(zj)
    w = weight(p, pi[..., None], lanes(pj), eps)[..., :C]
    put = lambda plane, v: plane.at[gw].set(v.astype(plane.dtype),
                                            mode="drop")
    st = st._replace(
        z=put(st.z, z), e=put(st.e, e), p=put(st.p, p),
        t=put(st.t, jnp.broadcast_to(t, z.shape)),
        zi=put(st.zi, zi), ei=put(st.ei, ei), pi=put(st.pi, pi),
        ti=put(st.ti, jnp.broadcast_to(t, zi.shape)))

    # 4. support and soft WTA (gate and Gumbel draws from the seeded keys)
    drive = jnp.sum(cntf[..., None] * w, axis=1)
    h = lo(st.h) * f(math.exp(-m.dt_ms / m.tau_m)) + drive
    s = h + jnp.log(pj + eps)
    u, g = wta_draws(bkey, t, o, nh, C)
    # argmax(logits + Gumbel), as jax.random.categorical samples; the
    # noise is drawn in float32 and added in the reference's precision
    score = (s / m.wta_temp + g.astype(dtype)).astype(jnp.float32)
    gate = u < m.out_rate * m.dt_ms
    own = jnp.where(gate, jnp.argmax(score, axis=1), -1).astype(jnp.int32)
    fired = forced_t[o:o + nh]
    mismatch = jnp.sum(gate != (fired >= 0))
    best = jnp.max(score, axis=1)

    def gap(j):
        at = jnp.take_along_axis(score, jnp.maximum(j, 0)[:, None], 1)[:, 0]
        return jnp.max(jnp.where(j >= 0, best - at, 0.0))

    gaps = jnp.stack([gap(j) for j in probes_t]) if probes_t else \
        jnp.zeros((0,), jnp.float32)

    # the fired batch: the first cap_fire fired HCUs of each device, in
    # order, over the whole network (hk, jk) and this share's part of it
    # (hl, jl), local ids
    is_f = (forced_t >= 0).reshape(m.n_dev, -1)
    rank = jnp.cumsum(is_f, axis=1) - 1
    capped = (is_f & (rank < m.cap_fire)).reshape(-1)
    kmax = m.cap_fire * m.n_dev
    hk = jnp.nonzero(capped, size=kmax, fill_value=H)[0].astype(jnp.int32)
    ok = hk < H
    jk = jnp.where(ok, forced_t[jnp.minimum(hk, H - 1)], 0)
    hl = jnp.nonzero(capped[o:o + nh], size=kmax // n,
                     fill_value=nh)[0].astype(jnp.int32)
    okl = hl < nh
    jl = jnp.where(okl, fired[jnp.minimum(hl, nh - 1)], 0)
    n_fired = jnp.sum(okl)

    # 5. column updates of the share's fired columns
    hc = jnp.minimum(hl, nh - 1)
    gc = hc[:, None] * R + jnp.arange(R)[None, :]           # (K, R) rows
    zi_c, _, pi_c = decay(lo(st.zi[gc]), lo(st.ei[gc]), lo(st.pi[gc]),
                          (t - st.ti[gc]).astype(dtype), m.tau_zi,
                          m.tau_e, m.tau_p)
    ix = (gc, jl[:, None])
    z, e, p = decay(lo(st.z[ix]), lo(st.e[ix]), lo(st.p[ix]),
                    (t - st.t[ix]).astype(dtype), m.tau_zij, m.tau_e,
                    m.tau_p)
    z = z + zi_c
    iw = (jnp.where(okl[:, None], gc, nh * R), jl[:, None])
    putc = lambda plane, v: plane.at[iw].set(v.astype(plane.dtype),
                                             mode="drop")
    st = st._replace(z=putc(st.z, z), e=putc(st.e, e), p=putc(st.p, p),
                     t=putc(st.t, jnp.broadcast_to(t, z.shape)))
    zj = lo(f32(zj).at[hl, jl].add(1.0, mode="drop"))

    # 6. fan-out of the whole fired batch, in fired-batch then fan-out
    # order, into the delay queues of this share's HCUs
    hg = jnp.minimum(hk, H - 1)
    dh = conn[0][hg, jk].reshape(-1)
    dr = conn[1][hg, jk].reshape(-1)
    dl = conn[2][hg, jk].reshape(-1)
    mv = jnp.repeat(ok, F) & (dh >= o) & (dh < o + nh)
    dh = dh - o
    bucket = (t + dl) % D
    key = jnp.where(mv, dh * D + bucket, nh * D)
    slot = q_count[jnp.clip(dh, 0, nh - 1), bucket] + _rank_within(key)
    keep = mv & (slot < A)
    q_rows = q_rows.at[jnp.where(keep, dh, nh), bucket, slot].set(
        dr, mode="drop")
    arrivals = jnp.zeros((nh, D), jnp.int32).at[jnp.where(mv, dh, nh),
                                                bucket].add(1, mode="drop")
    new_count = jnp.minimum(q_count + arrivals, A)
    drops = st.drops + jnp.sum(q_count + arrivals - new_count)

    st = st._replace(zj=f32(zj), ej=f32(ej), pj=f32(pj), h=f32(h),
                     q_rows=q_rows,
                     q_count=new_count, now=t, drops=drops)
    stats = dict(n_rows=n_rows, n_fired=n_fired,
                 n_ext=jnp.sum(ext_t < R), mismatch=mismatch, gaps=gaps)
    return st, own, stats


@functools.partial(jax.jit,
                   static_argnames=("m", "dtype", "n_probes", "part"),
                   donate_argnums=(0,))
def replay_chunk(st: State, conn, ext, forced, probes, bkey, *, m: Model,
                 dtype, n_probes: int, part=(0, 1)):
    """Advance share `part` = (d, n) of the reference len(ext) ticks
    (`_tick`). ext (T, h, W) the share's external rows; forced (T, H) the
    whole fired history to follow; probes (P, T, h) the share's winners
    whose gap is read. Returns (state', the share's own winners (T, h),
    the share's per-tick stats)."""
    def body(s, xs):
        e, fo, pr = xs
        s, own, stats = _tick(m, dtype, s, conn, e, fo,
                              tuple(pr[i] for i in range(n_probes)), bkey,
                              part)
        return s, (own, stats)

    if n_probes:
        pr = jnp.moveaxis(probes, 0, 1)
    else:
        pr = jnp.zeros((ext.shape[0], 0, m.n_hcu // part[1]), jnp.int32)
    return jax.lax.scan(body, st, (ext, forced, pr))


# ---------------------------------------------------------------------------
# flushed comparison
# ---------------------------------------------------------------------------

FIELDS = ("z", "e", "p", "w", "zi", "ei", "pi", "zj", "ej", "pj", "h")


LEAVES = ("z", "e", "p", "t", "zi", "ei", "pi", "ti", "zj", "ej", "pj", "h")
HCU_LEAVES = ("zj", "ej", "pj", "h")        # one row per HCU, not R


def flush(lv: dict, now, m: Model) -> dict:
    """Every trace of a block of HCUs brought current at `now`, in float32:
    (hb*R, C) cells, (hb*R,) presynaptic and (hb, C) postsynaptic traces;
    weights recomputed from the flushed P."""
    f32 = jnp.float32
    g = {k: (v.astype(f32) if k not in ("t", "ti") else v)
         for k, v in lv.items()}
    z, e, p = decay(g["z"], g["e"], g["p"], (now - g["t"]).astype(f32),
                    m.tau_zij, m.tau_e, m.tau_p)
    zi, ei, pi = decay(g["zi"], g["ei"], g["pi"], (now - g["ti"]).astype(f32),
                       m.tau_zi, m.tau_e, m.tau_p)
    pj = jnp.repeat(g["pj"], m.rows, axis=0)                 # (hb*R, C)
    w = weight(p, pi[:, None], pj, m.eps)
    return dict(z=z, e=e, p=p, w=w, zi=zi, ei=ei, pi=pi, zj=g["zj"],
                ej=g["ej"], pj=g["pj"], h=g["h"])


@functools.partial(jax.jit, static_argnames=("m", "hb"))
def block_errors(sys: dict, ref: State, s0, r0, *, m: Model, hb: int):
    """(max |sys - ref|, max |ref|) per field of FIELDS over HCUs
    [s0, s0 + hb) of `sys` against [r0, r0 + hb) of `ref`, both flushed to
    the reference's time."""
    def cut(lv, at):
        """HCUs [at, at + hb) of flat leaves: rows at*R onward of the
        per-row leaves (planes cut to their C logical lanes), rows at
        onward of the per-HCU ones."""
        out = {}
        for k in LEAVES:
            per = 1 if k in HCU_LEAVES else m.rows
            v = jax.lax.dynamic_slice_in_dim(lv[k], at * per, hb * per, 0)
            out[k] = v[:, :m.cols] if k in ("z", "e", "p", "t") else v
        return out
    fs = flush(cut(sys, s0), ref.now, m)
    fr = flush(cut(ref._asdict(), r0), ref.now, m)
    d = jnp.stack([jnp.max(jnp.abs(fs[k] - fr[k])) for k in FIELDS])
    s = jnp.stack([jnp.max(jnp.abs(fr[k])) for k in FIELDS])
    return d, s


class Errors(NamedTuple):
    diff: dict
    scale: dict

    def per_field(self) -> dict:
        """Largest |system - reference| over the largest |reference|."""
        return {k: (self.diff[k] / self.scale[k] if self.scale[k] > 0
                    else self.diff[k]) for k in FIELDS}

    def worst(self):
        errs = self.per_field()
        k = max(errs, key=lambda f: (not math.isfinite(errs[f]), errs[f]))
        return k, errs[k]


def block_size(n: int, m: Model, budget_bytes: float = 2.5e8) -> int:
    """HCUs per comparison block: a power of two dividing `n`, a few hundred
    MB of flushed cells."""
    per_hcu = m.rows * max(m.cols, 128) * 4 * 10
    hb = 1
    while n % (2 * hb) == 0 and 2 * hb * per_hcu <= budget_bytes:
        hb *= 2
    return hb


def compare_states(m: Model, pieces) -> Errors:
    """Largest per-field error of the system's flushed state against the
    reference's. `pieces` yields (leaves, ref, first): the system's raw
    state of n HCUs, keyed as LEAVES and laid out as the reference's
    ((n*R, C), (n*R,) and (n, C)), the reference `State` of a share on the
    same device, and the index in that share of the first of the n HCUs."""
    d = np.zeros(len(FIELDS))
    s = np.zeros(len(FIELDS))
    for lv, ref, off in pieces:
        n = lv["zj"].shape[0]
        hb = block_size(n, m)
        outs = [block_errors(lv, ref, s0, off + s0, m=m, hb=hb)
                for s0 in range(0, n, hb)]
        for bd, bs in outs:
            bd = np.asarray(bd)
            d = np.where(np.isnan(bd) | (bd > d), bd, d)
            s = np.maximum(s, np.asarray(bs))
        del lv, ref, outs
    d = np.where(np.isnan(d), np.inf, d)
    return Errors(dict(zip(FIELDS, map(float, d))),
                  dict(zip(FIELDS, map(float, s))))
