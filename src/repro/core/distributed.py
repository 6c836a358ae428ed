"""Distributed BCPNN runtime: shard_map over HCUs + all_to_all spike exchange.

Paper mapping (§III.A, §VI.E): the eBrainII hierarchy is
    BCU (chip)  >  H-Cube (vault, P=4 HCUs)  >  HCU
with a pipelined binary-tree spike NoC inside a BCU. On a TPU pod the
hierarchy becomes
    pod  >  chip  >  local HCU batch (vmap)
and the spike NoC becomes the capacity-bounded sparse exchange
(`SparseExchange`): only fired (dest, row, delay) triples travel, packed one
int32 per spike into per-destination buckets sized by the Fig 7 Poisson
math (`default_route_config`), shipped with one `jax.lax.all_to_all` per
tick that the engine issues BEFORE the column plane phase and consumes
after it (latency overlap). Justified by the paper's own observation that
spike traffic is three orders of magnitude below synaptic bandwidth, so the
exchange sits far below the ICI roofline — measured against that bound by
`benchmarks/weak_scaling.py` (see `launch/roofline.py` collective term).

Because every HCU's state is self-contained ("no memory consistency
problem", §II.B), HCU shards are freely relocatable: elastic re-sharding and
failure recovery move whole HCUs between devices without any consistency
protocol (see repro.runtime.elastic).

Engine routing (PR 3)
---------------------
The per-device tick is `repro.core.engine.tick` — the SAME body every local
driver runs — with two shard-specific parameters:

  * ``gid_base = device_index * h_local`` so the per-HCU RNG stream folds
    GLOBAL HCU ids (trajectories invariant to device count, the elasticity
    contract);
  * ``route`` = the pack + all_to_all spike exchange defined here
    (`SparseExchange`), replacing the local direct enqueue; its split
    send/recv phases bracket the column plane update so the collective is
    in flight while columns run (`overlap=`, default on — bitwise the same
    trajectory as the sequential exchange).

This module therefore contains ONLY spike pack/exchange and shard plumbing —
no tick math. The sharded worklist path (rodent/human scales) comes for free
from `engine.WorklistBackend`: each device's scan carry is its local slice
of the canonical flat (H*R, C) planes, updated in place, O(touched rows) per
device per tick. The canonical flat layout shards exactly like the batched
one did (leading axis = h_local * R rows per device).

Two drivers, same per-device tick body:
  * make_dist_tick — one compiled sharded tick per call (host loop);
  * make_dist_run  — the scan-compiled twin of `network.network_run`: the
    whole pre-staged (T, H, A_ext) input runs in ONE compiled computation,
    all_to_all exchanges included — zero host round-trips per tick.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import engine as E
from repro.core import hcu as H
from repro.core import network as N
from repro.core.params import BCPNNParams


class RouteConfig(NamedTuple):
    """Static capacities of the spike exchange."""
    cap_fire: int        # max simultaneously fired HCUs per device per tick
    cap_route: int       # max messages per (src dev -> dst dev) pair per tick
    pack: bool = True    # pack each spike into one int32 (paper Fig 3 format)


def default_route_config(p: BCPNNParams, h_local: int,
                         n_dev: int | None = None) -> RouteConfig:
    """Dimension the exchange the way the paper dimensions its queues (§IV):
    Poisson-tail capacity with a months-scale drop budget, NOT worst case.

    Expected messages per (src dev -> dst dev) pair per tick:
        lam = out_rate * h_local * fanout / n_dev
    cap_route = smallest q with <= 1 expected drop/month at Poisson(lam)
    (overflows are counted in drops_fire — same budget discipline as the
    36-deep active queue).
    """
    from repro.core.queues import min_queue_for_monthly_drop_budget
    cap_fire = max(2, int(0.35 * h_local) + 1)
    if n_dev is None:
        return RouteConfig(cap_fire=cap_fire, cap_route=cap_fire * p.fanout)
    lam = max(p.out_rate * h_local * p.fanout / n_dev, 0.1)
    cap = min_queue_for_monthly_drop_budget(lam, budget=1.0, max_q=4096)
    cap = min(max(8, cap), cap_fire * p.fanout)
    return RouteConfig(cap_fire=cap_fire, cap_route=cap)


def lossless_route_config(p: BCPNNParams, h_local: int) -> RouteConfig:
    """Worst-case exchange dimensioning: capacity never binds (every device
    can fire all of its HCUs and route their entire fanout to one peer), so
    the exchange drops nothing and — because padded route slots carry no
    trajectory-relevant bits — the logical trajectory is bitwise invariant
    to the mesh shape. This is the elasticity contract `ElasticRunner`
    relies on when it remaps HCUs onto a smaller mesh (`RouteConfig` is
    re-derived per device count; see docs/RESILIENCE.md)."""
    return RouteConfig(cap_fire=max(h_local, 1),
                       cap_route=max(h_local, 1) * p.fanout)


def _pack_bits(p: BCPNNParams, h_local: int):
    loc_bits = max((h_local - 1).bit_length(), 1)
    row_bits = (p.rows).bit_length()              # rows value == invalid marker
    dly_bits = max((p.max_delay - 1).bit_length(), 1)
    assert loc_bits + row_bits + dly_bits + 1 <= 31, "spike word overflow"
    return loc_bits, row_bits, dly_bits


def pack_spikes(dest_loc, dest_row, delay, valid, p: BCPNNParams,
                h_local: int):
    """One spike == one int32 word (paper Fig 3: dest HCU | row | delay)."""
    lb, rb, db = _pack_bits(p, h_local)
    w = (dest_loc & ((1 << lb) - 1))
    w = (w << rb) | (dest_row & ((1 << rb) - 1))
    w = (w << db) | (delay & ((1 << db) - 1))
    w = (w << 1) | valid.astype(jnp.int32)
    return w


def unpack_spikes(w, p: BCPNNParams, h_local: int):
    lb, rb, db = _pack_bits(p, h_local)
    valid = (w & 1) == 1
    delay = (w >> 1) & ((1 << db) - 1)
    dest_row = (w >> (1 + db)) & ((1 << rb) - 1)
    dest_loc = (w >> (1 + db + rb)) & ((1 << lb) - 1)
    return dest_loc, dest_row, delay, valid


class SparseExchange:
    """Split-phase sparse spike routing: the distributed tick's spike NoC.

    Only fired work travels. `send` compacts the fired batch's fanout into
    per-destination capacity-bounded buckets of packed (dest, row, delay)
    spike words — sized by `default_route_config`'s Fig 7 Poisson-tail
    dimensioning, overflow counted into the `drops_route` Fig 7 class — and
    issues the all_to_all. `recv` unpacks the delivered words and enqueues
    them into the local delay queues.

    `engine.tick` drives the two phases around the column plane update
    (send -> columns -> recv), so the collective is in flight while the
    column plane traffic runs — the paper's bandwidth asymmetry (§I: spike
    traffic is ~3 orders of magnitude below synaptic traffic) makes the
    exchange the cheap side of that overlap. Neither phase reads what the
    other writes (exchange: delay queues + drop counters; columns: ij
    planes), so the overlapped trajectory is bitwise the sequential one —
    calling the object itself runs send+recv back-to-back (the pre-overlap
    exchange, kept as the `overlap=False` A/B escape hatch).
    """

    def __init__(self, p: BCPNNParams, rc: RouteConfig, axis, ndev, h_local):
        self.p, self.rc, self.axis = p, rc, axis
        self.ndev, self.h_local = ndev, h_local

    def send(self, state, dest_h, dest_r, dly, valid, p_, n_):
        p, rc, ndev, h_local = self.p, self.rc, self.ndev, self.h_local
        dest_dev = dest_h // h_local
        dest_loc = dest_h % h_local
        key = jnp.where(valid, dest_dev, ndev)
        rank = N._rank_within_key(key)
        ok = valid & (rank < rc.cap_route)
        route_drops = jnp.sum(valid) - jnp.sum(ok)
        flat = jnp.where(ok, dest_dev * rc.cap_route + rank,
                         ndev * rc.cap_route)

        def bucketize(vals, fill):
            buf = jnp.full((ndev * rc.cap_route,), fill, jnp.int32)
            return buf.at[flat].set(vals, mode="drop").reshape(ndev,
                                                               rc.cap_route)

        if rc.pack:
            # one int32 per spike (paper Fig 3 spike word): 4x less ICI
            # traffic
            words = pack_spikes(dest_loc, dest_r, dly, ok, p, h_local)
            send = bucketize(jnp.where(ok, words, 0), 0)  # (ndev, cap_route)
        else:
            send = jnp.stack([
                bucketize(dest_loc, 0),
                bucketize(dest_r, p.rows),    # p.rows == invalid row marker
                bucketize(dly, 1),
                bucketize(jnp.where(ok, 1, 0).astype(jnp.int32), 0),
            ], axis=-1)                        # (ndev, cap_route, 4)
        recv = jax.lax.all_to_all(send, self.axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # route-capacity overflow is its own Fig 7 class (drops_route), not
        # fired-batch overflow: HealthMonitor budgets the two separately
        state = state._replace(drops_route=state.drops_route + route_drops)
        return state, recv

    def recv(self, state, inflight, p_, n_):
        p, rc, ndev, h_local = self.p, self.rc, self.ndev, self.h_local
        if rc.pack:
            recv = inflight.reshape(ndev * rc.cap_route)
            d_loc, d_row, d_dly, d_ok = unpack_spikes(recv, p, h_local)
            return N.enqueue_spikes(state, d_loc, d_row, d_dly, d_ok, p,
                                    h_local)
        recv = inflight.reshape(ndev * rc.cap_route, 4)
        return N.enqueue_spikes(state, recv[:, 0], recv[:, 1], recv[:, 2],
                                recv[:, 3] == 1, p, h_local)

    def __call__(self, state, dest_h, dest_r, dly, valid, p_, n_):
        state, inflight = self.send(state, dest_h, dest_r, dly, valid,
                                    p_, n_)
        return self.recv(state, inflight, p_, n_)


def _exchange_route(p: BCPNNParams, rc: RouteConfig, axis, ndev, h_local,
                    overlap: bool = True):
    """Build the sharded spike-routing hook for `engine.tick`. With
    `overlap` (the default) this is the `SparseExchange` object itself and
    the tick runs it split around the column phase; without, a plain
    callable running the same exchange sequentially after columns — the
    historical route hook, bitwise the same trajectory."""
    ex = SparseExchange(p, rc, axis, ndev, h_local)
    if overlap:
        return ex

    def route(state, dest_h, dest_r, dly, valid, p_, n_):
        return ex(state, dest_h, dest_r, dly, valid, p_, n_)

    return route


def _local_tick(state: N.NetworkState, conn: N.Connectivity,
                ext_rows: jnp.ndarray, p: BCPNNParams, rc: RouteConfig,
                axis, be: "E.TickBackend", overlap: bool = True):
    """Per-device body executed under shard_map: `engine.tick` with the
    all_to_all spike route and a global-HCU-id RNG base. Columns run
    unconditionally (no lax.cond), matching the historical sharded tick."""
    h_local = state.delay_rows.shape[0]
    ndev = jax.lax.psum(1, axis)
    dev = jax.lax.axis_index(axis)
    return E.tick(state, conn, ext_rows, p, be, rc.cap_fire,
                  gid_base=dev * h_local,
                  route=_exchange_route(p, rc, axis, ndev, h_local,
                                        overlap=overlap),
                  cond_columns=False)


def _shard_specs(axes):
    """(state, conn, per-HCU, replicated) PartitionSpecs for an HCU shard.

    The canonical flat hcus leaves shard on their leading axis exactly like
    the batched ones did: device d owns flat rows [d*h_local*R,
    (d+1)*h_local*R) — whole HCUs, never split rows."""
    spec_h = P(axes)      # shard leading (HCU / H*R) dim over the axes
    rep = P()
    state_specs = N.NetworkState(
        hcus=H.HCUState(*([spec_h] * len(H.HCUState._fields))),
        delay_rows=spec_h, delay_count=spec_h,
        t=rep, drops_in=rep, drops_fire=rep, drops_route=rep, base_key=rep)
    conn_specs = N.Connectivity(spec_h, spec_h, spec_h)
    return state_specs, conn_specs, spec_h, rep


def make_dist_tick(mesh: Mesh, p: BCPNNParams, rc: RouteConfig,
                   axis="hcu", eager: bool = False,
                   backend: str | None = None, donate: bool = True,
                   worklist: bool | None = None,
                   fused: bool | None = None,
                   fused_cols: bool | None = None,
                   overlap: bool = True):
    """Build the sharded tick: state/conn/ext sharded over `axis`, which may
    be a single mesh axis name or a tuple of axis names (flattened).
    `worklist` forces the worklist engine backend on/off (default: auto by
    size, `hcu.use_worklist`); `fused` forces its single-pass fused row
    phase (default: on, `hcu.use_fused_rows`) and `fused_cols` its
    single-pass fused column phase (default: on, `hcu.use_fused_cols`).
    `overlap` (default on) issues the spike all_to_all before the column
    phase so its latency hides behind column traffic — bitwise the same
    trajectory as the sequential exchange (`SparseExchange`)."""
    axes = axis if isinstance(axis, tuple) else (axis,)
    state_specs, conn_specs, spec_h, _ = _shard_specs(axes)
    be = E.select_backend(p, eager=eager, worklist=worklist, kernel=backend,
                          fused=fused, fused_cols=fused_cols)

    def local(state, conn, ext):
        state, fired = _local_tick(be.carry_in(state, p), conn, ext,
                                   p=p, rc=rc, axis=axes, be=be,
                                   overlap=overlap)
        return be.carry_out(state, p), fired

    # check_vma off: the spike exchange's all_to_all is deliberately
    # unreplicated
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(state_specs, conn_specs, spec_h),
        out_specs=(state_specs, spec_h),
        check_vma=False,
    )
    # donating the state lets XLA scatter the touched rows/columns in place
    # — the lazy model's bytes-per-tick then match the paper's traffic
    # budget instead of copying whole synaptic planes (§Perf iteration)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def make_dist_run(mesh: Mesh, p: BCPNNParams, rc: RouteConfig,
                  axis="hcu", eager: bool = False,
                  backend: str | None = None, donate: bool = True,
                  worklist: bool | None = None,
                  fused: bool | None = None,
                  fused_cols: bool | None = None,
                  overlap: bool = True):
    """Scan-compiled multi-tick sharded driver (network_run's sharded twin).

    Returns fn(state, conn, ext) -> (state', fired (T, H)) where ext is the
    pre-staged (T, H, A_ext) tensor sharded on the HCU axis. The whole
    T-tick loop — including the per-tick all_to_all spike exchange — runs
    inside ONE compiled computation: zero host round-trips, exactly the
    per-tick trajectory of `make_dist_tick` applied T times. At worklist
    scales (`hcu.use_worklist`, or forced via `worklist=`) each device scans
    over its local slice of the canonical flat planes in place, so
    per-device traffic per tick is O(touched rows) instead of O(planes).
    """
    axes = axis if isinstance(axis, tuple) else (axis,)
    state_specs, conn_specs, spec_h, _ = _shard_specs(axes)
    ext_spec = P(None, axes)            # (T, H_local, A): time replicated
    fired_spec = P(None, axes)
    be = E.select_backend(p, eager=eager, worklist=worklist, kernel=backend,
                          fused=fused, fused_cols=fused_cols)

    def _local_run(state, conn, ext):
        def body(s, e):
            return _local_tick(s, conn, e, p=p, rc=rc, axis=axes, be=be,
                               overlap=overlap)
        with jax.named_scope("carry"):
            carry = be.carry_in(state, p)
        state, fired = jax.lax.scan(body, carry, ext)
        with jax.named_scope("carry"):
            return be.carry_out(state, p), fired

    fn = jax.shard_map(
        _local_run,
        mesh=mesh,
        in_specs=(state_specs, conn_specs, ext_spec),
        out_specs=(state_specs, fired_spec),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def shard_network(mesh: Mesh, state: N.NetworkState, conn: N.Connectivity,
                  axis="hcu"):
    """Place an (already materialized) network onto the mesh."""
    axes = axis if isinstance(axis, tuple) else (axis,)
    spec_h, rep = P(axes), P()
    sh = lambda spec: lambda x: jax.device_put(x, NamedSharding(mesh, spec))
    state = N.NetworkState(
        hcus=jax.tree.map(sh(spec_h), state.hcus),
        delay_rows=sh(spec_h)(state.delay_rows),
        delay_count=sh(spec_h)(state.delay_count),
        t=sh(rep)(state.t), drops_in=sh(rep)(state.drops_in),
        drops_fire=sh(rep)(state.drops_fire),
        drops_route=(None if state.drops_route is None
                     else sh(rep)(state.drops_route)),
        base_key=sh(rep)(state.base_key))
    conn = jax.tree.map(sh(spec_h), conn)
    return state, conn
