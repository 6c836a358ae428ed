"""Multi-HCU BCPNN network: state, spike queues, routing, and tick drivers.

Maps the paper's infrastructure (§II.A.3, §IV, §VI.D-E) onto JAX:

  * delay queue  — (H, max_delay, A) ring of buckets indexed by arrival tick;
                   a spike with biological delay d lands in bucket (t+d) % D.
                   Bucket capacity A is the paper's active-queue size (36 for
                   human scale, from the Poisson tail analysis of Fig 7);
                   overflows are counted as drops, exactly the paper's
                   1-spike-per-month budget.
  * active queue — the bucket being consumed this tick (+ external input).
  * fanout       — static connectivity (dest_hcu, dest_row, delay) per MCU,
                   the analogue of the pipelined binary-tree spike NoC. In the
                   sharded runtime the tree becomes an all_to_all over fixed
                   per-device-pair buckets (see distributed.py).
  * column batching — only HCUs that actually fired pay for a column update;
                   fired HCUs are compacted into a fixed-capacity batch
                   (cap_fire) the same way spikes are queued.

Canonical state layout (PR 3)
-----------------------------
`NetworkState.hcus` stores the FLAT layout (`repro.core.layout`): ij planes
(H*R, C), i-vectors (H*R,), j-vectors (H, C). This is the layout the
worklist tick engine consumes natively and the layout checkpoints persist;
`hcu_view(state)` exposes the batched (H, R, C) view for per-HCU vmapped
code (`flush`, inspection, the dense engine backend). Old (H, R, C)-layout
checkpoints load through `repro.checkpoint.restore_network`'s migration
shim.

Tick pipeline
-------------
The tick body itself lives in `repro.core.engine`: one `tick` skeleton
(consume bucket -> plane update -> fan out) parameterized by a `TickBackend`
(DenseBackend per-HCU vmap vs WorklistBackend flat-plane worklist,
`engine.select_backend`). This module keeps the network *infrastructure* —
queues, spike routing, compaction — and the execution drivers:

  * `network_tick` — one jitted tick (host-loop building block).
  * `run`          — per-tick host loop (one jit dispatch + host sync per
                     ms). Kept as the baseline and for callers that need a
                     host-side decision between ticks.
  * `network_run`  — the production path: external input is pre-staged as a
                     dense (T, H, A_ext) tensor (`stage_external`), and the
                     loop is compiled with `jax.lax.scan` in chunks of
                     `chunk` ticks (default 128). Per chunk there is exactly
                     ONE dispatch; the NetworkState carry is donated and, at
                     worklist scales, IS the stored flat layout — no
                     per-tick reshapes, plane traffic O(touched rows).

All drivers share the exact same single-tick body, so their trajectories
are bitwise identical under a fixed PRNG key — in lazy, eager and merged
modes, on both the dense and worklist backends (tests/test_network_run.py,
tests/test_worklist.py, tests/test_engine_fixtures.py).

Scan-chunking contract:
  * ext staging      — ext[k] is consumed by tick t0+k+1 where t0 is
                       state.t at entry (matching `run`, which calls
                       ext_fn(state.t + 1) before each tick);
  * fired history    — returned as (T, H) int32, fired[k, h] = MCU index
                       that HCU h fired at tick t0+k+1, or -1;
  * chunking         — T need not divide by `chunk`: full chunks compile
                       one scan, the remainder compiles a second (at most
                       two compilations per (shape, mode));
  * donation         — the caller's `state` is donated; use the returned
                       state (same semantics as `network_tick`).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import hcu as H
from repro.core import layout as L
from repro.core.params import BCPNNParams


class Connectivity(NamedTuple):
    dest_hcu: jnp.ndarray   # (H, C, F) int32
    dest_row: jnp.ndarray   # (H, C, F) int32
    delay: jnp.ndarray      # (H, C, F) int32, in [1, max_delay-1]


class NetworkState(NamedTuple):
    hcus: H.HCUState        # CANONICAL FLAT layout (see module docstring)
    delay_rows: jnp.ndarray  # (H, D, A) int32; empty slots == R
    delay_count: jnp.ndarray  # (H, D) int32
    t: jnp.ndarray          # () int32 current time (ms)
    drops_in: jnp.ndarray   # () int32  — delay-queue overflow drops
    drops_fire: jnp.ndarray  # () int32 — fired-batch overflow drops
    base_key: jnp.ndarray   # PRNG key
    jring: jnp.ndarray | None = None   # (H, C, M) merged-mode spike rings
    # () int32 — inter-device route-capacity overflow drops (sharded fabric
    # only; local drivers never touch it). LAST field: pre-PR 7 checkpoints
    # are one trailing leaf short, which `checkpoint.restore_network` shims.
    drops_route: jnp.ndarray | None = None


def drop_counters(state: NetworkState) -> dict:
    """Cumulative spike-drop counters as a plain dict — the Fig 7 failure
    currency ({'in': delay-queue, 'fire': fired-batch, 'route': inter-device
    fabric overflows}). Tolerates pre-`drops_route` states (counts as 0)."""
    route = state.drops_route
    return {"in": int(state.drops_in), "fire": int(state.drops_fire),
            "route": 0 if route is None else int(route)}


def hcu_view(state: NetworkState, layout=None) -> H.HCUState:
    """Batched (H, R, C)/(H, R) view of `state.hcus` — the shape
    `jax.vmap`-over-HCUs consumers want, e.g.
    `jax.vmap(lambda s: flush(s, state.t, p))(hcu_view(state))`.
    Zero-copy on the canonical flat layout; under a blocked `layout` the ij
    planes are first unpacked to canonical order (`layout.load_hcus`, pure
    data movement)."""
    return L.batched_state(L.load_hcus(state.hcus, layout),
                           state.delay_rows.shape[0])


def make_connectivity(p: BCPNNParams, key, n_hcu: int | None = None) -> Connectivity:
    """Random static fanout: each MCU projects to `fanout` (HCU, row) targets
    with biological delays of mean ~`mean_delay` ms (truncated geometric)."""
    n = n_hcu or p.n_hcu
    k1, k2, k3 = jax.random.split(key, 3)
    shape = (n, p.cols, p.fanout)
    dest_hcu = jax.random.randint(k1, shape, 0, n, jnp.int32)
    dest_row = jax.random.randint(k2, shape, 0, p.rows, jnp.int32)
    lam = 1.0 / max(p.mean_delay - 1.0, 1e-3)
    geo = jnp.floor(jnp.log1p(-jax.random.uniform(k3, shape)) / -lam).astype(jnp.int32)
    delay = jnp.clip(1 + geo, 1, p.max_delay - 1)
    return Connectivity(dest_hcu, dest_row, delay)


def init_network(p: BCPNNParams, key, n_hcu: int | None = None,
                 merged: bool = False, layout=None) -> NetworkState:
    n = n_hcu or p.n_hcu
    # canonical flat layout, re-tiled iff a blocked layout is requested
    # (pure data movement — a blocked-layout network holds bitwise the same
    # logical values as a flat one)
    hcus = L.store_hcus(H.init_hcu_batch(p, n), layout)
    D, A = p.max_delay, p.active_queue
    jring = None
    if merged:
        from repro.core import merged as M
        jring = jnp.broadcast_to(M.init_ring(p),
                                 (n, p.cols, M.RING_DEPTH)).copy()
    return NetworkState(
        jring=jring,
        hcus=hcus,
        delay_rows=jnp.full((n, D, A), p.rows, jnp.int32),
        delay_count=jnp.zeros((n, D), jnp.int32),
        t=jnp.asarray(0, jnp.int32),
        drops_in=jnp.asarray(0, jnp.int32),
        drops_fire=jnp.asarray(0, jnp.int32),
        drops_route=jnp.asarray(0, jnp.int32),
        # private derived key: network_tick donates the state, so base_key
        # must not alias a caller-held (or sibling-network) buffer
        base_key=jax.random.fold_in(key, 0x5EED),
    )


# Below this message count the O(M^2) fused compare-reduce rank beats the
# sort-based path on op overhead; above it the sort path's O(M log M) wins.
_RANK_DENSE_MAX = 2048


def _rank_within_key(keys: jnp.ndarray) -> jnp.ndarray:
    """Rank of each element within its key group (stable: by position).

    rank[i] == #{j < i : keys[j] == keys[i]} — identical to position within
    the group under a stable sort.
    """
    M = keys.shape[0]
    if M <= _RANK_DENSE_MAX:
        eq = keys[:, None] == keys[None, :]                 # (M, M)
        earlier = jnp.arange(M)[None, :] < jnp.arange(M)[:, None]
        return jnp.sum(eq & earlier, axis=1).astype(keys.dtype)
    order = jnp.argsort(keys)                               # stable
    sorted_keys = keys[order]
    idx = jnp.arange(M)
    is_first = jnp.concatenate([jnp.array([True]),
                                sorted_keys[1:] != sorted_keys[:-1]])
    first_pos = jax.lax.cummax(jnp.where(is_first, idx, 0))
    rank_sorted = idx - first_pos
    return jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)


def consume_bucket(state: NetworkState, t, p: BCPNNParams, n: int):
    """Read this tick's delay bucket and clear it. Shared by the local and
    sharded tick bodies (keeping their trajectories identical). On small
    networks the clear is a fused iota-compare where (cheaper than the
    dynamic-update-slice scatter on XLA CPU); at scale the slice update
    touches only the one bucket."""
    D = p.max_delay
    bucket = state.delay_rows[:, t % D, :]                     # (H, A)
    if n * D * p.active_queue <= H.DENSE_CELLS_MAX:
        is_bucket = jnp.arange(D) == t % D                     # (D,)
        state = state._replace(
            delay_rows=jnp.where(is_bucket[None, :, None], p.rows,
                                 state.delay_rows),
            delay_count=jnp.where(is_bucket[None, :], 0, state.delay_count))
    else:
        state = state._replace(
            delay_rows=state.delay_rows.at[:, t % D, :].set(p.rows),
            delay_count=state.delay_count.at[:, t % D].set(0))
    return state, bucket


def enqueue_spikes(state: NetworkState, dest_h, dest_row, delay, valid,
                   p: BCPNNParams, n_hcu: int):
    """Insert a flat batch of spike messages into the delay queues.

    Fixed-capacity slot allocation: messages are ranked within their
    (dest_hcu, bucket) group; slot = current_count + rank; messages whose slot
    exceeds the bucket capacity A are dropped and counted (paper Fig 7).
    """
    D, A = p.max_delay, p.active_queue
    M = dest_h.shape[0]
    bucket = (state.t + delay) % D
    key = jnp.where(valid, dest_h * D + bucket, n_hcu * D)      # invalid rank last
    rank = _rank_within_key(key)
    base = state.delay_count[dest_h, bucket]                    # (M,)
    slot = base + rank
    ok = valid & (slot < A)
    flat_idx = jnp.where(ok, (dest_h * D + bucket) * A + slot, n_hcu * D * A)
    delay_rows = state.delay_rows.reshape(-1).at[flat_idx].set(
        dest_row, mode="drop").reshape(n_hcu, D, A)
    # bucket occupancy: add arrivals, clip at capacity
    if M * n_hcu * D <= H.DENSE_CELLS_MAX:
        # dense compare+reduce ((M, H*D) one-hot sum) instead of
        # scatter-add: integer sum is order-independent (bitwise-identical)
        # and avoids the scatter op cost on small networks. `key` is the
        # (h, bucket) flat index with invalids sent out of range.
        arrivals = jnp.sum(
            (key[:, None] == jnp.arange(n_hcu * D)[None, :]).astype(jnp.int32),
            axis=0).reshape(n_hcu, D)
    else:
        arrivals = jnp.zeros((n_hcu, D), jnp.int32).at[dest_h, bucket].add(
            valid.astype(jnp.int32), mode="drop")
    new_count = jnp.minimum(state.delay_count + arrivals, A)
    dropped = jnp.sum(state.delay_count + arrivals - new_count)
    return state._replace(delay_rows=delay_rows, delay_count=new_count,
                          drops_in=state.drops_in + dropped)


def select_fired(fired: jnp.ndarray, cap: int):
    """Compact fired HCU indices (fired[h] >= 0) into `cap` slots."""
    n = fired.shape[0]
    is_fired = fired >= 0
    order = jnp.argsort(~is_fired)              # fired first, stable
    idx = order[:cap]
    sel_valid = is_fired[idx]
    h_idx = jnp.where(sel_valid, idx, n)
    j_idx = jnp.where(sel_valid, fired[idx], 0)
    n_dropped = jnp.sum(is_fired) - jnp.sum(sel_valid)
    return h_idx.astype(jnp.int32), j_idx.astype(jnp.int32), n_dropped


# ---------------------------------------------------------------------------
# session batching (serving): a leading (S,) lane dim over NetworkState
# ---------------------------------------------------------------------------

def stack_sessions(state: NetworkState, n_sessions: int) -> NetworkState:
    """Replicate one NetworkState into `n_sessions` independent session
    lanes: every leaf gains a leading (S,) batch dim.

    Each lane then evolves under its own per-session external stream — the
    state layout the continuous-batching recall server
    (`repro.launch.serve_bcpnn`) carries. Lanes must be advanced with
    `jax.lax.map` (NOT vmap): lax.map runs one lane at a time with exactly
    the single-session `_run_chunk` graph and shapes, so lane trajectories
    stay bitwise identical to independent `Simulator.run` calls; vmap would
    fuse across lanes, and XLA:CPU fused codegen is 1-ulp context-sensitive
    (docs/NUMERICS.md).
    """
    def rep(a):
        a = jnp.asarray(a)
        return jnp.repeat(a[None], n_sessions, axis=0)
    return jax.tree.map(rep, state)


@functools.partial(jax.jit, donate_argnums=(0,))
def write_sessions(stacked: NetworkState, template: NetworkState,
                   lanes: jnp.ndarray) -> NetworkState:
    """Scatter a fresh `template` into the session lanes named by `lanes`
    ((K,) int32; out-of-range entries are dropped, so pad with S to write
    fewer than K lanes with one compiled shape). The stacked state is
    donated: slot recycling writes freed lanes in place — admission never
    copies the other lanes or recompiles."""
    def put(st, tp):
        tp = jnp.asarray(tp)
        rep = jnp.broadcast_to(tp[None], (lanes.shape[0],) + tp.shape)
        return st.at[lanes].set(rep, mode="drop")
    return jax.tree.map(put, stacked, template)


def take_session(stacked: NetworkState, lane: int) -> NetworkState:
    """One session lane back as a plain single-session NetworkState
    (inspection / the bitwise-vs-Simulator serving tests)."""
    return jax.tree.map(lambda a: a[lane], stacked)


# ---------------------------------------------------------------------------
# execution drivers (thin wrappers over engine.tick)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("p", "eager", "backend",
                                             "cap_fire", "merged",
                                             "worklist", "fused",
                                             "fused_cols", "layout"),
                   donate_argnums=(0,))
def network_tick(state: NetworkState, conn: Connectivity, ext_rows: jnp.ndarray,
                 p: BCPNNParams, *, eager: bool = False, merged: bool = False,
                 backend: str | None = None, cap_fire: int | None = None,
                 worklist: bool | None = None, fused: bool | None = None,
                 fused_cols: bool | None = None, layout=None):
    """Advance the whole network by one 1 ms tick.

    ext_rows: (H, A_ext) external input spikes (row index, padding == p.rows)
    Returns (state', fired (H,)) with fired[h] = MCU index or -1.
    merged=True runs the eBrainIII merged-column-update mode (core/merged.py;
    state must be built with init_network(..., merged=True)).
    worklist=True/False forces the worklist engine backend on/off (default:
    auto by size, `hcu.use_worklist`); fused=True/False likewise forces the
    worklist backend's single-pass fused row phase (default: on,
    `hcu.use_fused_rows`) and fused_cols=True/False its single-pass fused
    column phase (default: on, `hcu.use_fused_cols`); trajectories are
    identical every way.
    layout selects the plane storage order (None/"flat" canonical flat,
    "blocked"/"blocked_tpu"/a `layout.BlockedLayout` for column-blocked
    tiles; `state.hcus` must be stored in that layout) — trajectories are
    identical under every layout (storage order, not math).
    """
    from repro.core import engine as E
    be = E.select_backend(p, eager=eager, merged=merged, worklist=worklist,
                          kernel=backend, fused=fused, fused_cols=fused_cols,
                          layout=layout)
    state, fired = E.tick(be.carry_in(state, p), conn, ext_rows, p, be,
                          cap_fire)
    return be.carry_out(state, p), fired


@functools.partial(jax.jit, static_argnames=("p", "eager", "backend",
                                             "cap_fire", "merged",
                                             "worklist", "fused",
                                             "fused_cols", "layout"),
                   donate_argnums=(0,))
def _run_chunk(state: NetworkState, conn: Connectivity, ext: jnp.ndarray,
               p: BCPNNParams, *, eager: bool, merged: bool,
               backend: str | None, cap_fire: int | None,
               worklist: bool | None, fused: bool | None,
               fused_cols: bool | None, layout=None):
    """One compiled scan over ext (T_chunk, H, A_ext): a single dispatch
    advances the network T_chunk ticks, threading the donated state. The
    backend picks the carry layout ONCE per chunk (`carry_in`/`carry_out` at
    the scan boundary): the worklist backend's carry is the stored flat
    layout itself, so the tick body has zero per-tick reshapes."""
    from repro.core import engine as E
    be = E.select_backend(p, eager=eager, merged=merged, worklist=worklist,
                          kernel=backend, fused=fused, fused_cols=fused_cols,
                          layout=layout)

    def body(s, e):
        return E.tick(s, conn, e, p, be, cap_fire)

    with jax.named_scope("carry"):
        carry = be.carry_in(state, p)
    state, fired = jax.lax.scan(body, carry, ext)
    with jax.named_scope("carry"):
        return be.carry_out(state, p), fired


def network_run(state: NetworkState, conn: Connectivity, ext: jnp.ndarray,
                p: BCPNNParams, *, chunk: int = 128, eager: bool = False,
                merged: bool = False, backend: str | None = None,
                cap_fire: int | None = None, worklist: bool | None = None,
                fused: bool | None = None, fused_cols: bool | None = None,
                layout=None):
    """Scan-compiled multi-tick driver (see module docstring contract).

    ext: (T, H, A_ext) pre-staged external spikes — use `stage_external`.
    Returns (state', fired_hist (T, H) int32). Bitwise-equivalent to `run`
    with the same inputs, ~dispatch-free: one compiled step per `chunk`
    ticks instead of one per tick.
    """
    ext = jnp.asarray(ext)
    T = ext.shape[0]
    n = state.delay_rows.shape[0]
    if T == 0:
        return state, jnp.zeros((0, n), jnp.int32)
    hist = []
    i = 0
    while i < T:
        step = min(chunk, T - i)
        state, fired = _run_chunk(state, conn, ext[i:i + step], p,
                                  eager=eager, merged=merged, backend=backend,
                                  cap_fire=cap_fire, worklist=worklist,
                                  fused=fused, fused_cols=fused_cols,
                                  layout=layout)
        hist.append(fired)
        i += step
    return state, (hist[0] if len(hist) == 1 else jnp.concatenate(hist))


def stage_external(ext, n_ticks: int | None = None, t0: int = 0) -> jnp.ndarray:
    """Stage external input as the dense (T, H, A_ext) tensor `network_run`
    consumes. `ext` is either an iterable of (H, A_ext) arrays or a callable
    ext_fn(t) (the `run` protocol); t0 is state.t at entry, so ext_fn is
    sampled at t0+1 .. t0+n_ticks exactly like the host loop."""
    if callable(ext):
        assert n_ticks is not None, "n_ticks required with a callable"
        ext = [ext(t0 + 1 + k) for k in range(n_ticks)]
    else:
        ext = list(ext)
    return jnp.stack([jnp.asarray(e) for e in ext])


def run(state: NetworkState, conn: Connectivity, ext_fn, n_ticks: int,
        p: BCPNNParams, **kw):
    """Per-tick host-loop driver: ext_fn(t) -> (H, A_ext) external rows.

    One jit dispatch + `int(state.t)` host sync per tick — kept as the
    dispatch-bound baseline (benchmarks/tick_loop.py) and for callers that
    need host-side control between ticks. Production paths should stage
    input and use `network_run`.
    """
    fired_hist = []
    for _ in range(n_ticks):
        ext = ext_fn(int(state.t) + 1)
        state, fired = network_tick(state, conn, ext, p, **kw)
        fired_hist.append(fired)
    return state, jnp.stack(fired_hist)
