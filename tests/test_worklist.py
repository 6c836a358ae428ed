"""Worklist tick runtime vs the per-HCU vmap path — bitwise identity.

The flat-plane worklist runtime (core/worklist.py + the worklist branches in
core/network.py) is a memory-traffic refactor, not a semantics change: with
`worklist=True` forced on small sizes, every trajectory — fired history,
all state planes, queues, rings — must be bit-for-bit identical to the
per-HCU vmapped path, in lazy, merged and sharded modes, across random
spike patterns, duplicate rows, queue-overflow ticks and empty ticks.

The worklist path achieves this by construction: it stages touched rows
into buffers with in-place dynamic-slice loops and then runs the *same
vmapped compute graph* (same shapes, same broadcasts, same code objects)
as the per-HCU path — XLA:CPU's fused codegen is context-sensitive at the
1-ulp level, so these tests are the guard that the shared-graph discipline
holds.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import (init_network, make_connectivity, network_run,
                        test_scale as tiny_scale)
from repro.core import hcu as H
from repro.core import network as N
from repro.core import worklist as WL
from repro.core.params import BCPNNParams

SRC = str(Path(__file__).resolve().parents[1] / "src")

# two fixed dimensionings so jit caches are reused across cases/examples
LAZY_P = tiny_scale(n_hcu=4, rows=64, cols=16)
HOT_P = BCPNNParams(n_hcu=6, rows=48, cols=12, fanout=12, active_queue=6,
                    max_delay=6, out_rate=0.5)      # queue-overflow regime
MERGED_P = BCPNNParams(n_hcu=4, rows=24, cols=16, fanout=4, active_queue=8,
                       max_delay=8, out_rate=0.6)   # ring-overflow regime


def _ext_tensor(p, seed, n_ticks, width=8, lam=3.0, duplicates=False):
    """Random staged input; lam=0 gives all-empty ticks; duplicates=True
    forces repeated row indices within a tick's slot array."""
    rng = np.random.default_rng(seed)
    out = np.full((n_ticks, p.n_hcu, width), p.rows, np.int32)
    for t in range(n_ticks):
        for h in range(p.n_hcu):
            n = min(width, rng.poisson(lam))
            rows = rng.integers(0, p.rows, n)
            if duplicates and n >= 2:
                rows[1] = rows[0]
            out[t, h, :n] = rows
    return jnp.asarray(out)


def _run_both(p, ext, merged=False, chunk=16, key_seed=0, fused=None,
              fused_cols=None):
    key = jax.random.PRNGKey(key_seed)
    conn = make_connectivity(p, jax.random.fold_in(key, 1))
    kw = dict(merged=merged, chunk=chunk,
              cap_fire=p.n_hcu if merged else None)
    sa, fa = network_run(init_network(p, key, merged=merged), conn, ext, p,
                         worklist=False, **kw)
    sb, fb = network_run(init_network(p, key, merged=merged), conn, ext, p,
                         worklist=True, fused=fused, fused_cols=fused_cols,
                         **kw)
    return sa, fa, sb, fb


# Two DIFFERENT Pallas programs run in interpret mode (a worklist kernel vs
# the dense row kernel) are each compiled by XLA:CPU on their own, and it may
# round the same cell math 1 ulp apart (docs/NUMERICS.md). Such pairs are
# held to this many ulp on float planes; fired histories, integer planes and
# queues stay exact.
INTERPRET_MAXULP = 2


def _assert_within_ulp(sa, fa, sb, fb, maxulp=INTERPRET_MAXULP):
    np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    for name in sa.hcus._fields:
        a, b = np.asarray(getattr(sa.hcus, name)), \
            np.asarray(getattr(sb.hcus, name))
        if a.dtype.kind == "f":
            np.testing.assert_array_max_ulp(a, b, maxulp=maxulp)
        else:
            np.testing.assert_array_equal(a, b, err_msg=f"plane {name}")
    np.testing.assert_array_equal(np.asarray(sa.delay_rows),
                                  np.asarray(sb.delay_rows))
    np.testing.assert_array_equal(np.asarray(sa.delay_count),
                                  np.asarray(sb.delay_count))
    assert int(sa.drops_in) == int(sb.drops_in)
    assert int(sa.drops_fire) == int(sb.drops_fire)


def _assert_bitwise(sa, fa, sb, fb, merged=False):
    np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    for name in sa.hcus._fields:
        a, b = np.asarray(getattr(sa.hcus, name)), \
            np.asarray(getattr(sb.hcus, name))
        np.testing.assert_array_equal(a, b, err_msg=f"plane {name}")
    np.testing.assert_array_equal(np.asarray(sa.delay_rows),
                                  np.asarray(sb.delay_rows))
    np.testing.assert_array_equal(np.asarray(sa.delay_count),
                                  np.asarray(sb.delay_count))
    assert int(sa.drops_in) == int(sb.drops_in)
    assert int(sa.drops_fire) == int(sb.drops_fire)
    if merged:
        np.testing.assert_array_equal(np.asarray(sa.jring),
                                      np.asarray(sb.jring))


@pytest.mark.parametrize("case", ["random", "duplicates", "empty"])
def test_lazy_worklist_bitwise(case):
    lam = {"random": 3.0, "duplicates": 4.0, "empty": 0.0}[case]
    ext = _ext_tensor(LAZY_P, seed=11, n_ticks=40, lam=lam,
                      duplicates=(case == "duplicates"))
    sa, fa, sb, fb = _run_both(LAZY_P, ext)
    if case != "empty":
        assert (np.asarray(fa) >= 0).sum() > 0, "must exercise output spikes"
    _assert_bitwise(sa, fa, sb, fb)


def test_lazy_worklist_bitwise_under_queue_overflow():
    """High rate + tight queues: delay-queue and fired-batch drops occur and
    must be counted identically (the worklist never drops row updates —
    cap_total covers every slot)."""
    ext = _ext_tensor(HOT_P, seed=5, n_ticks=60, lam=6.0)
    sa, fa, sb, fb = _run_both(HOT_P, ext, chunk=60)
    assert int(sa.drops_in) > 0 and int(sa.drops_fire) > 0, \
        "case must exercise queue overflow"
    _assert_bitwise(sa, fa, sb, fb)


@pytest.mark.parametrize("case", ["random", "empty"])
def test_merged_worklist_bitwise(case):
    """Merged mode: ring pushes, overflow flushes and same-tick patches all
    ride the worklist; jring must match bit-for-bit too."""
    lam = {"random": 6.0, "empty": 0.0}[case]
    ext = _ext_tensor(MERGED_P, seed=7, n_ticks=80, lam=lam)
    sa, fa, sb, fb = _run_both(MERGED_P, ext, merged=True, chunk=11)
    if case == "random":
        assert (np.asarray(fa) >= 0).sum() > MERGED_P.n_hcu * 8, \
            "case must exercise ring overflow (fires > H * RING_DEPTH)"
    _assert_bitwise(sa, fa, sb, fb, merged=True)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), lam=st.sampled_from([0.0, 2.0, 6.0]),
       merged=st.booleans())
def test_worklist_bitwise_property(seed, lam, merged):
    """Property form: any spike pattern, any regime, both modes."""
    p = MERGED_P if merged else LAZY_P
    ext = _ext_tensor(p, seed=seed, n_ticks=24, lam=lam,
                      duplicates=bool(seed % 2))
    sa, fa, sb, fb = _run_both(p, ext, merged=merged, chunk=24,
                               key_seed=seed % 7)
    _assert_bitwise(sa, fa, sb, fb, merged=merged)


def test_sharded_worklist_bitwise():
    """make_dist_run(worklist=True) == make_dist_run(worklist=False), planes
    and fired history, over 4 host devices (subprocess: device count must be
    set before jax initializes)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import *
        from repro.core import distributed as DD

        p = test_scale(n_hcu=8, rows=64, cols=16)
        key = jax.random.PRNGKey(0)
        conn = make_connectivity(p, jax.random.fold_in(key, 1))
        mesh = jax.make_mesh((4,), ("hcu",))
        rc = DD.default_route_config(p, 2)
        rng = np.random.default_rng(7)
        ext = np.full((25, p.n_hcu, 8), p.rows, np.int32)
        for t in range(25):
            for h in range(p.n_hcu):
                n = min(8, rng.poisson(3))
                ext[t, h, :n] = rng.integers(0, p.rows, n)
        ext = jnp.asarray(ext)
        outs = {}
        for wl in (False, True):
            s0, c0 = DD.shard_network(mesh, init_network(p, key), conn)
            fn = DD.make_dist_run(mesh, p, rc, axis="hcu", worklist=wl)
            s1, f1 = fn(s0, c0, ext)
            outs[wl] = (s1, np.asarray(f1))
        np.testing.assert_array_equal(outs[False][1], outs[True][1])
        assert (outs[False][1] >= 0).sum() > 0
        for name in outs[False][0].hcus._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(outs[False][0].hcus, name)),
                np.asarray(getattr(outs[True][0].hcus, name)), err_msg=name)
        print("SHARDED-WORKLIST-OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env={**__import__("os").environ,
                                       "PYTHONPATH": SRC})
    assert "SHARDED-WORKLIST-OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.parametrize("fused", [False, True])
def test_lazy_worklist_fused_vs_staged_bitwise(fused):
    """The fused single-pass row phase (`fused=True`, the default) and the
    three-phase staged form (`fused=False`) must both match the dense path
    bit-for-bit — the fused loop inlines the SAME (1, C) cell formulas the
    vmapped compute runs, and the lazy island is small enough that XLA:CPU
    compiles it identically in both contexts (docs/NUMERICS.md)."""
    ext = _ext_tensor(LAZY_P, seed=23, n_ticks=40, lam=3.0)
    sa, fa, sb, fb = _run_both(LAZY_P, ext, fused=fused)
    assert (np.asarray(fa) >= 0).sum() > 0
    _assert_bitwise(sa, fa, sb, fb)


def test_lazy_fused_bitwise_at_rodent_dimensioning():
    """Pin the fused/staged identity AT A SHAPE WHERE FUSED ACTUALLY RUNS
    BY DEFAULT: R=1200, C=70 (rodent dimensioning, R*C > DENSE_CELLS_MAX so
    `use_worklist` holds without an override). The numerics doctrine
    (docs/NUMERICS.md) is that codegen identity across compilation contexts
    is shape-dependent and must be empirically pinned — the toy-size A/Bs
    above do not cover the large-shape compilations a jax/XLA upgrade could
    change."""
    p = BCPNNParams(n_hcu=2, rows=1200, cols=70, fanout=2, active_queue=8,
                    max_delay=8)
    assert H.use_worklist(p), "must exercise the default-on regime"
    ext = _ext_tensor(p, seed=13, n_ticks=8, lam=4.0)
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(p, jax.random.fold_in(key, 1))
    sa, fa = network_run(init_network(p, key), conn, ext, p, chunk=8,
                         fused=False)
    sb, fb = network_run(init_network(p, key), conn, ext, p, chunk=8,
                         fused=True)
    _assert_bitwise(sa, fa, sb, fb)


def test_lazy_worklist_fused_under_queue_overflow():
    ext = _ext_tensor(HOT_P, seed=5, n_ticks=60, lam=6.0)
    sa, fa, sb, fb = _run_both(HOT_P, ext, chunk=60, fused=True)
    assert int(sa.drops_in) > 0 and int(sa.drops_fire) > 0
    _assert_bitwise(sa, fa, sb, fb)


def test_pallas_interpret_fused_megakernel_matches_vmap_path():
    """The fused scalar-prefetch megakernel (`ops.fused_row_update`,
    interpret mode) must reproduce the vmapped pallas-interpret path exactly
    — ij planes, i-vectors (rewritten in place by the kernel) and weight
    planes alike."""
    ext = _ext_tensor(LAZY_P, seed=3, n_ticks=12, lam=3.0)
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(LAZY_P, jax.random.fold_in(key, 1))
    sa, fa = network_run(init_network(LAZY_P, key), conn, ext, LAZY_P,
                         chunk=12, worklist=False, backend="pallas_interpret")
    sb, fb = network_run(init_network(LAZY_P, key), conn, ext, LAZY_P,
                         chunk=12, worklist=True, fused=True,
                         backend="pallas_interpret")
    _assert_bitwise(sa, fa, sb, fb)


@pytest.mark.parametrize("xr", [8, 7])
def test_pallas_interpret_blocked_layout_matches_flat(xr):
    """The TPU story for the blocked layout: at a degenerate (Tc == 1)
    tile the stored plane is a pure reshape of the row-padded flat plane
    (`BlockedLayout.flat_view`), so the scalar-prefetch megakernels run
    unmodified — only the row-index stream is remapped. The blocked
    pallas-interpret trajectory must equal the flat one bitwise; xr=7
    forces row padding (junk rows + sentinel remap)."""
    from repro.core import layout as L
    ext = _ext_tensor(LAZY_P, seed=3, n_ticks=12, lam=3.0)
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(LAZY_P, jax.random.fold_in(key, 1))
    lay = L.BlockedLayout(rows=LAZY_P.rows, cols=LAZY_P.cols, xr=xr, xc=128)
    assert lay.tpu_degenerate
    assert (lay.padded_rows > LAZY_P.rows) == (xr == 7)
    sa, fa = network_run(init_network(LAZY_P, key), conn, ext, LAZY_P,
                         chunk=12, worklist=True, fused=True,
                         backend="pallas_interpret")
    sb, fb = network_run(init_network(LAZY_P, key, layout=lay), conn, ext,
                         LAZY_P, chunk=12, worklist=True, fused=True,
                         backend="pallas_interpret", layout=lay)
    sb = sb._replace(hcus=L.load_hcus(sb.hcus, lay))
    _assert_bitwise(sa, fa, sb, fb)


def test_pallas_interpret_worklist_matches_vmap_path():
    """The non-fused scalar-prefetch Pallas worklist kernel (interpret mode)
    must reproduce the vmapped pallas-interpret path: the same fired
    history, and planes within INTERPRET_MAXULP (the two sides are
    different kernels, compiled separately)."""
    ext = _ext_tensor(LAZY_P, seed=3, n_ticks=12, lam=3.0)
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(LAZY_P, jax.random.fold_in(key, 1))
    sa, fa = network_run(init_network(LAZY_P, key), conn, ext, LAZY_P,
                         chunk=12, worklist=False, backend="pallas_interpret")
    sb, fb = network_run(init_network(LAZY_P, key), conn, ext, LAZY_P,
                         chunk=12, worklist=True, fused=False,
                         backend="pallas_interpret")
    assert (np.asarray(fa) >= 0).sum() > 0
    _assert_within_ulp(sa, fa, sb, fb)


@pytest.mark.parametrize("fused_cols", [False, True])
def test_lazy_worklist_fused_cols_vs_staged_bitwise(fused_cols):
    """The fused single-pass column phase (`fused_cols=True`, the default)
    and the three-phase staged form (`fused_cols=False`) must both match the
    dense path bit-for-bit — the fused loop inlines the SAME (R,) cell
    formulas the vmapped compute runs, and the lazy column island (one
    `decay_zep` + increment + `log`, the same island the fused row phase
    proved) compiles identically in both contexts (docs/NUMERICS.md)."""
    ext = _ext_tensor(LAZY_P, seed=29, n_ticks=40, lam=3.0)
    sa, fa, sb, fb = _run_both(LAZY_P, ext, fused_cols=fused_cols)
    assert (np.asarray(fa) >= 0).sum() > 0, "must exercise column updates"
    _assert_bitwise(sa, fa, sb, fb)


@pytest.mark.parametrize("rows,cols", [(1200, 70), (10000, 100)])
def test_lazy_fused_cols_bitwise_at_scale_dimensioning(rows, cols):
    """Pin the fused/staged COLUMN identity at shapes where fused is the
    default-on path (R*C > DENSE_CELLS_MAX): rodent16 (R=1200, C=70) and
    human-column (R=10000, C=100) dimensioning. Codegen identity across
    compilation contexts is shape-dependent and must be empirically pinned
    (docs/NUMERICS.md) — the toy-size A/Bs do not cover these
    compilations."""
    p = BCPNNParams(n_hcu=2, rows=rows, cols=cols, fanout=2, active_queue=8,
                    max_delay=8, out_rate=0.9)
    assert H.use_worklist(p), "must exercise the default-on regime"
    n_ticks = 8 if rows <= 1200 else 4
    ext = _ext_tensor(p, seed=17, n_ticks=n_ticks, lam=4.0)
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(p, jax.random.fold_in(key, 1))
    sa, fa = network_run(init_network(p, key), conn, ext, p, chunk=n_ticks,
                         fused_cols=False)
    sb, fb = network_run(init_network(p, key), conn, ext, p, chunk=n_ticks,
                         fused_cols=True)
    assert (np.asarray(fa) >= 0).sum() > 0, "must exercise column updates"
    _assert_bitwise(sa, fa, sb, fb)


def test_lazy_worklist_fused_cols_under_queue_overflow():
    """Queue/fired-batch overflow under the fused column path: drops must be
    counted identically and the padding fired-batch slots (h_idx == n) must
    stay no-ops in the fused loop."""
    ext = _ext_tensor(HOT_P, seed=5, n_ticks=60, lam=6.0)
    sa, fa, sb, fb = _run_both(HOT_P, ext, chunk=60, fused_cols=True)
    assert int(sa.drops_in) > 0 and int(sa.drops_fire) > 0
    _assert_bitwise(sa, fa, sb, fb)


@pytest.mark.parametrize("fused_cols", [False, True])
def test_merged_worklist_fused_cols_is_inert(fused_cols):
    """Merged mode: `fused_cols` is accepted but the merged column flush and
    the same-tick `patch_cells` interaction keep the shared
    `merged_col_math` island — trajectories (incl. ring overflow flushes)
    must be bitwise-identical to the dense merged path either way."""
    ext = _ext_tensor(MERGED_P, seed=7, n_ticks=80, lam=6.0)
    sa, fa, sb, fb = _run_both(MERGED_P, ext, merged=True, chunk=11,
                               fused_cols=fused_cols)
    assert (np.asarray(fa) >= 0).sum() > MERGED_P.n_hcu * 8, \
        "case must exercise ring overflow (fires > H * RING_DEPTH)"
    _assert_bitwise(sa, fa, sb, fb, merged=True)


def test_pallas_interpret_fused_col_megakernel_matches_vmap_path():
    """The fused column megakernel (`ops.fused_col_update`, interpret mode)
    must reproduce the vmapped pallas-interpret path exactly — the fired
    (R, 1) column blocks are rewritten in place with the same kernel cell
    math the batched column kernel runs."""
    ext = _ext_tensor(LAZY_P, seed=3, n_ticks=12, lam=3.0)
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(LAZY_P, jax.random.fold_in(key, 1))
    sa, fa = network_run(init_network(LAZY_P, key), conn, ext, LAZY_P,
                         chunk=12, worklist=False, backend="pallas_interpret")
    sb, fb = network_run(init_network(LAZY_P, key), conn, ext, LAZY_P,
                         chunk=12, worklist=True, fused_cols=True,
                         backend="pallas_interpret")
    assert (np.asarray(fa) >= 0).sum() > 0
    _assert_bitwise(sa, fa, sb, fb)


# 300 HCUs, so the fired batch can hold more than one lane tile's 128
# entries; at out_rate 0.5 about 150 HCUs fire a tick, so valid entries sit
# past slot 128
WIDE_P = BCPNNParams(n_hcu=300, rows=16, cols=16, fanout=8, active_queue=8,
                     max_delay=8, out_rate=0.5)


def _pallas_kernels(jaxpr):
    """(kernel function name, grid) of every pallas_call in a jaxpr, nested
    jaxprs (scan, cond, pjit bodies) included."""
    found = []
    for eq in jaxpr.eqns:
        if eq.primitive.name == "pallas_call":
            found.append((eq.params["jaxpr"].debug_info.func_name,
                          tuple(eq.params["grid_mapping"].grid)))
        for v in eq.params.values():
            for x in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    found += _pallas_kernels(sub)
    return found


@pytest.mark.parametrize("cap,xr", [(130, None), (300, None), (130, 7)])
def test_fused_cols_megakernel_large_fired_batch_fallback(cap, xr):
    """A fired-batch capacity larger than one lane tile (cap_fire > 128; 300
    spans three tiles) takes the column megakernel too, on the flat planes
    and on a TPU-degenerate blocked layout (xr=7: row padding): the chunk
    holds the `_fused_col_kernel` call over cap entries and no batched
    column kernel, and the trajectory (drops at cap 130 included) stays
    bitwise against the vmapped pallas-interpret path."""
    from repro.core import layout as L
    p = WIDE_P
    lay = None if xr is None else L.BlockedLayout(rows=p.rows, cols=p.cols,
                                                  xr=xr, xc=128)
    assert lay is None or lay.tpu_degenerate
    ext = _ext_tensor(p, seed=3, n_ticks=2, lam=3.0)
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(p, jax.random.fold_in(key, 1))
    kw = dict(chunk=2, cap_fire=cap, backend="pallas_interpret")
    chunk = jax.make_jaxpr(lambda s, c, e: N._run_chunk(
        s, c, e, p, eager=False, merged=False, backend="pallas_interpret",
        cap_fire=cap, worklist=True, fused=None, fused_cols=True,
        layout=lay))(init_network(p, key, layout=lay), conn, ext)
    kernels = dict(_pallas_kernels(chunk.jaxpr))
    rows = p.rows if lay is None else lay.padded_rows
    bs = next(b for b in (8, 4, 2, 1) if rows % b == 0)
    assert kernels.get("_fused_col_kernel") == (cap, rows // bs), kernels
    assert "_col_kernel" not in kernels, kernels
    sa, fa = network_run(init_network(p, key), conn, ext, p, worklist=False,
                         **kw)
    sb, fb = network_run(init_network(p, key, layout=lay), conn, ext, p,
                         worklist=True, fused_cols=True, layout=lay, **kw)
    if lay is not None:
        sb = sb._replace(hcus=L.load_hcus(sb.hcus, lay))
    assert ((np.asarray(fa) >= 0).sum(axis=1) > 128).all(), \
        "case must put valid entries past slot 128"
    _assert_bitwise(sa, fa, sb, fb)


# ----------------------------- unit tests ------------------------------------

def test_build_worklist_compaction_and_dedup_sentinels():
    rows_u = jnp.asarray([[1, 4, 64, 64],      # 2 valid
                          [64, 64, 64, 64],    # empty HCU
                          [0, 63, 64, 64]],    # 2 valid
                         jnp.int32)
    g_row, order, nv = WL.build_worklist(rows_u, 64)
    assert int(nv) == 4
    got = np.asarray(g_row)[np.asarray(order)[:4]]
    np.testing.assert_array_equal(got, [1, 4, 128, 191])
    # padding slots carry the H*R sentinel
    assert np.asarray(g_row)[2] == 3 * 64


def test_compact_mask_matches_stable_argsort():
    rng = np.random.default_rng(0)
    for _ in range(16):
        mask = jnp.asarray(rng.random(23) < 0.4)
        order, count = WL.compact_mask(mask)
        ref = np.argsort(~np.asarray(mask), kind="stable")
        k = int(count)
        assert k == int(np.asarray(mask).sum())
        np.testing.assert_array_equal(np.asarray(order)[:k], ref[:k])


def test_use_worklist_guard():
    assert not H.use_worklist(LAZY_P)                      # 64*16 cells
    assert H.use_worklist(BCPNNParams(n_hcu=2, rows=1200, cols=70))
    assert H.use_worklist(LAZY_P, override=True)
    assert not H.use_worklist(BCPNNParams(n_hcu=2, rows=1200, cols=70),
                              override=False)


def test_use_fused_cols_guard():
    assert H.use_fused_cols(LAZY_P)                        # default on
    assert not H.use_fused_cols(LAZY_P, override=False)
    assert H.use_fused_cols(LAZY_P, override=True)
