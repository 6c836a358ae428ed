"""TickEngine backend selection, the Simulator facade, and checkpoint
round-trips through the canonical flat layout.

Checkpoint contract (the paper's restartability requirement at 1000-node
scale): save -> load -> continue must be bitwise-identical to an
uninterrupted run — in lazy, merged and sharded modes — and pre-refactor
(H, R, C)-layout checkpoints must load through the migration shim
(`checkpoint.restore_network`) and continue bit-exactly too.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore, restore_network, save
from repro.core import (DenseBackend, Simulator, WorklistBackend, hcu_view,
                        init_network, make_connectivity, network_run,
                        select_backend,
                        test_scale as tiny_scale)
from repro.core import hcu as H
from repro.core.params import BCPNNParams

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

LAZY_P = tiny_scale(n_hcu=4, rows=64, cols=16)
MERGED_P = BCPNNParams(n_hcu=4, rows=24, cols=16, fanout=4, active_queue=8,
                       max_delay=8, out_rate=0.6)


def _ext_tensor(p, seed, n_ticks, width=8, lam=3.0):
    rng = np.random.default_rng(seed)
    out = np.full((n_ticks, p.n_hcu, width), p.rows, np.int32)
    for t in range(n_ticks):
        for h in range(p.n_hcu):
            n = min(width, rng.poisson(lam))
            out[t, h, :n] = rng.integers(0, p.rows, n)
    return jnp.asarray(out)


def _assert_state_equal(sa, sb, merged=False):
    for name in sa.hcus._fields:
        np.testing.assert_array_equal(np.asarray(getattr(sa.hcus, name)),
                                      np.asarray(getattr(sb.hcus, name)),
                                      err_msg=f"plane {name}")
    np.testing.assert_array_equal(np.asarray(sa.delay_rows),
                                  np.asarray(sb.delay_rows))
    np.testing.assert_array_equal(np.asarray(sa.delay_count),
                                  np.asarray(sb.delay_count))
    assert int(sa.t) == int(sb.t)
    assert int(sa.drops_in) == int(sb.drops_in)
    assert int(sa.drops_fire) == int(sb.drops_fire)
    if merged:
        np.testing.assert_array_equal(np.asarray(sa.jring),
                                      np.asarray(sb.jring))


# ----------------------------- backend selection -----------------------------

def test_select_backend_mirrors_use_worklist_guard():
    assert isinstance(select_backend(LAZY_P), DenseBackend)
    big = BCPNNParams(n_hcu=2, rows=1200, cols=70)
    assert isinstance(select_backend(big), WorklistBackend)
    assert isinstance(select_backend(LAZY_P, worklist=True), WorklistBackend)
    assert isinstance(select_backend(big, worklist=False), DenseBackend)
    # the eager golden reference is dense by definition
    assert select_backend(big, eager=True) == DenseBackend(mode="eager")
    assert select_backend(big, merged=True) == WorklistBackend(mode="merged")
    assert select_backend(LAZY_P, merged=True) == DenseBackend(mode="merged")
    # backends are hashable value objects (static jit args)
    assert hash(select_backend(LAZY_P)) == hash(DenseBackend())


# ----------------------------- Simulator facade ------------------------------

def test_simulator_matches_hand_wired_runtime():
    """Simulator.run == init_network + make_connectivity + network_run."""
    ext = _ext_tensor(LAZY_P, seed=5, n_ticks=30)
    sim = Simulator(LAZY_P, key=0)
    f_sim = sim.run(ext)

    key = jax.random.PRNGKey(0)
    conn = make_connectivity(LAZY_P, jax.random.fold_in(key, 1))
    st, f_ref = network_run(init_network(LAZY_P, key), conn, ext, LAZY_P)
    np.testing.assert_array_equal(np.asarray(f_sim), np.asarray(f_ref))
    _assert_state_equal(sim.state, st)


def test_simulator_tick_and_views():
    sim = Simulator(LAZY_P, key=0)
    ext = np.full((LAZY_P.n_hcu, 4), LAZY_P.rows, np.int32)
    ext[0, 0] = 3
    fired = sim.tick(jnp.asarray(ext))
    assert fired.shape == (LAZY_P.n_hcu,)
    assert int(sim.state.t) == 1
    hb = sim.hcus()
    assert hb.zij.shape == (LAZY_P.n_hcu, LAZY_P.rows, LAZY_P.cols)
    fl = sim.flushed()
    assert bool(jnp.all(jnp.isfinite(fl.wij)))


# ----------------------------- checkpoint round-trips ------------------------

@pytest.mark.parametrize("mode", ["lazy", "merged"])
def test_checkpoint_roundtrip_continues_bitwise(mode, tmp_path):
    """save -> load -> continue == uninterrupted run, to the last bit."""
    merged = mode == "merged"
    p = MERGED_P if merged else LAZY_P
    ext = _ext_tensor(p, seed=9, n_ticks=40, lam=4.0)
    kw = dict(merged=merged, cap_fire=p.n_hcu if merged else None)
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(p, jax.random.fold_in(key, 1))

    st = init_network(p, key, merged=merged)
    st, _ = network_run(st, conn, ext[:15], p, **kw)
    save(str(tmp_path), 15, st)
    st_a, fired_a = network_run(st, conn, ext[15:], p, **kw)

    st_b = restore_network(str(tmp_path), 15, init_network(p, key,
                                                           merged=merged))
    st_b, fired_b = network_run(st_b, conn, ext[15:], p, **kw)
    np.testing.assert_array_equal(np.asarray(fired_a), np.asarray(fired_b))
    assert (np.asarray(fired_a) >= 0).sum() > 0
    _assert_state_equal(st_a, st_b, merged=merged)


def test_checkpoint_roundtrip_sharded_bitwise(tmp_path):
    """Sharded run -> save (gathers shards) -> restore -> reshard ->
    continue == uninterrupted sharded run (subprocess: 4 host devices)."""
    script = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from repro.checkpoint import restore_network, save
        from repro.core import init_network, make_connectivity, test_scale
        from repro.core import distributed as DD

        p = test_scale(n_hcu=8, rows=64, cols=16)
        key = jax.random.PRNGKey(0)
        conn = make_connectivity(p, jax.random.fold_in(key, 1))
        mesh = jax.make_mesh((4,), ("hcu",))
        rc = DD.default_route_config(p, 2)
        fn = DD.make_dist_run(mesh, p, rc, axis="hcu")
        rng = np.random.default_rng(13)
        ext = np.full((30, p.n_hcu, 8), p.rows, np.int32)
        for t in range(30):
            for h in range(p.n_hcu):
                n = min(8, rng.poisson(3))
                ext[t, h, :n] = rng.integers(0, p.rows, n)
        ext = jnp.asarray(ext)

        s, c = DD.shard_network(mesh, init_network(p, key), conn)
        s, _ = fn(s, c, ext[:12])
        save({ckpt!r}, 12, s)
        s_a, f_a = fn(s, c, ext[12:])

        s_b = restore_network({ckpt!r}, 12, init_network(p, key))
        s_b, c_b = DD.shard_network(mesh, s_b, conn)
        s_b, f_b = fn(s_b, c_b, ext[12:])
        np.testing.assert_array_equal(np.asarray(f_a), np.asarray(f_b))
        assert (np.asarray(f_a) >= 0).sum() > 0
        for name in s_a.hcus._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(s_a.hcus, name)),
                np.asarray(getattr(s_b.hcus, name)), err_msg=name)
        np.testing.assert_array_equal(np.asarray(s_a.delay_rows),
                                      np.asarray(s_b.delay_rows))
        print("SHARDED-CKPT-OK")
    """).format(ckpt=str(tmp_path / "ckpt"))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": SRC})
    assert "SHARDED-CKPT-OK" in r.stdout, r.stdout + r.stderr[-3000:]


def test_legacy_layout_checkpoint_migrates_and_continues_bitwise():
    """A real pre-refactor checkpoint (tests/fixtures/legacy_ckpt, saved by
    the (H, R, C)-layout runtime at t=10) loads through the one-call shim
    and continues bitwise like the pinned continuation in
    legacy_ckpt_ext.npz (captured on JAX 0.9.0, XLA:CPU by
    tests/fixtures/capture_head.py)."""
    p = tiny_scale(n_hcu=2, rows=32, cols=16)
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(p, jax.random.fold_in(key, 1))
    d = np.load(FIXTURES / "legacy_ckpt_ext.npz")
    ext = jnp.asarray(d["ext"])

    # the raw restore must refuse the layout mismatch...
    with pytest.raises(ValueError):
        restore(str(FIXTURES / "legacy_ckpt"), 10, init_network(p, key))
    # ...and the shim must fix it
    st = restore_network(str(FIXTURES / "legacy_ckpt"), 10,
                         init_network(p, key))
    assert st.hcus.zij.shape == (p.n_hcu * p.rows, p.cols)
    assert int(st.t) == 10
    st, fired = network_run(st, conn, ext[10:], p)

    np.testing.assert_array_equal(np.asarray(fired), d["fired_cont"])
    for name in st.hcus._fields:
        np.testing.assert_array_equal(np.asarray(getattr(st.hcus, name)),
                                      d[f"hcus_{name}"],
                                      err_msg=f"plane {name}")
    np.testing.assert_array_equal(np.asarray(st.delay_rows), d["delay_rows"])
    np.testing.assert_array_equal(np.asarray(st.delay_count),
                                  d["delay_count"])
    assert int(st.t) == int(d["t"]) == 30
    assert int(st.drops_in) == int(d["drops_in"])
    assert int(st.drops_fire) == int(d["drops_fire"])


def test_simulator_save_load_roundtrip(tmp_path):
    """The facade's save/load pair continues bitwise too."""
    ext = _ext_tensor(LAZY_P, seed=3, n_ticks=24)
    sim = Simulator(LAZY_P, key=0)
    sim.run(ext[:12])
    sim.save(str(tmp_path))
    f_a = sim.run(ext[12:])
    state_a = sim.state

    sim2 = Simulator(LAZY_P, key=0).load(str(tmp_path))
    assert int(sim2.state.t) == 12
    f_b = sim2.run(ext[12:])
    np.testing.assert_array_equal(np.asarray(f_a), np.asarray(f_b))
    _assert_state_equal(state_a, sim2.state)


def test_migrate_shim_passes_canonical_checkpoints_through(tmp_path):
    """restore_network on an already-flat checkpoint is a plain restore."""
    st = init_network(LAZY_P, jax.random.PRNGKey(0))
    save(str(tmp_path), 0, st)
    r = restore_network(str(tmp_path), 0, st)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hcu_view_roundtrip():
    """flat_state(batched_state(x)) is the identity on canonical state."""
    from repro.core import batched_state, flat_state
    st = init_network(LAZY_P, jax.random.PRNGKey(0))
    hb = hcu_view(st)
    assert hb.zij.shape == (LAZY_P.n_hcu, LAZY_P.rows, LAZY_P.cols)
    assert hb.zi.shape == (LAZY_P.n_hcu, LAZY_P.rows)
    back = flat_state(hb)
    for a, b in zip(st.hcus, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # init matches the tiled per-HCU init exactly
    ref = flat_state(jax.vmap(lambda _: H.init_hcu_state(LAZY_P))(
        jnp.arange(LAZY_P.n_hcu)))
    for a, b in zip(st.hcus, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
