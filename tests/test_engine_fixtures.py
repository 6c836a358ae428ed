"""Trajectory contract: the runtime reproduces pinned trajectories bit for
bit.

tests/fixtures/head_*.npz hold trajectories captured on JAX 0.9.0, XLA:CPU
(see tests/fixtures/capture_head.py): staged input, connectivity, fired
history, and every NetworkState leaf (ij planes stored in the canonical flat
(H*R, C) layout). The live runtime must reproduce them bit for bit in every
mode — lazy / eager / merged, dense and worklist backends, scan and
host-loop drivers, local and sharded.

Bitwise identity holds within one JAX build and one backend. If one of these
fails after an INTENTIONAL trajectory change, or after the installed JAX
changed, regenerate the fixtures (and say so in the PR) — capture_head.py
says how, and what to check before committing them.
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

from repro.core import (Connectivity, init_network, network_run, run,
                        test_scale as tiny_scale)
from repro.core.params import BCPNNParams

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

# must match tests/fixtures/capture_head.py
LAZY_P = tiny_scale(n_hcu=4, rows=64, cols=16)
MERGED_P = BCPNNParams(n_hcu=4, rows=24, cols=16, fanout=4, active_queue=8,
                       max_delay=8, out_rate=0.6)


def _conn(d):
    return Connectivity(jnp.asarray(d["conn_dest_hcu"]),
                        jnp.asarray(d["conn_dest_row"]),
                        jnp.asarray(d["conn_delay"]))


def _assert_matches(state, fired, d, name):
    np.testing.assert_array_equal(np.asarray(fired), d["fired"],
                                  err_msg=f"{name}: fired history")
    for f in state.hcus._fields:
        np.testing.assert_array_equal(np.asarray(getattr(state.hcus, f)),
                                      d[f"hcus_{f}"],
                                      err_msg=f"{name}: plane {f}")
    np.testing.assert_array_equal(np.asarray(state.delay_rows),
                                  d["delay_rows"], err_msg=name)
    np.testing.assert_array_equal(np.asarray(state.delay_count),
                                  d["delay_count"], err_msg=name)
    assert int(state.t) == int(d["t"])
    assert int(state.drops_in) == int(d["drops_in"])
    assert int(state.drops_fire) == int(d["drops_fire"])
    if "jring" in d:
        np.testing.assert_array_equal(np.asarray(state.jring), d["jring"],
                                      err_msg=name)


CASES = {
    # name: (params, kwargs, host-loop?)
    "lazy_dense": (LAZY_P, dict(worklist=False), False),
    "lazy_worklist": (LAZY_P, dict(worklist=True), False),
    "eager": (LAZY_P, dict(eager=True), False),
    "merged_dense": (MERGED_P, dict(merged=True, worklist=False,
                                    cap_fire=MERGED_P.n_hcu), False),
    "merged_worklist": (MERGED_P, dict(merged=True, worklist=True,
                                       cap_fire=MERGED_P.n_hcu), False),
    "host_lazy": (LAZY_P, dict(worklist=False), True),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_trajectory_matches_pre_refactor(name):
    p, kw, host = CASES[name]
    d = np.load(FIXTURES / f"head_{name}.npz")
    conn = _conn(d)
    ext = jnp.asarray(d["ext"])
    state = init_network(p, jax.random.PRNGKey(0),
                         merged=kw.get("merged", False))
    if host:
        state, fired = run(state, conn, lambda t: ext[t - 1], ext.shape[0],
                           p, **kw)
    else:
        state, fired = network_run(state, conn, ext, p, chunk=13, **kw)
    assert (np.asarray(fired) >= 0).sum() > 0, "fixture must exercise spikes"
    _assert_matches(state, fired, d, name)


LAYOUT_CASES = [c for c in ("lazy_dense", "lazy_worklist", "merged_dense",
                            "merged_worklist")]


@pytest.mark.parametrize("name", LAYOUT_CASES)
@pytest.mark.parametrize("tile", [(8, 4), (7, 5)])
def test_trajectory_layout_invariant(name, tile):
    """The PR 8 contract: plane storage order is NOT semantics. The same
    fixtures that pin the flat runtime must reproduce bitwise when the
    planes are stored column-blocked (Row-Merge tiles) — including a
    non-divisible tile, where pad cells exist but never feed compute."""
    from repro.core import layout as L
    p, kw, _ = CASES[name]
    lay = L.BlockedLayout(rows=p.rows, cols=p.cols, xr=tile[0], xc=tile[1])
    d = np.load(FIXTURES / f"head_{name}.npz")
    state = init_network(p, jax.random.PRNGKey(0),
                         merged=kw.get("merged", False), layout=lay)
    state, fired = network_run(state, _conn(d), jnp.asarray(d["ext"]), p,
                               chunk=13, layout=lay, **kw)
    state = state._replace(hcus=L.load_hcus(state.hcus, lay))
    _assert_matches(state, fired, d, f"{name}:blocked{tile}")


def test_sharded_trajectory_matches_pre_refactor():
    """Both sharded backends vs the sharded fixtures captured on JAX 0.9.0
    (subprocess: device count must be set before jax initializes)."""
    script = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import Connectivity, init_network, test_scale
        from repro.core import distributed as DD

        p = test_scale(n_hcu=8, rows=64, cols=16)
        key = jax.random.PRNGKey(0)
        mesh = jax.make_mesh((4,), ("hcu",))
        rc = DD.default_route_config(p, 2)
        FIXTURES = os.environ["REPRO_FIXTURES_DIR"]
        for name, wl in (("sharded_dense", False), ("sharded_worklist", True)):
            d = np.load(FIXTURES + f"/head_{name}.npz")
            conn = Connectivity(jnp.asarray(d["conn_dest_hcu"]),
                                jnp.asarray(d["conn_dest_row"]),
                                jnp.asarray(d["conn_delay"]))
            s0, c0 = DD.shard_network(mesh, init_network(p, key), conn)
            fn = DD.make_dist_run(mesh, p, rc, axis="hcu", worklist=wl)
            s1, f1 = fn(s0, c0, jnp.asarray(d["ext"]))
            np.testing.assert_array_equal(np.asarray(f1), d["fired"],
                                          err_msg=name)
            for f in s1.hcus._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(s1.hcus, f)), d[f"hcus_{f}"],
                    err_msg=f"{name}:{f}")
            np.testing.assert_array_equal(np.asarray(s1.delay_rows),
                                          d["delay_rows"], err_msg=name)
        print("SHARDED-FIXTURES-OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True,
                       env={**os.environ, "PYTHONPATH": SRC,
                            "REPRO_FIXTURES_DIR": str(FIXTURES)})
    assert "SHARDED-FIXTURES-OK" in r.stdout, r.stdout + r.stderr[-3000:]
