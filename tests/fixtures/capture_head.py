"""Capture reference trajectories from the CURRENT runtime into .npz fixtures.

Run from the repo root:

    JAX_PLATFORMS=cpu PYTHONPATH=src:tests python tests/fixtures/capture_head.py

The bitwise-identity tests (tests/test_engine_fixtures.py, and the legacy
checkpoint test in tests/test_engine.py) compare the live runtime against
these files, so the fixtures pin the trajectory of the runtime on the JAX
build they were captured with: JAX 0.9.0, XLA:CPU. Regenerate them when a PR
intentionally changes trajectories, or when the installed JAX changes (and
say so in the PR). Bitwise identity holds within one JAX build and one
backend only: JAX 0.5 changed the default PRNG stream
(`jax_threefry_partitionable`), and XLA:CPU's rounding of the trace math
moves by 1 ulp between releases. After regenerating, confirm that the modes
that share parameters still agree with each other bitwise (lazy vs
worklist, dense vs worklist, local vs sharded) and that the eager fixture
fires the same spikes as the lazy ones.

Fixtures store, per mode: the staged external input, the connectivity arrays,
the fired history, and every NetworkState leaf (ij-planes reshaped to the
canonical flat (H*R, C) layout so comparisons are layout-independent).
`legacy_ckpt_ext.npz` stores the input of the legacy-checkpoint test and the
continuation of the old-layout checkpoint `legacy_ckpt` (which itself is
never rewritten: it is what the migration reads).
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
SRC = str(HERE.parents[1] / "src")
sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.checkpoint import restore_network  # noqa: E402
from repro.core import (init_network, make_connectivity, network_run,  # noqa: E402
                        run)
from repro.core.params import BCPNNParams, test_scale  # noqa: E402

# Must match tests/test_engine_fixtures.py exactly.
LAZY_P = test_scale(n_hcu=4, rows=64, cols=16)
MERGED_P = BCPNNParams(n_hcu=4, rows=24, cols=16, fanout=4, active_queue=8,
                       max_delay=8, out_rate=0.6)


def ext_tensor(p, seed, n_ticks, width=8, lam=3.0):
    rng = np.random.default_rng(seed)
    out = np.full((n_ticks, p.n_hcu, width), p.rows, np.int32)
    for t in range(n_ticks):
        for h in range(p.n_hcu):
            n = min(width, rng.poisson(lam))
            out[t, h, :n] = rng.integers(0, p.rows, n)
    return out


def flat2(x):
    """(H, R, C) -> (H*R, C) / (H, R) -> (H*R,) canonical flat layout."""
    a = np.asarray(x)
    if a.ndim == 3:
        return a.reshape(a.shape[0] * a.shape[1], a.shape[2])
    return a


def state_arrays(state, p):
    out = {}
    for name in state.hcus._fields:
        leaf = np.asarray(getattr(state.hcus, name))
        if name in ("zij", "eij", "pij", "wij", "tij"):
            leaf = leaf.reshape(p.n_hcu * p.rows, p.cols)
        elif name in ("zi", "ei", "pi", "ti"):
            leaf = leaf.reshape(p.n_hcu * p.rows)
        out[f"hcus_{name}"] = leaf
    out["delay_rows"] = np.asarray(state.delay_rows)
    out["delay_count"] = np.asarray(state.delay_count)
    out["t"] = np.asarray(state.t)
    out["drops_in"] = np.asarray(state.drops_in)
    out["drops_fire"] = np.asarray(state.drops_fire)
    if state.jring is not None:
        out["jring"] = np.asarray(state.jring)
    return out


def capture_local(name, p, *, merged=False, eager=False, worklist=None,
                  seed, n_ticks, lam, chunk, cap_fire=None, host=False):
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(p, jax.random.fold_in(key, 1))
    ext = ext_tensor(p, seed, n_ticks, lam=lam)
    state = init_network(p, key, merged=merged)
    kw = dict(eager=eager, merged=merged, worklist=worklist,
              cap_fire=cap_fire)
    if host:
        ext_j = jnp.asarray(ext)
        state, fired = run(state, conn, lambda t: ext_j[t - 1], n_ticks, p,
                           **kw)
    else:
        state, fired = network_run(state, conn, jnp.asarray(ext), p,
                                   chunk=chunk, **kw)
    data = state_arrays(state, p)
    data.update(ext=ext, fired=np.asarray(fired),
                conn_dest_hcu=np.asarray(conn.dest_hcu),
                conn_dest_row=np.asarray(conn.dest_row),
                conn_delay=np.asarray(conn.delay))
    np.savez_compressed(HERE / f"head_{name}.npz", **data)
    print(f"captured {name}: {int((np.asarray(fired) >= 0).sum())} spikes, "
          f"t={int(state.t)}")


SHARDED_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {src!r})
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import init_network, make_connectivity, test_scale
    from repro.core import distributed as DD
    sys.path.insert(0, {fixtures!r})
    from capture_head import ext_tensor, state_arrays

    p = test_scale(n_hcu=8, rows=64, cols=16)
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(p, jax.random.fold_in(key, 1))
    mesh = jax.make_mesh((4,), ("hcu",))
    rc = DD.default_route_config(p, 2)
    ext = ext_tensor(p, seed=7, n_ticks=25, lam=3.0)
    for wl in (False, True):
        s0, c0 = DD.shard_network(mesh, init_network(p, key), conn)
        fn = DD.make_dist_run(mesh, p, rc, axis="hcu", worklist=wl)
        s1, f1 = fn(s0, c0, jnp.asarray(ext))
        data = state_arrays(s1, p)
        data.update(ext=ext, fired=np.asarray(f1),
                    conn_dest_hcu=np.asarray(conn.dest_hcu),
                    conn_dest_row=np.asarray(conn.dest_row),
                    conn_delay=np.asarray(conn.delay))
        name = "sharded_worklist" if wl else "sharded_dense"
        np.savez_compressed(os.path.join({fixtures!r}, f"head_{{name}}.npz"),
                            **data)
        print(f"captured {{name}}: {{int((np.asarray(f1) >= 0).sum())}} spikes")
""")


def capture_legacy_continuation():
    """Continue the old-layout checkpoint (t=10) to the end of its input and
    store the fired history and final state next to that input."""
    path = HERE / "legacy_ckpt_ext.npz"
    d = dict(np.load(path))
    p = test_scale(n_hcu=2, rows=32, cols=16)
    key = jax.random.PRNGKey(0)
    conn = make_connectivity(p, jax.random.fold_in(key, 1))
    state = restore_network(str(HERE / "legacy_ckpt"), 10,
                            init_network(p, key))
    state, fired = network_run(state, conn, jnp.asarray(d["ext"][10:]), p)
    keep = {k: d[k] for k in ("ext", "fired_prefix")}
    np.savez_compressed(path, **keep, fired_cont=np.asarray(fired),
                        **state_arrays(state, p))
    print(f"captured legacy continuation: "
          f"{int((np.asarray(fired) >= 0).sum())} spikes, t={int(state.t)}")


def main():
    capture_legacy_continuation()
    capture_local("lazy_dense", LAZY_P, worklist=False, seed=11, n_ticks=40,
                  lam=3.0, chunk=13)
    capture_local("lazy_worklist", LAZY_P, worklist=True, seed=11, n_ticks=40,
                  lam=3.0, chunk=13)
    capture_local("eager", LAZY_P, eager=True, seed=11, n_ticks=40, lam=3.0,
                  chunk=13)
    capture_local("merged_dense", MERGED_P, merged=True, worklist=False,
                  seed=7, n_ticks=60, lam=5.0, chunk=13,
                  cap_fire=MERGED_P.n_hcu)
    capture_local("merged_worklist", MERGED_P, merged=True, worklist=True,
                  seed=7, n_ticks=60, lam=5.0, chunk=13,
                  cap_fire=MERGED_P.n_hcu)
    capture_local("host_lazy", LAZY_P, worklist=False, seed=11, n_ticks=20,
                  lam=3.0, chunk=0, host=True)
    script = SHARDED_SCRIPT.format(src=SRC, fixtures=str(HERE))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": SRC})
    print(r.stdout)
    if r.returncode != 0:
        sys.exit("sharded capture failed:\n" + r.stderr[-3000:])


if __name__ == "__main__":
    main()
