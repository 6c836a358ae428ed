"""The tick's named phase scopes reach the compiled program's op_names.

`engine.tick` splits its body into `tick/queue`, `tick/rows`, `tick/wta`
and `tick/cols`, and the Pallas wrappers in `kernels/ops.py` open
`lane_pad`; a device trace attributes time by these paths (bench/scopes.py),
so every driver's compiled HLO has to carry them in `metadata.op_name`."""
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import init_network, make_connectivity
from repro.core import network as N
from repro.core.params import BCPNNParams

SRC = str(Path(__file__).resolve().parents[1] / "src")
P = BCPNNParams(n_hcu=8, rows=64, cols=16, fanout=8, active_queue=12)
PHASES = ("tick/queue", "tick/rows", "tick/wta", "tick/cols")


def op_names(text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', text))


def chunk_hlo(p, backend, worklist, merged=False) -> str:
    key = jax.random.PRNGKey(0)
    st = jax.eval_shape(lambda k: init_network(p, k, merged=merged), key)
    conn = jax.eval_shape(lambda k: make_connectivity(p, k), key)
    ext = jax.ShapeDtypeStruct((3, p.n_hcu, 2), jnp.int32)
    return N._run_chunk.lower(
        st, conn, ext, p, eager=False, merged=merged, backend=backend,
        cap_fire=None, worklist=worklist, fused=None, fused_cols=None,
        layout=None).compile().as_text()


@pytest.mark.parametrize("backend,worklist,merged", [
    ("ref", True, False), ("pallas_interpret", True, False),
    ("ref", False, False), ("ref", True, True)],
    ids=["worklist", "worklist-pallas", "dense", "worklist-merged"])
def test_run_chunk_op_names_carry_the_tick_phases(backend, worklist, merged):
    names = op_names(chunk_hlo(P, backend, worklist, merged))
    # a merged tick runs its column flush inside the one row call
    for phase in PHASES[:3] if merged else PHASES:
        assert any(f"/{phase}/" in n for n in names), phase
    # the phases are disjoint: no op sits under two of them
    for n in names:
        assert sum(f"/{ph}/" in n + "/" for ph in PHASES) <= 1, n
    if backend == "pallas_interpret":
        for phase in ("tick/rows", "tick/cols"):
            assert any(re.search(f"/{phase}/.*lane_pad/", n)
                       for n in names), phase
    else:
        assert not any("/lane_pad/" in n for n in names)


SCRIPT = textwrap.dedent("""
    import os, re, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, jax.numpy as jnp
    from repro.core import init_network, make_connectivity
    from repro.core import distributed as DD
    from repro.core.params import BCPNNParams

    p = BCPNNParams(n_hcu=8, rows=64, cols=16, fanout=8, active_queue=12)
    key = jax.random.PRNGKey(0)
    mesh = jax.make_mesh((4,), ("hcu",))
    st, conn = DD.shard_network(mesh, init_network(p, key),
                                make_connectivity(p, jax.random.fold_in(key,
                                                                        1)))
    ext = jnp.full((3, p.n_hcu, 2), p.rows, jnp.int32)
    fn = DD.make_dist_run(mesh, p, DD.default_route_config(p, 2),
                          worklist=True)
    text = fn.lower(st, conn, ext).compile().as_text()
    a2a = [m.group(1) for m in re.finditer(
        r"all-to-all[^\\n]*op_name=\\"([^\\"]*)\\"", text)]
    print("RESULT " + json.dumps(dict(
        names=sorted(set(re.findall(r'op_name="([^"]*)"', text))),
        all_to_all=a2a)))
""")


def test_sharded_run_op_names_carry_the_tick_phases():
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=600,
                         env=dict(os.environ, PYTHONPATH=SRC,
                                  JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.split("RESULT ", 1)[1])
    for phase in PHASES:
        assert any(f"/{phase}/" in n for n in res["names"]), phase
    # the spike exchange is part of the queue phase
    assert res["all_to_all"]
    assert all("/tick/queue/" in n for n in res["all_to_all"])
