"""The correctness check fails what it must fail, on the CPU at a small size.

A subprocess drives the rest of a benchmark run (`harness.run`: set-up,
window, reference replay, comparison) with the look for a chip skipped, on
rodent-width cells of 8 HCUs (one device) and 4 x 4 HCUs (four virtual
devices), under the limits of the real rodent configurations. It runs each
cell sound, then with the timed path broken underneath, and records
`correct`:

  state_unchanged   every tick returns its state unchanged
  half_batch        every other entry of the fired batch left out
  answer_altered    each WTA winner altered where it is produced
  exchange_left_out the spike all-to-all between devices left out

It also runs the bfloat16 control (`control.readings`), which has to fail
one of the limits that the sound run passes.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent

SCRIPT = r'''
import json, sys, time, types
from pathlib import Path
root = Path(sys.argv[1]); repo = Path(sys.argv[2]); cases = sys.argv[3:]
sys.path.insert(0, str(repo / "bench"))
sys.path.insert(0, str(repo / "src"))
import jax
import harness, control
from repro.core import distributed as DD, engine as E, hcu as H
from repro.core import network as N

harness.require_chips = lambda n: jax.devices()[:n]
harness.peak_memory = lambda devs: 1
_peak = harness.peak_of
harness.peak_of = lambda kind: _peak("TPU v5 lite")
# lossless routes at these few HCUs per device (the Poisson-sized default
# route capacity drops at small meshes); the fired-batch cap is unchanged
_rc = DD.default_route_config
DD.default_route_config = lambda p, h, n=None: DD.RouteConfig(
    cap_fire=_rc(p, h, n).cap_fire, cap_route=h * p.fanout)

def state_unchanged():
    orig = E.tick
    def tick(state, *a, **k):
        new, fired = orig(state, *a, **k)
        return state._replace(t=new.t), fired
    E.tick = tick

def half_batch():
    orig = N.select_fired
    def select(fired, cap):
        h, j, nd = orig(fired, cap)
        return h.at[1::2].set(fired.shape[0]), j, nd
    N.select_fired = select

def answer_altered():
    orig = H.periodic_math
    def pm(*a, **k):
        h, f = orig(*a, **k)
        return h, jax.numpy.where(f >= 0, (f + 1) % h.shape[-1], f)
    H.periodic_math = pm

def exchange_left_out():
    jax.lax.all_to_all = lambda x, *a, **k: x

out = {}
saved = (E.tick, N.select_fired, H.periodic_math, jax.lax.all_to_all)
for case in cases:
    wl, fault = case.split(":")
    E.tick, N.select_fired, H.periodic_math, jax.lax.all_to_all = saved
    jax.clear_caches()
    if fault == "control":
        spec = harness.load_spec(root)
        c = harness.cell(spec, root, wl)
        harness.import_program(root)
        harness.setup_jax(root)
        r = harness.Run(c, 77, jax.devices()[:int(c["workload"]["chips"])])
        r.window(0.2)
        out[case] = control.readings(r, jax.devices()[0])
        continue
    if fault != "sound":
        globals()[fault]()
    args = types.SimpleNamespace(workload=wl, seed=2**31 + 11, seconds=0.2,
                                 trace=0)
    res = harness.run(args, root, time.perf_counter())
    out[case] = {"correct": res["correct"], "checks": res["checks"]}
print("RESULT " + json.dumps(out))
'''


def make_root(tmp: Path) -> Path:
    """A checkout holding the tiny cells, the benchmark's own files and the
    repo's `src`."""
    (tmp / "bench").mkdir()
    for d in ("traffic", "metrics"):
        (tmp / "bench" / d).symlink_to(BENCH / d)
    (tmp / "bench" / "configs").mkdir()
    (tmp / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"], spec["workloads"] = [], []
    for name, base, n in (("tiny1", "rodent_share1152", 8),
                          ("tiny4", "rodent_host4x1152", 16)):
        cfg = json.loads((BENCH / "configs" / f"{base}.json").read_text())
        cfg["params"]["n_hcu"] = n
        cfg["name"] = name
        f = f"bench/configs/{name}.json"
        (tmp / f).write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "test", "file": f,
                                "reduced": ["n_hcu"], "why": "test"})
        spec["workloads"].append({"name": f"{name}.poisson", "config": name,
                                  "traffic": "poisson",
                                  "chips": cfg["chips"], "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def drive(root: Path, cases, devices: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(root), str(REPO),
                        *cases], env=env, capture_output=True, text=True,
                       timeout=600, cwd=root)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


ONE = ["tiny1.poisson:" + f for f in
       ("sound", "state_unchanged", "half_batch", "answer_altered",
        "control")]
FOUR = ["tiny4.poisson:" + f for f in ("sound", "exchange_left_out")]


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("bench_root"))
    out = drive(root, ONE, 1)
    out.update(drive(root, FOUR, 4))
    return out


@pytest.mark.parametrize("case", [ONE[0], FOUR[0]])
def test_sound_run_is_correct(results, case):
    r = results[case]
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("case", ONE[1:4] + FOUR[1:])
def test_fault_is_caught(results, case):
    r = results[case]
    assert not r["correct"], r["checks"]


def test_control_fails_a_limit_the_system_passes(results):
    r = results["tiny1.poisson:control"]
    limits = json.loads((BENCH / "configs" / "rodent_share1152.json")
                        .read_text())["limits"]
    assert all(r["system"][k] <= limits[k] for k in limits), r
    assert any(r["control"][k] > limits[k] for k in limits), r
