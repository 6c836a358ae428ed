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
  chip2_unchanged   device 2 alone returns its share of the state
                    unchanged, so the check has to compare every device
  route_drops       routes of 20 spikes between devices, which drop part
                    of the fan-out: the reference does not model them, so
                    a dropped spike has to fail the check, and `failed`
                    counts it

It also runs the bfloat16 control (`control.readings`), which has to fail
one of the limits that the sound run passes, on one device and on four.
And it checks the check itself: the values it reads after a fixed number
of ticks (pinned from the whole-network replay on one device that came
before the replay by shares); the replay by shares on four devices against
the whole-network replay on one, bit for bit; and `failed` on four devices
as the sum of every device's own drop counters.
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
import jax.numpy as jnp
import numpy as np
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

def chip2_unchanged():
    orig = DD._local_tick
    def local_tick(state, conn, ext, **k):
        new, fired = orig(state, conn, ext, **k)
        keep = jax.lax.axis_index(k["axis"]) == 2
        return new._replace(hcus=jax.tree.map(
            lambda a, b: jnp.where(keep, a, b), state.hcus, new.hcus)), fired
    DD._local_tick = local_tick

def route_drops():
    DD.default_route_config = lambda p, h, n=None: DD.RouteConfig(
        cap_fire=_rc(p, h, n).cap_fire, cap_route=20)

def set_up(wl, seed):
    spec = harness.load_spec(root)
    c = harness.cell(spec, root, wl)
    harness.import_program(root)
    harness.setup_jax(root)
    return c, harness.Run(c, seed, jax.devices()[:int(c["workload"]["chips"])])

def ticks(r, n_chunks):
    """Run to a fixed number of chunks, not for a time."""
    for k in range(len(r.fired), n_chunks):
        r.fired.append(r.prog(r.chunks[k]))
    return r.history()

def control_(wl):
    c, r = set_up(wl, 77)
    r.window(0.2)
    return control.readings(r)

def fixed(wl):
    c, r = set_up(wl, 2**31 + 11)
    checks, stats = harness.check(r.m, r.prog, r.conn, r.ext,
                                  ticks(r, 6), r.seed, r.chunk, r.devs,
                                  c["config"]["limits"])
    return {"checks": {k: v["value"] for k, v in checks.items()},
            "attempted": harness.attempted(stats, r.m)}

def shares_bitwise(wl):
    c, r = set_up(wl, 2**31 + 13)
    # a history that fires more than each device's fired batch holds and
    # fills the delay queues past their depth, and external rows, which
    # the cell's own mix leaves out
    rng = np.random.default_rng(5)
    T, H, R = 4 * r.chunk, r.m.n_hcu, r.m.rows
    fired = np.where(rng.random((T, H)) < 0.6,
                     rng.integers(0, r.m.cols, (T, H)), -1).astype(np.int32)
    ext = rng.integers(0, R + 1, (r.ext.shape[0], H, 4), dtype=np.int32)
    args = (r.m, r.conn, ext, fired, r.seed, r.chunk)
    (whole,), s1, own1 = harness.replay(*args, r.devs[:1], probes=(fired,))
    shares, s4, own4 = harness.replay(*args, r.devs, probes=(fired,))
    differ = []
    for k in whole._fields:
        b = [np.asarray(getattr(sh, k)) for sh in shares]
        b = (b[0] if k == "now" else sum(b) if k == "drops"
             else np.concatenate(b))
        if not np.array_equal(np.asarray(getattr(whole, k)), b):
            differ.append(k)
    differ += [f"stats.{k}" for k in s1 if not np.array_equal(s1[k], s4[k])]
    if not np.array_equal(own1, own4):
        differ.append("own")
    return {"differ": differ,
            "on_own_chip": [set(sh.z.devices()) == {d}
                            for sh, d in zip(shares, r.devs)],
            "fired": int((fired >= 0).sum()),
            "drops": int(whole.drops)}

def drops_summed(wl):
    devs = jax.devices()[:4]
    mesh = jax.sharding.Mesh(devs, ("hcu",))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    per_chip = lambda vals: jax.make_array_from_single_device_arrays(
        (), rep, [jax.device_put(np.int32(v), d) for v, d in zip(vals, devs)])
    prog = harness.Program.__new__(harness.Program)
    prog.mesh = mesh
    prog.sim = types.SimpleNamespace(state=types.SimpleNamespace(
        drops_in=per_chip([1, 2, 3, 4]), drops_fire=per_chip([0, 10, 0, 20]),
        drops_route=per_chip([100, 0, 300, 0])))
    return {"failed": prog.drops()}

READ = {"control": control_, "fixed": fixed, "shares_bitwise": shares_bitwise,
        "drops_summed": drops_summed}

out = {}
saved = (E.tick, N.select_fired, H.periodic_math, jax.lax.all_to_all,
         DD._local_tick, DD.default_route_config)
for case in cases:
    wl, fault = case.split(":")
    (E.tick, N.select_fired, H.periodic_math, jax.lax.all_to_all,
     DD._local_tick, DD.default_route_config) = saved
    jax.clear_caches()
    if fault in READ:
        out[case] = READ[fault](wl)
        continue
    if fault != "sound":
        globals()[fault]()
    args = types.SimpleNamespace(workload=wl, seed=2**31 + 11, seconds=0.2,
                                 trace=0)
    res = harness.run(args, root, time.perf_counter())
    out[case] = {"correct": res["correct"], "checks": res["checks"],
                 "failed": res["failed"]}
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
        "control", "fixed")]
FOUR = ["tiny4.poisson:" + f for f in
        ("sound", "exchange_left_out", "chip2_unchanged", "state_unchanged",
         "half_batch", "answer_altered", "control", "fixed",
         "shares_bitwise", "drops_summed", "route_drops")]

# The check's values at seed 2**31 + 11 after six 8-tick chunks, read with
# the whole-network replay on one device that the replay by shares
# replaced (XLA:CPU, this JAX build): the shares must not move them.
PINNED = {
    "tiny1.poisson:fixed": {
        "checks": {"state_err": 1.0487611865063197e-07, "wta_gap": 0.0,
                   "fire_mismatch": 0}, "attempted": 4300},
    "tiny4.poisson:fixed": {
        "checks": {"state_err": 1.1799840253762645e-07, "wta_gap": 0.0,
                   "fire_mismatch": 0}, "attempted": 7400},
}


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


@pytest.mark.parametrize("case", ONE[1:4] + FOUR[1:6])
def test_fault_is_caught(results, case):
    r = results[case]
    assert not r["correct"], r["checks"]


def test_fault_on_one_chip_fails_the_state(results):
    """Device 2's share alone is wrong: the comparison of every device's
    share, not the WTA's choices, has to see it."""
    c = results["tiny4.poisson:chip2_unchanged"]["checks"]["state_err"]
    assert c["value"] > c["limit"], c


def control_fails_a_limit_the_system_passes(r):
    limits = json.loads((BENCH / "configs" / "rodent_share1152.json")
                        .read_text())["limits"]
    assert all(r["system"][k] <= limits[k] for k in limits), r
    assert any(r["control"][k] > limits[k] for k in limits), r


def test_control_fails_a_limit_the_system_passes(results):
    control_fails_a_limit_the_system_passes(results["tiny1.poisson:control"])


def test_control_fails_a_limit_on_four_devices(results):
    control_fails_a_limit_the_system_passes(results["tiny4.poisson:control"])


@pytest.mark.parametrize("case", sorted(PINNED))
def test_check_values_unchanged_by_the_shares(results, case):
    assert results[case] == PINNED[case]


def test_replay_by_shares_is_the_whole_replay(results):
    r = results["tiny4.poisson:shares_bitwise"]
    assert r["differ"] == [] and all(r["on_own_chip"]), r
    assert r["fired"] > 0 and r["drops"] > 0, r


def test_route_drop_fails_the_check(results):
    """Routes of 20 spikes drop part of the fan-out in the system; the
    reference delivers every spike, so the state differs past its limit,
    and `failed` counts what the routes dropped."""
    r = results["tiny4.poisson:route_drops"]
    c = r["checks"]["state_err"]
    assert not r["correct"] and c["value"] > c["limit"], r
    assert r["failed"] > 0, r


def test_failed_sums_every_devices_drops(results):
    assert results["tiny4.poisson:drops_summed"]["failed"] == 440
