#!/usr/bin/env python3
"""Bring-up smoke run of the BCPNN tick path on a TPU, through the Pallas
worklist kernels.

    python chip_smoke.py                # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips   # four chips: phase (d) only

  (a) full width  Simulator.run for 256 ticks (two 128-tick scan chunks) at
                  the paper's human widths (R=10000, C=100) with N_HCU
                  hypercolumns, Poisson input at in_rate 10 rows/ms per HCU.
                  Asserts that the compiled chunk holds the row and column
                  Pallas kernels and that the flushed state is sane.
  (b) reference   8 human-width HCUs, 64 ticks, kernel="pallas" against
                  kernel="ref" on the same chip: identical fired histories,
                  flushed planes within RTOL.
  (c) serving     a BCPNNRecallServer at rodent widths (R=1200, C=70)
                  answers 8 recall requests after a short train_assoc.
  (d) four chips  Simulator.run_sharded on a 4-device "hcu" mesh with a
                  lossless route config, 4x32 human-width HCUs, 64 ticks,
                  against Simulator.run of the same network on one chip.

Everything runs in this one process, which owns the chip(s). The script
exits non-zero, and prints no result, when JAX finds no TPU, when the kernel
backend it would run is not "pallas", or when any check fails. The last line
of stdout is printed only after every phase passed:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.

Compile cache: $JAX_COMPILATION_CACHE_DIR when set, else `.jax_cache/` next
to this file.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent

# Largest power of two of human-width HCUs whose Pallas scan chunk fits one
# v5e (15.75 GB usable): 256 needs 17.28 GB, because every kernel call pads
# the five planes to 128 lanes (compile rehearsal for a described v5e).
N_HCU = 128
TICKS, CHUNK = 256, 128
EXT_WIDTH = 24           # wide enough that Poisson(10) input is not clipped
REF_HCU, REF_TICKS = 8, 64
# mixed error |a - b| / max(|b|, 1) allowed between kernel="pallas" and
# kernel="ref" planes (relative above 1, absolute below)
RTOL = 1e-5
SHARD_DEV, SHARD_HCU, SHARD_TICKS = 4, 32, 64


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond, msg: str):
    if not cond:
        fail(msg)


def log(msg: str):
    print(msg, flush=True)


def _import_repo():
    src = ROOT / "src"
    if not (src / "repro" / "core").is_dir():
        fail(f"no repro package under {src}: run from a checkout of the repo")
    sys.path.insert(0, str(src))


def _setup_jax():
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    cache = jax.config.jax_compilation_cache_dir
    entries = len(list(Path(cache).glob("*"))) if Path(cache).is_dir() else 0
    log(f"compile cache: {cache} ({entries} entries at start)")
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX found no TPU (platform {devs[0].platform!r})")
    from repro.kernels import ops
    check(ops.default_backend() == "pallas",
          f"kernel backend is {ops.default_backend()!r}, not 'pallas' "
          f"(REPRO_KERNEL_BACKEND={os.environ.get('REPRO_KERNEL_BACKEND')!r})")
    log(f"device: {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"kernel backend pallas")
    return devs


class Clock:
    """Wall seconds per labelled step; each step ends in block_until_ready."""

    def __init__(self, phase: str):
        self.phase = phase

    def __call__(self, label: str, fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        log(f"  [{self.phase}] {label}: {time.perf_counter() - t0:.3f} s")
        return out


def _custom_calls(text: str) -> set[str]:
    """Names of the Pallas kernels (tpu_custom_call) in compiled HLO text."""
    names = set()
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.match(r"\s*(?:ROOT\s+)?%([A-Za-z_]\w*?)(?:\.\d+)?\s*=", line)
            if m:
                names.add(m.group(1))
    return names


def _column_path(kernels: set[str]) -> str:
    if "fused_col_update_kernel_call" in kernels:
        return "megakernel"
    if "col_update_kernel_call" in kernels:
        return "batched"
    return "none"


def _human_ext(p, ticks: int, seed: int):
    from repro.core import network as N
    from repro.data.synthetic import poisson_external_drive
    return N.stage_external(poisson_external_drive(p, ticks, seed=seed,
                                                   width=EXT_WIDTH))


def _host_planes(sim) -> dict:
    fl = sim.flushed()
    return {f: np.asarray(getattr(fl, f)) for f in fl._fields}


def _sane(planes: dict, what: str):
    for f, a in planes.items():
        if a.dtype.kind == "f":
            check(np.isfinite(a).all(), f"{what}: non-finite values in {f}")
    for f in ("pij", "pi", "pj"):
        check((planes[f] >= 0).all(), f"{what}: negative P trace in {f}")


def _max_mixed_error(a: dict, b: dict) -> dict:
    out = {}
    for f in a:
        x, y = a[f].astype(np.float64), b[f].astype(np.float64)
        out[f] = float(np.max(np.abs(x - y) / np.maximum(np.abs(y), 1.0)))
    return out


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_full_width(dev, n_hcu: int = N_HCU, ticks: int = TICKS,
                     chunk: int = CHUNK):
    """(a) Simulator.run at human widths through the Pallas kernels."""
    from repro.core import Simulator
    from repro.core import network as N
    from repro.core.params import human_scale

    clock = Clock("a")
    p = human_scale(n_hcu)
    log(f"(a) full width: {n_hcu} HCUs x R={p.rows} x C={p.cols}, "
        f"{ticks} ticks in chunks of {chunk}, Poisson in_rate {p.in_rate}/ms")
    ext = clock("stage input", lambda: _human_ext(p, ticks, seed=0))
    sim = clock("init network", lambda: Simulator(p, key=0, chunk=chunk))
    compiled = clock("lower+compile chunk", lambda: N._run_chunk.lower(
        sim.state, sim.conn, ext[:chunk], p, **sim._kw()).compile())
    m = compiled.memory_analysis()
    log(f"  memory_analysis: arguments {m.argument_size_in_bytes} B, "
        f"temporaries {m.temp_size_in_bytes} B, outputs "
        f"{m.output_size_in_bytes} B, aliased {m.alias_size_in_bytes} B, "
        f"generated code {m.generated_code_size_in_bytes} B")
    kernels = _custom_calls(compiled.as_text())
    log(f"  tpu_custom_call kernels in the chunk: {sorted(kernels)}")
    check(kernels & {"fused_row_update_kernel_call",
                     "worklist_update_kernel_call"},
          "no Pallas row kernel in the compiled scan chunk")
    col = _column_path(kernels)
    log(f"  column path: {col}")
    check(col == "megakernel", f"column phase ran the {col} path")
    fired = [clock(f"run ticks {t}-{t + chunk} (Simulator.run)",
                   lambda t=t: sim.run(ext[t:t + chunk]))
             for t in range(0, ticks, chunk)]
    fired = np.concatenate([np.asarray(f) for f in fired])
    planes = clock("flush state to host", lambda: _host_planes(sim))
    _sane(planes, "full width")
    spikes = int((fired >= 0).sum())
    hcus_fired = int((fired >= 0).any(axis=0).sum())
    log(f"  spikes fired: {spikes} by {hcus_fired}/{n_hcu} HCUs; drops "
        f"{sim.drops()}; t={int(sim.state.t)}")
    log(f"  peak_bytes_in_use: {_peak_bytes(dev)}")
    check(hcus_fired > 0, "no HCU fired")
    check(int(sim.state.t) == ticks, "simulated time does not match")


def run_pair(clock, side_a, side_b):
    """Run two simulators of one network in turn, each side a (make, run)
    pair; return (fired, flushed planes) of each, on the host, freeing the
    first one's device state before the second starts."""
    out = []
    for label, (make, run) in (("a", side_a), ("b", side_b)):
        sim = clock(f"init {label}", make)
        fired = clock(f"run {label}", lambda: run(sim))
        out.append((np.asarray(fired),
                    clock(f"flush {label}", lambda: _host_planes(sim))))
        del sim
    return out


def _compare(label, a, b):
    (fa, pa), (fb, pb) = a, b
    check(np.array_equal(fa, fb), f"{label}: fired histories differ")
    check((fa >= 0).sum() > 0, f"{label}: nothing fired")
    _sane(pa, label)
    _sane(pb, label)
    err = _max_mixed_error(pa, pb)
    worst = max(err, key=err.get)
    log(f"  fired histories identical ({int((fa >= 0).sum())} spikes); "
        f"largest mixed error {err[worst]:.3e} in {worst} (RTOL {RTOL:g})")
    log("  per plane: " + ", ".join(f"{f}={e:.2e}" for f, e in err.items()))
    check(err[worst] <= RTOL, f"{label}: {worst} off by {err[worst]:.3e}")


def phase_reference(n_hcu: int = REF_HCU, ticks: int = REF_TICKS,
                    kernel: str = "pallas"):
    """(b) the Pallas kernels against the pure-jnp reference, one chip."""
    from repro.core import Simulator
    from repro.core.params import human_scale

    clock = Clock("b")
    p = human_scale(n_hcu)
    log(f"(b) reference: {n_hcu} HCUs at human widths, {ticks} ticks, "
        f"kernel={kernel!r} vs kernel='ref'")
    ext = _human_ext(p, ticks, seed=1)
    run = lambda s: s.run(ext)
    a, b = run_pair(clock, (lambda: Simulator(p, key=0, kernel=kernel), run),
                    (lambda: Simulator(p, key=0, kernel="ref"), run))
    _compare("reference", a, b)


def phase_serving(p=None, n_requests: int = 8, train_reps: int = 3):
    """(c) recall serving at rodent widths."""
    from repro.core import Simulator
    from repro.data import make_patterns
    from repro.experiments import train_assoc
    from repro.launch.serve_bcpnn import BCPNNRecallServer, RecallRequest

    from benchmarks.serve_bcpnn import _serving_params

    clock = Clock("c")
    # rodent widths with the serving benchmark's associative-memory dynamics
    p = p or _serving_params()
    log(f"(c) serving: {p.n_hcu} HCUs x R={p.rows} x C={p.cols}, "
        f"{n_requests} recall requests")
    sim = Simulator(p, key=0, cap_fire=p.n_hcu)
    patterns = make_patterns(p, 3, seed=3)
    attractor = clock(f"train_assoc ({train_reps} reps)",
                      lambda: train_assoc(sim, patterns, reps=train_reps))
    srv = BCPNNRecallServer(sim, slots=4, queue_capacity=n_requests,
                            step_ticks=12)
    rng = np.random.default_rng(0)
    reqs = [RecallRequest(rid, np.asarray(patterns[rid % 3], np.int32),
                          rng.random(p.n_hcu) < 0.6, budget_ticks=48)
            for rid in range(n_requests)]
    clock("serve", lambda: len(srv.run(reqs)))
    st = srv.stats()
    correct = total = 0
    for r in srv.completed:
        att = attractor[r.rid % 3]
        probe = ~np.asarray(r.cue_mask, bool) & (r.winners >= 0)
        correct += int((r.winners[probe] == att[probe]).sum())
        total += int(probe.sum())
    log(f"  completed {st['completed']}/{n_requests} ({st['done']} converged, "
        f"{st['expired']} expired) in {st['steps']} steps; p50 service "
        f"{st['p50_service_ms']} ms; undriven HCUs on the trained attractor "
        f"{correct}/{total}")
    check(st["completed"] == n_requests
          and all(r.status in ("done", "expired") for r in srv.completed),
          f"only {st['completed']} of {n_requests} recalls completed")


def phase_four_chips(devs, n_dev: int = SHARD_DEV, h_local: int = SHARD_HCU,
                     ticks: int = SHARD_TICKS):
    """(d) Simulator.run_sharded over an "hcu" mesh vs Simulator.run."""
    from repro.core import Simulator
    from repro.core.distributed import lossless_route_config
    from repro.core.params import human_scale

    check(len(devs) == n_dev, f"--four-chips needs {n_dev} devices, "
          f"JAX found {len(devs)}")
    clock = Clock("d")
    n = n_dev * h_local
    p = human_scale(n)
    log(f"(d) four chips: {n_dev}x{h_local} HCUs at human widths, {ticks} "
        f"ticks, run_sharded (lossless routes) vs run on one chip")
    mesh = jax.make_mesh((n_dev,), ("hcu",))
    rc = lossless_route_config(p, h_local)
    ext = _human_ext(p, ticks, seed=2)

    def run_sharded(sim):
        fired = sim.run_sharded(ext, mesh=mesh, rc=rc)
        jax.block_until_ready(fired)
        for s in sim.state.hcus.zij.addressable_shards:
            log(f"  zij shard on device {s.device.id}: {s.data.shape}")
        for d in devs:
            log(f"  device {d.id} peak_bytes_in_use: {_peak_bytes(d)}")
        return fired

    # one-chip run with a lossless fired batch too (cap_fire = all HCUs),
    # so neither side drops a spike the other keeps
    a, b = run_pair(clock, (lambda: Simulator(p, key=0), run_sharded),
                    (lambda: Simulator(p, key=0, cap_fire=n),
                     lambda s: s.run(ext)))
    _compare("sharded vs one chip", a, b)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded phase")
    args = ap.parse_args(argv)
    _import_repo()
    devs = _setup_jax()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(devs)
    else:
        phase_full_width(devs[0])
        phase_reference()
        phase_serving()
    cache = Path(jax.config.jax_compilation_cache_dir)
    log(f"compile cache: {len(list(cache.glob('*')))} entries at end; "
        f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
