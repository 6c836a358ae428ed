"""The chip benchmark of the BCPNN tick path, driven by data.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything specific to a cell is found by name: the cell in
`BENCHMARK.json` names its configuration (whose `file` holds the model's
sizes, its chips, chunk length and the limits of the correctness check)
and its traffic mix (`bench/traffic/<mix>.json`, read by `generator.py`);
each per-layer metric is `bench/metrics/<metric>.py`, a `read(ctx)` that
returns a number or None. This file holds nothing specific to one cell.

A run, in one process that owns the cell's chips:

  set-up   check for the chips and the Pallas kernel backend; build the
           network on the device from the seed (`Simulator(p, key=seed)`,
           fan-out drawn from the seed by `connectivity`); stage the
           external input on the device in chunks; run two warm-up chunks
           through the same call the window uses (they compile, or load
           from the compile cache kept in `.jax_cache/` of the checkout
           unless JAX_COMPILATION_CACHE_DIR is set).
  window   dispatch chunks, one in flight, until `--seconds` have passed;
           sim_ms_per_s is every simulated ms over all of the window's
           wall time. With --trace 1 the window is traced instead and the
           per-layer metrics are read from the trace.
  check    read the devices' peak memory, then replay every tick of the run
           in the plain reference (`reference.py`), teacher-forced on the
           fired history the system produced, and compare the flushed
           state and the WTA's choices against the configuration's limits.

The last line of stdout is the result's JSON object; the compared numbers
and their limits are also the last lines of stderr.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


class BenchError(SystemExit):
    """Ends the run with a non-zero exit code and no result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding a cell's pieces by name
# ---------------------------------------------------------------------------

def load_spec(root: Path) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def cell(spec: dict, root: Path, name: str) -> dict:
    """The workload `name` with its configuration and traffic mix loaded."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((root / cfgs[w["config"]]["file"]).read_text())
    mix_path = root / "bench" / "traffic" / f"{w['traffic']}.json"
    if not mix_path.is_file():
        raise BenchError(f"no traffic mix {mix_path}")
    return dict(workload=w, config=cfg, mix=json.loads(mix_path.read_text()))


def metrics_of(spec: dict, workload: str, kind: str) -> list:
    """The end-to-end or per-layer metric entries that `workload` reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or workload in m["workloads"]]


def metric_reader(root: Path, name: str):
    """`read(ctx)` of bench/metrics/<name>.py."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path} for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak_of(device_kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in peaks:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return peaks[device_kind]


# ---------------------------------------------------------------------------
# the chips
# ---------------------------------------------------------------------------

def setup_jax(root: Path):
    """Compile cache inside the checkout unless the environment names one."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def import_program(root: Path):
    src = root / "src"
    if not (src / "repro" / "core").is_dir():
        raise BenchError(f"no repro package under {src}")
    sys.path.insert(0, str(src))


def require_chips(n: int):
    """The first `n` TPU devices, or exit: no fallback to the CPU, and the
    kernels must be the Pallas ones."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found {len(devs)}")
    from repro.kernels import ops
    if ops.default_backend() != "pallas":
        raise BenchError(f"kernel backend is {ops.default_backend()!r}, "
                         f"not 'pallas'")
    return devs[:n]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def connectivity(m, seed: int):
    """Fan-out of every (HCU, column): `fanout` targets uniform over HCUs
    and rows, delays 1 + Geometric(1 / (mean_delay - 1)) clipped to
    [1, max_delay - 1] (the model's dimensioning, eBrainII sec. IV)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        shape = (m.n_hcu, m.cols, m.fanout)
        dh = jax.random.randint(k1, shape, 0, m.n_hcu, jnp.int32)
        dr = jax.random.randint(k2, shape, 0, m.rows, jnp.int32)
        lam = 1.0 / max(m.mean_delay - 1.0, 1e-3)
        geo = jnp.floor(jnp.log1p(-jax.random.uniform(k3, shape)) / -lam)
        dl = jnp.clip(1 + geo.astype(jnp.int32), 1, m.max_delay - 1)
        return dh, dr, dl

    return make(jax.random.fold_in(jax.random.PRNGKey(seed), 0xC0))


class Program:
    """The system under test, driven through `Simulator.run` (one chip) or
    `Simulator.run_sharded` (an "hcu" mesh over the cell's chips)."""

    def __init__(self, params: dict, seed: int, devs, conn):
        import jax
        from repro.core import Simulator
        from repro.core.params import BCPNNParams
        self.p = BCPNNParams(**params)
        self.devs = devs
        self.sim = Simulator(self.p, key=seed)
        self.sim.conn = type(self.sim.conn)(*conn)
        self.mesh = (None if len(devs) == 1
                     else jax.sharding.Mesh(devs, ("hcu",)))

    def place(self, ext):
        """Stage one chunk of external rows where the call expects it."""
        import jax
        if self.mesh is None:
            return jax.device_put(ext, self.devs[0])
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(ext, NamedSharding(self.mesh, P(None, "hcu")))

    def __call__(self, ext):
        if self.mesh is None:
            return self.sim.run(ext)
        return self.sim.run_sharded(ext, mesh=self.mesh)

    def drops(self) -> int:
        return int(sum(self.sim.drops().values()))

    def pieces(self, dev0):
        """The system's raw state for `reference.compare_states`: one
        (first HCU, leaves) pair per device, moved to the reference's
        device (the flat (H*R, C) planes are the reference's layout)."""
        import jax
        hc = self.sim.state.hcus
        names = dict(z="zij", e="eij", p="pij", t="tij", zi="zi", ei="ei",
                     pi="pi", ti="ti", zj="zj", ej="ej", pj="pj", h="h")
        for d in range(len(self.devs)):
            leaves = {}
            for k, f in names.items():
                a = getattr(hc, f)
                sh = sorted(a.addressable_shards,
                            key=lambda s: s.index[0].start or 0)[d]
                leaves[k] = jax.device_put(sh.data, dev0)
            yield d * (self.p.n_hcu // len(self.devs)), leaves


def peak_memory(devs) -> int:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    vals = [v for v in vals if v is not None]
    if not vals:
        raise BenchError("the device reports no peak_bytes_in_use")
    return max(vals)


def replay(m, conn, ext_np, forced, seed: int, chunk: int, dev0,
           dtype=None, probes=()):
    """Replay every tick of `forced` (the fired history, (T, H)) in the
    reference on `dev0`, teacher-forced on it, reading the WTA gap of each
    history in `probes`. Returns (final state, per-tick stats as NumPy,
    the reference's own winners (T, H))."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import reference as ref

    dtype = dtype or jnp.float32
    T = forced.shape[0]
    nb = ext_np.shape[0] // chunk
    put = lambda x: jax.device_put(x, dev0)
    with jax.default_device(dev0):
        st = ref.init_state(m, dtype)
        ext = [put(ext_np[i * chunk:(i + 1) * chunk]) for i in range(nb)]
        bkey = ref.base_key(seed)
        stats, own = [], []
        for k in range(T // chunk):
            sl = slice(k * chunk, (k + 1) * chunk)
            fk = put(forced[sl])
            pr = put(np.stack([q[sl] for q in probes]) if probes else
                     np.zeros((0, chunk, m.n_hcu), np.int32))
            st, (o, s) = ref.replay_chunk(st, conn, ext[k % nb], fk, pr,
                                          bkey, m=m, dtype=dtype,
                                          n_probes=len(probes))
            stats.append(s)
            own.append(o)
    stats = {k: np.concatenate([np.asarray(s[k]) for s in stats])
             for k in stats[0]}
    return st, stats, np.concatenate([np.asarray(o) for o in own])


def check(m, pieces, conn, ext_np, fired, seed: int, chunk: int, dev0,
          limits: dict):
    """Replay the whole run in the reference on `dev0` and compare. Returns
    (checks {name: {value, limit}}, per-tick stats)."""
    import reference as ref

    st, stats, _ = replay(m, conn, ext_np, fired, seed, chunk, dev0,
                          probes=(fired,))
    errs = ref.compare_states(m, pieces, st)
    log("state error per field: " + ", ".join(
        f"{k}={v:.3e}" for k, v in errs.per_field().items()))
    values = {"state_err": errs.worst()[1],
              "wta_gap": float(stats["gaps"][:, 0].max()),
              "fire_mismatch": int(stats["mismatch"].sum())}
    checks = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    return checks, stats


def passed(checks: dict) -> bool:
    return all(isinstance(c["value"], (int, float))
               and math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


class Run:
    """One seeded network of a cell, set up and warmed: the system under
    test, its staged input chunks and the fired history so far (one entry
    per chunk, the two warm-up chunks first)."""

    def __init__(self, c: dict, seed: int, devs):
        import jax
        import generator
        import reference as ref
        self.seed, self.devs = seed, devs
        self.chunk = int(c["config"]["chunk_ticks"])
        params = c["config"]["params"]
        self.m = m = ref.model_from_params(params, n_dev=len(devs))
        # every fan-out target is on the cell's chips: those spikes are part
        # of each HCU's arrivals, and the mix's external rows the rest
        self.ext = generator.external_rows(c["mix"], m.n_hcu, m.rows, seed,
                                           recurrent=m.out_rate * m.fanout)
        if self.ext.shape[0] % self.chunk:
            raise BenchError("buffer_ticks must be a multiple of chunk_ticks")
        self.conn = connectivity(m, seed)
        self.prog = Program(params, seed, devs, self.conn)
        self.chunks = [self.prog.place(self.ext[i:i + self.chunk])
                       for i in range(0, self.ext.shape[0], self.chunk)]
        # two warm-up chunks: the first call sees the state as the network
        # was built and compiles for it; the second sees the layout the
        # chunk itself returns, which every later call sees too, and
        # compiles once more (measured: a first window chunk 4 s long)
        self.fired = []
        for k in range(2):
            self.fired.append(self.prog(self.chunks[k]))
            jax.block_until_ready(self.fired[-1])

    def window(self, seconds: float) -> float:
        """Dispatch chunks, one in flight, until `seconds` have passed and
        the last one has finished; returns the wall seconds."""
        import jax
        t0 = time.perf_counter()
        pending = self.fired[-1]
        while True:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = self.prog(self.chunks[len(self.fired)
                                            % len(self.chunks)])
            self.fired.append(out)
            with jax.profiler.TraceAnnotation("bench.wait"):
                pending.block_until_ready()
            pending = out
            if time.perf_counter() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("bench.wait"):
            pending.block_until_ready()
        return time.perf_counter() - t0

    def history(self):
        import numpy as np
        return np.concatenate([np.asarray(f) for f in self.fired])


def start_trace(jax):
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tdir, profiler_options=opts)
    return tdir


def per_layer(root: Path, spec, w, run: Run, tdir: str, window_s: float,
              ticks: tuple, stats, width: int, kind: str):
    """Per-layer metrics, device busy time and the breakdown of a traced
    window over ticks [ticks[0], ticks[1])."""
    import xtrace
    tr = xtrace.load(tdir, host_names=lambda n: n.startswith("bench."))
    shutil.rmtree(tdir, ignore_errors=True)
    m = run.m
    sl = slice(*ticks)
    ctx = dict(trace=tr, window_s=window_s, ticks=ticks[1] - ticks[0], m=m,
               peak=peak_of(kind), chips=len(run.devs),
               n_rows=int(stats["n_rows"][sl].sum()),
               n_fired=int(stats["n_fired"][sl].sum()),
               slots=m.active_queue + width)
    metrics = {}
    for e in metrics_of(spec, w["name"], "per_layer"):
        v = metric_reader(root, e["name"])(ctx)
        if v is not None:
            metrics[e["name"]] = {"value": v, "unit": e["unit"]}
    busy = [xtrace.busy_ns(tr.devices.get(d.id, [])) for d in run.devs]
    busy_s = sum(busy) / len(busy) / 1e9
    breakdown = {"device_ops": xtrace.top_ops(tr),
                 "idle_gaps": xtrace.idle_gaps(tr)}
    return metrics, busy_s, breakdown


def run(args, root: Path, t_start: float) -> dict:
    spec = load_spec(root)
    c = cell(spec, root, args.workload)
    w, cfg = c["workload"], c["config"]
    chips = int(w["chips"])
    if chips != int(cfg["chips"]):
        raise BenchError(f"{w['name']} asks for {chips} chips, its "
                         f"configuration for {cfg['chips']}")
    import_program(root)
    jax = setup_jax(root)
    devs = require_chips(chips)
    dev0, kind = devs[0], devs[0].device_kind
    peak_of(kind)
    log(f"{w['name']}: {cfg['params']['n_hcu']} HCUs x "
        f"R={cfg['params']['rows']} x C={cfg['params']['cols']} on {chips} "
        f"x {kind}, chunks of {cfg['chunk_ticks']} ticks, seed {args.seed}")

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if "backend_compile" in event else None)
    r = Run(c, args.seed, devs)
    m = r.m
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")
    tdir = start_trace(jax) if args.trace else None
    n0, c0 = len(r.fired), len(compiles)
    window_s = r.window(args.seconds)
    if tdir:
        jax.profiler.stop_trace()
    ticks = (n0 * r.chunk, len(r.fired) * r.chunk)
    log(f"window {window_s:.3f} s, {ticks[1] - ticks[0]} ticks, "
        f"{len(compiles) - c0} compiles in it")
    hbm = peak_memory(devs)

    fired = r.history()
    failed = r.prog.drops()
    t_check = time.perf_counter()
    checks, stats = check(m, r.prog.pieces(dev0), r.conn, r.ext, fired,
                          args.seed, r.chunk, dev0, cfg["limits"])
    log(f"check {time.perf_counter() - t_check:.3f} s")
    attempted = int(stats["n_ext"].sum() + stats["n_fired"].sum() * m.fanout)

    device = {"platform": dev0.platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": hbm}
    result = {"correct": passed(checks), "attempted": attempted,
              "failed": failed}
    if not tdir:
        values = {"sim_ms_per_s": (ticks[1] - ticks[0]) * m.dt_ms / window_s,
                  "hbm_peak_gb": hbm / 1e9, "setup_s": setup_s}
        metrics = {e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
                   for e in metrics_of(spec, w["name"], "end_to_end")}
    else:
        metrics, busy_s, result["breakdown"] = per_layer(
            root, spec, w, r, tdir, window_s, ticks, stats,
            int(c["mix"]["width"]), kind)
        device.update(busy_s=busy_s, window_s=window_s)
    result.update(metrics=metrics, device=device, checks=checks)
    return result


def main(argv=None, root: Path | None = None,
         t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = (root or BENCH.parent).resolve()
    result = run(args, root, t_start)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0
