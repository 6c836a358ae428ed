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
           network from the seed (`Simulator(p, key=seed)`, fan-out drawn
           from the seed by `connectivity` on the first chip and held on
           the host): on the chip for one chip, on the host for several
           (handed over as NumPy), whose shares `run_sharded` then puts
           each on its own chip; stage the external input on the device
           in chunks; run two warm-up chunks through the same call the
           window uses (they compile, or load from the compile cache
           kept in `.jax_cache/` of the checkout unless
           JAX_COMPILATION_CACHE_DIR is set).
  window   dispatch chunks, one in flight, until `--seconds` have passed;
           sim_ms_per_s is every simulated ms over all of the window's
           wall time. With --trace 1 the window is traced instead and the
           per-layer metrics are read from the trace.
  check    read the devices' peak memory, then replay every tick of the run
           in the plain reference (`reference.py`), teacher-forced on the
           fired history the system produced, chip by chip: each chip
           replays its own HCUs and compares them with its own share of
           the system's flushed state; the WTA's choices too, against the
           configuration's limits.

The last line of stdout is the result's JSON object; the compared numbers
and their limits are also the last lines of stderr.
"""
from __future__ import annotations

import argparse
import functools
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
    [1, max_delay - 1] (the model's dimensioning, eBrainII sec. IV). Drawn
    on the default device, so that its values are those of the chip, and
    returned as NumPy: no chip holds the whole network's fan-out but for
    the check, which puts a copy on each."""
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

    return jax.device_get(
        make(jax.random.fold_in(jax.random.PRNGKey(seed), 0xC0)))


class Program:
    """The system under test, driven through `Simulator.run` (one chip) or
    `Simulator.run_sharded` (an "hcu" mesh over the cell's chips)."""

    def __init__(self, params: dict, seed: int, devs, conn):
        import jax
        import numpy as np
        from repro.core import Simulator
        from repro.core.params import BCPNNParams
        self.p = BCPNNParams(**params)
        self.devs = devs
        if len(devs) == 1:
            self.mesh = None
            self.sim = Simulator(self.p, key=seed)
            conn = jax.device_put(conn, devs[0])
        else:
            # built on the host and handed over as NumPy: `run_sharded`
            # puts each chip's share on its chip, and no chip holds the
            # whole network (host-backed jax arrays pass through the first
            # chip: 2.84 GB more there at 4 x 1,152 rodent HCUs)
            self.mesh = jax.sharding.Mesh(devs, ("hcu",))
            with jax.default_device(jax.devices("cpu")[0]):
                self.sim = Simulator(self.p, key=seed)
            self.sim.state = jax.tree.map(np.asarray, self.sim.state)
        self.sim.conn = type(self.sim.conn)(*conn)

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
        """Spikes dropped in the run. On several chips each chip counts its
        own overflows in counters that the program declares replicated,
        so every chip's own copy is read and the copies summed."""
        if self.mesh is None:
            return int(sum(self.sim.drops().values()))
        st = self.sim.state
        return sum(int(s.data) for a in (st.drops_in, st.drops_fire,
                                         st.drops_route) if a is not None
                   for s in a.addressable_shards)

    def pieces(self, shares):
        """`pieces` for `reference.compare_states`: chip d's share of the
        system's raw state, its HCUs [d*h, (d+1)*h) as they lie on the
        chip (the flat (H*R, C) planes are the reference's layout), beside
        the reference's share `shares[d]` on the same chip."""
        hc = self.sim.state.hcus
        names = dict(z="zij", e="eij", p="pij", t="tij", zi="zi", ei="ei",
                     pi="pi", ti="ti", zj="zj", ej="ej", pj="pj", h="h")
        for dev, ref_st in zip(self.devs, shares, strict=True):
            leaves = {k: next(s.data for s in getattr(hc, f).addressable_shards
                              if s.device == dev)
                      for k, f in names.items()}
            yield leaves, ref_st, 0


def peak_memory(devs) -> int:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    vals = [v for v in vals if v is not None]
    if not vals:
        raise BenchError("the device reports no peak_bytes_in_use")
    return max(vals)


def replay(m, conn, ext_np, forced, seed: int, chunk: int, devs,
           dtype=None, probes=()):
    """Replay every tick of `forced` (the fired history, (T, H)) in the
    reference, teacher-forced on it, reading the WTA gap of each history
    in `probes`: chip d of `devs` replays its own HCUs [d*h, (d+1)*h),
    with its own copy of the fan-out `conn` and the whole history. Every
    chip's chunk is sent before any is waited for. Returns (each chip's
    final reference share, per-tick stats over all chips as NumPy, the
    reference's own winners (T, H))."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import reference as ref

    dtype = dtype or jnp.float32
    T, n = forced.shape[0], len(devs)
    h = m.n_hcu // n
    nb = ext_np.shape[0] // chunk
    shares = []
    for d, dev in enumerate(devs):
        put = functools.partial(jax.device_put, device=dev)
        with jax.default_device(dev):
            shares.append(dict(
                put=put, st=ref.init_state(m, dtype, n),
                conn=put(conn), bkey=ref.base_key(seed), stats=[], own=[],
                ext=[put(ext_np[i * chunk:(i + 1) * chunk, d * h:(d + 1) * h])
                     for i in range(nb)]))
    for k in range(T // chunk):
        sl = slice(k * chunk, (k + 1) * chunk)
        for d, sh in enumerate(shares):
            cols = slice(d * h, (d + 1) * h)
            pr = sh["put"](np.stack([q[sl, cols] for q in probes])
                           if probes else np.zeros((0, chunk, h), np.int32))
            sh["st"], (o, s) = ref.replay_chunk(
                sh["st"], sh["conn"], sh["ext"][k % nb],
                sh["put"](forced[sl]), pr, sh["bkey"], m=m, dtype=dtype,
                n_probes=len(probes), part=(d, n))
            sh["stats"].append(s)
            sh["own"].append(o)
    per_chip = [{k: np.concatenate([np.asarray(s[k]) for s in sh["stats"]])
                 for k in sh["stats"][0]} for sh in shares]
    stats = {k: (np.max if k == "gaps" else np.sum)(
        [c[k] for c in per_chip], axis=0) for k in per_chip[0]}
    own = np.concatenate([np.concatenate([np.asarray(o) for o in sh["own"]])
                          for sh in shares], axis=1)
    return [sh["st"] for sh in shares], stats, own


def check(m, prog, conn, ext_np, fired, seed: int, chunk: int, devs,
          limits: dict):
    """Replay the whole run in the reference chip by chip and compare each
    chip's share of the system (`prog.pieces`) with the reference's share
    on the same chip. Returns (checks {name: {value, limit}}, per-tick
    stats)."""
    import reference as ref

    shares, stats, _ = replay(m, conn, ext_np, fired, seed, chunk, devs,
                              probes=(fired,))
    errs = ref.compare_states(m, prog.pieces(shares))
    log("state error per field: " + ", ".join(
        f"{k}={v:.3e}" for k, v in errs.per_field().items()))
    values = {"state_err": errs.worst()[1],
              "wta_gap": float(stats["gaps"][:, 0].max()),
              "fire_mismatch": int(stats["mismatch"].sum())}
    checks = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    return checks, stats


def attempted(stats, m) -> int:
    """Spikes the run offered: the external rows and the fired batch's
    fan-out."""
    return int(stats["n_ext"].sum() + stats["n_fired"].sum() * m.fanout)


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
    checks, stats = check(m, r.prog, r.conn, r.ext, fired,
                          args.seed, r.chunk, devs, cfg["limits"])
    log(f"check {time.perf_counter() - t_check:.3f} s")

    device = {"platform": dev0.platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": hbm}
    result = {"correct": passed(checks), "attempted": attempted(stats, m),
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
