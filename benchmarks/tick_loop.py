"""Tick-loop runtime benchmark: per-tick host loop vs scan-compiled runtime.

The tentpole perf claim of the compiled runtime (core/network.py): the
per-tick host loop pays one jit dispatch + one device sync per simulated ms,
which dominates wall-clock long before the fused cell math does; `network_run`
compiles the whole loop with lax.scan and pays one dispatch per chunk.

Three sizes are measured (CPU `ref` backend):
  * default — small planes, the dispatch-bound regime the scan runtime is
    built to eliminate (this is the size the ≥5x acceptance gate runs at);
    stays on the per-HCU fused dense forms (below `hcu.use_worklist`).
  * rodent16 — rodent-ish R/C dimensioning (R=1200, C=70, 16 HCUs) on the
    worklist engine backend: since PR 4 the row phase runs as the fused
    single pass (`engine.worklist_lazy_rows` fused branch), so the tick is
    O(touched rows) with ONE loop walk and compute on valid entries only.
    Gated in CI alongside `default` since PR 3.
  * human_col — one human-scale hypercolumn slab: 4 HCUs at the paper's
    §II.A per-HCU dimensioning (R=10000, C=100, from
    `repro.configs.bcpnn_human`). This is the size whose per-row cost the
    paper's EQ2 budget is written about; it tracks that the worklist tick
    stays O(touched rows) when the planes are 25 MB/HCU. Gated in CI since
    PR 4.
  * human_col_blocked — the same slab stored under the Row-Merge
    column-blocked plane layout (PR 8, `layout="blocked"`): the end-to-end
    per-tick layout A/B. Not regression-gated; the layout-model gate holds
    the BENCH_layout.json column A/B (benchmarks/fig10_rowmerge.py).

All sizes are driven through the `Simulator` facade (scan runtime
`sim.run` vs host loop `sim.run_host`).

`python -m benchmarks.run --json` writes the results to BENCH_tick_loop.json.
The committed numbers are measured with `--legacy-cpu` (benchmarks.run's
opt-in pin of `--xla_cpu_use_thunk_runtime=false`): the legacy XLA CPU
runtime executes the identical HLO with ~3-4x lower per-op overhead, for
the host loop and the scan runtime alike. docs/BENCHMARKING.md has the
full workflow (regenerating the JSON, the CI regression gate).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.bcpnn_human import CONFIG as HUMAN_CFG
from repro.core import Simulator
from repro.core.params import BCPNNParams

# dispatch-bound default: the acceptance gate (scan >= 5x host ticks/sec)
DEFAULT = ("default", BCPNNParams(n_hcu=8, rows=128, cols=16, fanout=8,
                                  active_queue=16, max_delay=16))
RODENT = ("rodent16", BCPNNParams(n_hcu=16, rows=1200, cols=70, fanout=16,
                                  active_queue=16, max_delay=16))
# one human-scale hypercolumn slab: paper per-HCU dimensioning (R=10000,
# C=100), bench-sized HCU count/queues like rodent16
HUMAN_COL = ("human_col", BCPNNParams(n_hcu=4, rows=HUMAN_CFG.rows,
                                      cols=HUMAN_CFG.cols, fanout=4,
                                      active_queue=16, max_delay=16))
# the same slab under the PR 8 Row-Merge column-blocked plane layout
# (layout="blocked", the CPU tile) — an end-to-end per-tick A/B against
# human_col in the same committed JSON; NOT regression-gated (the flat
# entries stay the gated baseline), the column A/B is gated through
# BENCH_layout.json instead
HUMAN_COL_BLOCKED = ("human_col_blocked", HUMAN_COL[1], "blocked")

N_SCAN = 128         # ticks per measured scan call (one compiled chunk)
N_HOST = 32          # ticks per measured host-loop pass
REPEATS = 5          # min over repeats (see note below)

# The estimator is MIN over repeats, not median: CI runners and shared dev
# VMs burst-throttle (measured on the dev box: a 10x CPU-speed swing within
# one minute), and contention is strictly additive noise on a deterministic
# computation — the fastest observed repeat is the best estimate of the
# code's cost, where a median of 3 is a lottery ticket on the throttle
# phase. The committed numbers and the CI regression gate both use this
# estimator (PR 5; earlier JSONs were medians of 3, so the PR 5
# regeneration is the comparison floor going forward).


def _ext_tensor(p, T, width=8, lam=4.0, seed=0):
    rng = np.random.default_rng(seed)
    out = np.full((T, p.n_hcu, width), p.rows, np.int32)
    for t in range(T):
        for h in range(p.n_hcu):
            n = min(width, rng.poisson(lam))
            out[t, h, :n] = rng.integers(0, p.rows, n)
    return jnp.asarray(out)


def _measure(p, backend="ref", layout=None):
    """Returns (host_us_per_tick, scan_us_per_tick), min over REPEATS."""
    sim = Simulator(p, key=0, kernel=backend, chunk=N_SCAN, layout=layout)
    ext = _ext_tensor(p, N_SCAN)

    # warm both compilation caches
    sim.run_host(lambda t: ext[(t - 1) % N_SCAN], 2)
    sim.reset()
    sim.run(ext)
    jax.block_until_ready(sim.state.hcus.zij)

    host_t, scan_t = [], []
    for _ in range(REPEATS):
        sim.reset()
        t0 = time.perf_counter()
        f = sim.run_host(lambda t: ext[(t - 1) % N_SCAN], N_HOST)
        jax.block_until_ready(f)
        host_t.append((time.perf_counter() - t0) / N_HOST)

        sim.reset()
        t0 = time.perf_counter()
        f = sim.run(ext)
        jax.block_until_ready(f)
        scan_t.append((time.perf_counter() - t0) / N_SCAN)
    return min(host_t) * 1e6, min(scan_t) * 1e6


def measure_sizes(sizes=(DEFAULT, RODENT, HUMAN_COL, HUMAN_COL_BLOCKED)):
    """Returns {name: {host_us_per_tick, scan_us_per_tick, host_ticks_per_sec,
    scan_ticks_per_sec, speedup, n_hcu, rows, cols}}. A size tuple may carry
    a third element: the plane layout to run under (see HUMAN_COL_BLOCKED)."""
    results = {}
    for name, p, *rest in sizes:
        layout = rest[0] if rest else None
        host_us, scan_us = _measure(p, layout=layout)
        results[name] = {
            "n_hcu": p.n_hcu, "rows": p.rows, "cols": p.cols,
            "host_us_per_tick": host_us, "scan_us_per_tick": scan_us,
            "host_ticks_per_sec": 1e6 / host_us,
            "scan_ticks_per_sec": 1e6 / scan_us,
            "speedup": host_us / scan_us,
        }
    return results


def tick_loop(results=None):
    """benchmarks.run suite hook: CSV rows from the measured sizes."""
    results = results or measure_sizes()
    rows = []
    for name, r in results.items():
        rows.append((f"tick_loop/{name}/host_us_per_tick",
                     r["host_us_per_tick"], r["host_ticks_per_sec"]))
        rows.append((f"tick_loop/{name}/scan_us_per_tick",
                     r["scan_us_per_tick"], r["scan_ticks_per_sec"]))
        rows.append((f"tick_loop/{name}/scan_speedup", 0.0, r["speedup"]))
    return rows
