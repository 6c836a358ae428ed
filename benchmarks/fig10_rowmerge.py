"""Fig 9-10 Row-Merge layout benchmark: model tables + a measured CPU A/B.

  PYTHONPATH=src python -m benchmarks.fig10_rowmerge [--legacy-cpu] [--json]

Always writes ``BENCH_layout.json`` at the repo root (uploaded as a CI
artifact next to BENCH_tick_loop.json). Three sections:

  * paper_dram_model — the paper's own Fig 10 objective: DRAM row misses/s
    vs the merge factor X for the §II.A human HCU (R=10000, C=100) at the
    BCPNN access rates (10 kHz rows, 100 Hz columns). Minimum at X=10,
    5.05x fewer misses than direct (X=1) — `layout.dram_row_misses_per_s`.
  * tpu_tile_model / cpu_cache_line_model — the same trade-off re-derived
    for our two execution substrates. TPU: HBM bytes touched/s over (8k,
    128m) register-tile shapes (`layout.tile_bytes_touched_per_s`,
    minimized by `layout.best_tile`). CPU: 64-byte cache lines touched/s
    (`layout.cache_lines_touched_per_s`) over candidate `BlockedLayout`
    tiles vs the flat row-major plane — the model that picks the default
    CPU tile (`layout.CPU_BLOCK_XR/XC`).
  * measured_human_col — a same-process, same-machine-window wall-clock
    A/B of the worklist column phase at the human_col bench size
    (benchmarks/tick_loop.py), canonical flat vs the column-blocked CPU
    tile: the full production scan (`engine.tick` under `lax.scan`,
    donated carry) minus the same scan with the column phase ablated.
    Estimator is MIN over interleaved repeats (contention is additive noise; see
    tick_loop.py). Caveats: ablation deltas are O(phase) accurate, not
    exact — ablating the column phase perturbs downstream spike
    trajectories — and the flat/blocked deltas come from separately
    compiled scans, so XLA fusion differences are part of what is being
    measured (that is the point: the layout pays off only if the compiled
    artifact does).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time


def measure_column_ab(p, layouts, ticks=64, repeats=5):
    """Scan-context column-phase ablation per layout. Returns
    {tag: {scan_us_per_tick, column_update_ablation_us}} using one
    interleaved measurement window for all variants."""
    import functools
    from typing import NamedTuple

    import jax

    from benchmarks.tick_loop import _ext_tensor
    from repro.core import engine as E
    from repro.core import layout as L
    from repro.core import network as N

    class _NoColumns(NamedTuple):
        """Worklist backend with the lazy column phase swapped for a no-op
        (benchmark-only recomposition — tracks
        WorklistBackend.plane_update)."""
        base: object

        def carry_in(self, state, p):
            return self.base.carry_in(state, p)

        def carry_out(self, state, p):
            return self.base.carry_out(state, p)

        def plane_update(self, state, rows, t, keys, p, cap, cond_columns):
            hcus, w_rows, c = E.worklist_lazy_rows(
                state.hcus, rows, t, p, kernel=self.base.kernel,
                fused=self.base.fused, layout=self.base.layout)
            hcus, fired = E._wta(hcus, w_rows, c["counts"], t, keys, p)
            h_idx, j_idx, n_drop = N.select_fired(fired, cap)
            return state._replace(hcus=hcus), fired, h_idx, j_idx, n_drop

    key = jax.random.PRNGKey(0)
    conn = N.make_connectivity(p, jax.random.fold_in(key, 1))
    ext = _ext_tensor(p, ticks)

    def make_run(be):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def run(state, ext):
            def body(s, e):
                return E.tick(s, conn, e, p, be)
            s, f = jax.lax.scan(body, be.carry_in(state, p), ext)
            return be.carry_out(s, p), f
        return run

    variants = {}
    for lay in layouts:
        base = E.select_backend(p, layout=lay)
        assert isinstance(base, E.WorklistBackend), \
            "the column A/B is about the worklist regime"
        tag = L.layout_tag(lay)
        variants[(tag, "full")] = (lay, make_run(base))
        variants[(tag, "nocol")] = (lay, make_run(_NoColumns(base)))

    for lay, fn in variants.values():             # compile + warm all first
        s, f = fn(N.init_network(p, key, layout=lay), ext)
        jax.block_until_ready(f)
    meas = {k: [] for k in variants}
    for _ in range(repeats):                      # interleaved rounds
        for k, (lay, fn) in variants.items():
            state = N.init_network(p, key, layout=lay)
            t0 = time.perf_counter()
            s, f = fn(state, ext)
            jax.block_until_ready(f)
            meas[k].append((time.perf_counter() - t0) / ticks)

    out = {}
    for lay in layouts:
        tag = L.layout_tag(lay)
        full = min(meas[(tag, "full")]) * 1e6
        nocol = min(meas[(tag, "nocol")]) * 1e6
        out[tag] = {"scan_us_per_tick": full,
                    "column_update_ablation_us": full - nocol}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--legacy-cpu", action="store_true",
                    help="pin the legacy XLA CPU runtime (matches the "
                         "committed BENCH_*.json configuration)")
    ap.add_argument("--json", action="store_true",
                    help="print the JSON blob instead of CSV rows (the "
                         "file is written either way)")
    ap.add_argument("--fast", action="store_true",
                    help="model tables only; skip the measured A/B")
    ap.add_argument("--ticks", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()
    if args.legacy_cpu:
        from benchmarks.run import pin_legacy_cpu_runtime
        pin_legacy_cpu_runtime()

    from benchmarks.tick_loop import HUMAN_COL
    from repro.core import layout as L

    R, C, ROW_HZ, COL_HZ = 10_000, 100, 10_000.0, 100.0

    table = L.paper_fig10_table()
    best_x = min(table, key=table.get)
    (txr, txc), tscored = L.best_tile(R, C, ROW_HZ, COL_HZ)

    cpu_tiles = [(1, C)] + [(xr, xc) for xr in (4, 8, 16)
                            for xc in (2, 4, 8, 16)]
    cpu_model = {f"{xr}x{xc}":
                 L.cache_lines_touched_per_s(xr, xc, R, C, ROW_HZ, COL_HZ)
                 for xr, xc in cpu_tiles}

    results = {
        "paper_dram_model": {
            "rowmiss_per_s": {str(x): table[x] for x in sorted(table)},
            "best_x": best_x,
            "gain_vs_direct": table[1] / table[best_x],
        },
        "tpu_tile_model": {
            "best_tile": [txr, txc],
            "bytes_per_s": {f"{xr}x{xc}": v
                            for (xr, xc), v in sorted(tscored.items())},
        },
        "cpu_cache_line_model": {
            "lines_per_s": cpu_model,
            "default_tile": [L.CPU_BLOCK_XR, L.CPU_BLOCK_XC],
            "flat_over_default":
                cpu_model[f"1x{C}"]
                / cpu_model[f"{L.CPU_BLOCK_XR}x{L.CPU_BLOCK_XC}"],
        },
    }

    if not args.fast:
        name, p = HUMAN_COL
        lay = L.cpu_blocked(p)
        ab = measure_column_ab(p, [None, lay], ticks=args.ticks,
                               repeats=args.repeats)
        flat, blocked = ab["flat"], ab[L.layout_tag(lay)]
        results["measured_human_col"] = {
            "size": {"n_hcu": p.n_hcu, "rows": p.rows, "cols": p.cols},
            "ticks": args.ticks, "repeats": args.repeats,
            "estimator": "min-over-interleaved-repeats",
            "layouts": ab,
            "column_ablation_flat_over_blocked":
                flat["column_update_ablation_us"]
                / max(blocked["column_update_ablation_us"], 1e-9),
            "caveats": "scan-context ablation deltas are O(phase) accurate "
                       "(ablating columns perturbs downstream spikes); "
                       "flat/blocked are separately compiled scans measured "
                       "in one interleaved same-machine window",
        }

    out = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_layout.json"
    out.write_text(json.dumps(results, indent=2) + "\n")
    print(f"# wrote {out}", file=sys.stderr)

    if args.json:
        json.dump(results, sys.stdout, indent=2)
        print()
        return
    print("name,us_per_call,derived")
    for x in sorted(table):
        print(f"fig10/rowmiss_per_s_X{x},0.000,{table[x]:.6g}")
    print(f"fig10/best_X,0.000,{best_x}")
    print(f"fig10/cpu_lines_flat_over_default,0.000,"
          f"{results['cpu_cache_line_model']['flat_over_default']:.6g}")
    if "measured_human_col" in results:
        m = results["measured_human_col"]
        for tag, r in m["layouts"].items():
            print(f"fig10/human_col/{tag}/scan_us_per_tick,"
                  f"{r['scan_us_per_tick']:.3f},0")
            print(f"fig10/human_col/{tag}/column_ablation_us,"
                  f"{r['column_update_ablation_us']:.3f},0")
        print(f"fig10/human_col/column_ablation_flat_over_blocked,0.000,"
              f"{m['column_ablation_flat_over_blocked']:.6g}")


if __name__ == "__main__":
    main()
