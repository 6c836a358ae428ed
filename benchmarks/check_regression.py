"""Benchmark regression gates (shared by CI and `make ci-local`).

  PYTHONPATH=src python -m benchmarks.check_regression \
      [--committed /tmp/BENCH_committed.json --fresh BENCH_tick_loop.json] \
      [--serving-committed /tmp/BENCH_serving_committed.json \
       --serving-fresh BENCH_serving.json] \
      [--weak-scaling-committed /tmp/BENCH_weak_committed.json \
       --weak-scaling-fresh BENCH_weak_scaling.json] \
      [--layout-committed /tmp/BENCH_layout_committed.json \
       --layout-fresh BENCH_layout.json]

Four gates, all optional and all with the same headroom philosophy —
headroom absorbs CI-runner noise while still catching the step-function
regressions that matter (a lost in-place alias or an accidental full-plane
copy is 2x+, never 1.1x):

  * tick loop — any gated size's `scan_us_per_tick` in BENCH_tick_loop.json
    vs the committed baseline (1.25x headroom);
  * serving throughput (optional, when --serving-committed is given) — the
    rodent16 `qps_at_slo` in BENCH_serving.json. This gate is INVERTED
    (higher is better): it fails when the fresh throughput drops below
    committed/headroom, and unconditionally when qps_at_slo == 0 (the p95
    sojourn missed the SLO — a latency blow-up, not just slowness).
    Throughput on shared runners is noisier than the min-estimator tick
    numbers, hence the wider 2x headroom;
  * weak scaling (--weak-scaling-committed) — the sharded runtime's
    N_max-device / 1-device us/tick ratio in BENCH_weak_scaling.json (a
    same-window self-relative number, robust to machine speed) plus the
    per-device-count `drops_route` counters, which are DETERMINISTIC (the
    trajectory is bitwise reproducible) and held to
    max(committed, ceil(Fig 7 route budget)) — a broken sparse exchange
    either shifts the ratio by integer factors or starts dropping spikes;
  * layout model (--layout-committed) — BENCH_layout.json: the closed-form
    Fig 10 model sections must be unchanged (deterministic math: best_x,
    the default CPU tile, the modelled gains within 1%), and the measured
    human_col column-ablation flat/blocked win must not shrink below
    committed/headroom — the same-window interleaved A/B the PR 8 layout
    claim rests on.

Fails (exit 1) on any regression beyond the headroom factor.
"""
from __future__ import annotations

import argparse
import json
import math
import sys

GATED_SIZES = ("default", "rodent16", "human_col")
METRIC = "scan_us_per_tick"
HEADROOM = 1.25
SERVING_METRIC = "qps_at_slo"
SERVING_HEADROOM = 2.0
WEAK_HEADROOM = 1.5          # ratio-of-ratios on a 2-core shared runner
MODEL_RTOL = 0.01            # closed-form model drift tolerance


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--committed", default=None,
                    help="path to the committed (baseline) tick-loop JSON; "
                         "enables the tick-loop gate")
    ap.add_argument("--fresh", default="BENCH_tick_loop.json",
                    help="path to the freshly measured tick-loop JSON")
    ap.add_argument("--serving-committed", default=None,
                    help="committed (baseline) serving JSON; enables the "
                         "rodent16 QPS-at-SLO gate")
    ap.add_argument("--serving-fresh", default="BENCH_serving.json",
                    help="freshly measured serving JSON")
    ap.add_argument("--weak-scaling-committed", default=None,
                    help="committed (baseline) weak-scaling JSON; enables "
                         "the weak-scaling ratio + route-drop gate")
    ap.add_argument("--weak-scaling-fresh", default="BENCH_weak_scaling.json",
                    help="freshly measured weak-scaling JSON")
    ap.add_argument("--layout-committed", default=None,
                    help="committed (baseline) Fig 10 layout JSON; enables "
                         "the layout-model gate")
    ap.add_argument("--layout-fresh", default="BENCH_layout.json",
                    help="freshly measured Fig 10 layout JSON")
    ap.add_argument("--headroom", type=float, default=HEADROOM)
    ap.add_argument("--serving-headroom", type=float,
                    default=SERVING_HEADROOM)
    ap.add_argument("--weak-headroom", type=float, default=WEAK_HEADROOM)
    args = ap.parse_args()

    failures = []
    if args.committed:
        committed = json.load(open(args.committed))
        fresh = json.load(open(args.fresh))
        for name in GATED_SIZES:
            old, new = committed[name][METRIC], fresh[name][METRIC]
            print(f"{name}/{METRIC}: committed {old:.1f} us, fresh "
                  f"{new:.1f} us ({new / old:.2f}x, "
                  f"limit {args.headroom:.2f}x)")
            if new > old * args.headroom:
                failures.append(f"{name}/{METRIC} {new:.1f} us exceeds "
                                f"committed {old:.1f} us by "
                                f">{args.headroom:.2f}x")

    if args.serving_committed:
        sc = json.load(open(args.serving_committed))
        sf = json.load(open(args.serving_fresh))
        old = sc["rodent16"][SERVING_METRIC]
        new = sf["rodent16"][SERVING_METRIC]
        hr = args.serving_headroom
        print(f"rodent16/{SERVING_METRIC}: committed {old:.2f} qps, fresh "
              f"{new:.2f} qps (floor {old / hr:.2f} qps at "
              f"{hr:.2f}x headroom)")
        if new == 0:
            failures.append(
                f"rodent16/{SERVING_METRIC} is 0 — p95 sojourn "
                f"{sf['rodent16']['p95_sojourn_ms']:.0f} ms missed the "
                f"{sf['rodent16']['slo_ms']:.0f} ms SLO")
        elif new < old / hr:
            failures.append(
                f"rodent16/{SERVING_METRIC} {new:.2f} qps below committed "
                f"{old:.2f} qps by >{hr:.2f}x")

    if args.weak_scaling_committed:
        wc = json.load(open(args.weak_scaling_committed))
        wf = json.load(open(args.weak_scaling_fresh))
        key = "us_per_tick_ratio_max_over_1"
        old, new = wc["scaling"][key], wf["scaling"][key]
        hr = args.weak_headroom
        print(f"weak_scaling/{key}: committed {old:.3f}, fresh {new:.3f} "
              f"({new / old:.2f}x, limit {hr:.2f}x)")
        if new > old * hr:
            failures.append(
                f"weak_scaling/{key} {new:.3f} exceeds committed {old:.3f} "
                f"by >{hr:.2f}x")
        for n, entry in sorted(wf["devices"].items(), key=lambda kv: int(kv[0])):
            got = entry["drops"]["route"]
            budget = math.ceil(entry["fig7_budget"]["route"])
            base = wc["devices"].get(n, {}).get("drops", {}).get("route", 0)
            allowed = max(budget, base)
            print(f"weak_scaling/{n}dev/drops_route: {got} "
                  f"(allowed {allowed}: max(committed {base}, "
                  f"fig7 budget {budget}))")
            if got > allowed:
                failures.append(
                    f"weak_scaling/{n}dev drops_route {got} exceeds "
                    f"max(committed {base}, Fig 7 budget {budget})")

    if args.layout_committed:
        lc = json.load(open(args.layout_committed))
        lf = json.load(open(args.layout_fresh))
        checks = [
            ("paper_dram_model/best_x",
             lc["paper_dram_model"]["best_x"],
             lf["paper_dram_model"]["best_x"], "exact"),
            ("paper_dram_model/gain_vs_direct",
             lc["paper_dram_model"]["gain_vs_direct"],
             lf["paper_dram_model"]["gain_vs_direct"], "rtol"),
            ("cpu_cache_line_model/default_tile",
             lc["cpu_cache_line_model"]["default_tile"],
             lf["cpu_cache_line_model"]["default_tile"], "exact"),
            ("cpu_cache_line_model/flat_over_default",
             lc["cpu_cache_line_model"]["flat_over_default"],
             lf["cpu_cache_line_model"]["flat_over_default"], "rtol"),
        ]
        for name, old, new, kind in checks:
            print(f"layout/{name}: committed {old}, fresh {new}")
            bad = (old != new if kind == "exact"
                   else abs(new - old) > MODEL_RTOL * abs(old))
            if bad:
                failures.append(f"layout/{name} changed: committed {old}, "
                                f"fresh {new} (model regression)")
        if "measured_human_col" in lc:
            if "measured_human_col" not in lf:
                failures.append("layout/measured_human_col missing from "
                                "fresh BENCH_layout.json")
            else:
                k = "column_ablation_flat_over_blocked"
                old = lc["measured_human_col"][k]
                new = lf["measured_human_col"][k]
                hr = args.headroom
                print(f"layout/{k}: committed {old:.2f}x, fresh {new:.2f}x "
                      f"(floor {old / hr:.2f}x at {hr:.2f}x headroom)")
                if new < old / hr:
                    failures.append(
                        f"layout/{k} {new:.2f}x below committed {old:.2f}x "
                        f"by >{hr:.2f}x — the Row-Merge column win shrank")

    if failures:
        sys.exit("perf regression: " + "; ".join(failures))


if __name__ == "__main__":
    main()
