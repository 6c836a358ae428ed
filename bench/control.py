"""Readings that the correctness limits are set from, at a cell's own size.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process that owns the cell's chips: set the cell up
and run a window of `--seconds` exactly as `run.py` does, then replay the
run in the reference twice, chip by chip as `run.py` does, teacher-forced
on the system's fired history:

  * in float32, the precision the configuration states: the system's
    readings (`state_err`, `wta_gap`, `fire_mismatch` against it), which
    set the lower end of each limit;
  * in bfloat16, the control: the reference put in the system's place in
    the next precision below. Its final state and the winners its own WTA
    picks are read against the float32 replay by the same numbers, which
    set the upper end.

Prints one JSON line per seed and, last, the largest system reading and the
smallest control reading of each number. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

NUMBERS = ("state_err", "wta_gap", "fire_mismatch")


def host_pieces(held, shares, devs, m, block: int = 128):
    """`pieces` for `reference.compare_states` of reference shares held on
    the host, each against the share `shares[d]` on its chip, moved there
    a block of HCUs at a time."""
    import jax
    import reference as ref
    h = m.n_hcu // len(devs)
    block = min(block, h)
    for st, ref_st, dev in zip(held, shares, devs, strict=True):
        for h0 in range(0, h, block):
            out = {}
            for k in ref.LEAVES:
                per = 1 if k in ref.HCU_LEAVES else m.rows
                out[k] = jax.device_put(
                    getattr(st, k)[h0 * per:(h0 + block) * per], dev)
            yield out, ref_st, h0


def readings(r: "harness.Run") -> dict:
    """{"system": {...}, "control": {...}} of one set-up run whose window
    has closed. The control's final shares wait on the host while the
    float32 replay runs, so that the two fit each chip beside the
    system's share."""
    import jax
    import jax.numpy as jnp
    import reference as ref

    fired = r.history()
    args = (r.m, r.conn, r.ext, fired, r.seed, r.chunk, r.devs)
    st_b, _, own_b = harness.replay(*args, dtype=jnp.bfloat16)
    st_b = jax.device_get(st_b)
    shares, stats, _ = harness.replay(*args, probes=(fired, own_b))
    sys_err = ref.compare_states(r.m, r.prog.pieces(shares)).worst()
    ctl_err = ref.compare_states(
        r.m, host_pieces(st_b, shares, r.devs, r.m)).worst()
    ctl_gate = int((((own_b >= 0) != (fired >= 0))).sum())
    return {"system": {"state_err": sys_err[1],
                       "wta_gap": float(stats["gaps"][:, 0].max()),
                       "fire_mismatch": int(stats["mismatch"].sum()),
                       "worst_field": sys_err[0]},
            "control": {"state_err": ctl_err[1],
                        "wta_gap": float(stats["gaps"][:, 1].max()),
                        "fire_mismatch": ctl_gate,
                        "worst_field": ctl_err[0]},
            "ticks": int(fired.shape[0])}


def main(argv=None, root: Path | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = (root or harness.BENCH.parent).resolve()
    spec = harness.load_spec(root)
    c = harness.cell(spec, root, args.workload)
    harness.import_program(root)
    harness.setup_jax(root)
    devs = harness.require_chips(int(c["workload"]["chips"]))
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = harness.Run(c, seed, devs)
        r.window(args.seconds)
        out = {"seed": seed, **readings(r),
               "seconds": time.perf_counter() - t0}
        print(json.dumps(out), flush=True)
        rows.append(out)
        del r
    summary = {
        "system_max": {k: max(o["system"][k] for o in rows)
                       for k in NUMBERS},
        "control_min": {k: min(o["control"][k] for o in rows)
                        for k in NUMBERS},
        "limits": c["config"]["limits"]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
