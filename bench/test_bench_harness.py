"""CPU tests of the benchmark's own pieces: the trace reduction, the work
counts, the generator, finding a cell's files by name, the refusal to run
without a TPU, and the shape of BENCHMARK.json."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import generator  # noqa: E402
import harness  # noqa: E402
import work  # noqa: E402
import xtrace  # noqa: E402


# ---------------------------------------------------------------- intervals

def test_union_and_subtract():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == \
        [[0, 3], [5, 9]]
    a = [[0, 10], [20, 30]]
    assert xtrace.subtract(a, [(2, 4), (8, 22), (25, 26)]) == \
        [[0, 2], [4, 8], [22, 25], [26, 30]]
    assert xtrace.subtract(a, []) == a
    assert xtrace.length(xtrace.subtract(a, [(0, 30)])) == 0


def test_exposed_collective_and_busy():
    ev = [("fusion.1", 0, 10), ("all-to-all.3", 5, 20),
          ("fused_col_update_kernel_call.2", 12, 15), ("copy", 18, 25),
          ("all-to-all-done", 30, 32)]
    # collective [5, 20) + [30, 32); others cover [0, 10), [12, 15), [18, 25)
    assert xtrace.exposed_collective_ns(ev) == (10 - 10) + 2 + 3 + 2
    assert xtrace.busy_ns(ev) == 25 + 2
    assert xtrace.time_of(ev, "fused_col_update_kernel_call") == 3
    assert xtrace.time_containing(ev + [("vmap_jit_col_update_kernel_call__.1",
                                        40, 44)], "col_update_kernel_call") \
        == 3 + 4
    assert xtrace.base_name("%fused_row_update_kernel_call.12") == \
        "fused_row_update_kernel_call"
    # inside a scan: the `while` nests every op of the chunk, the exchange
    # too, and does not hide it
    scan = [("while.2", 0, 100), ("fusion.1", 10, 30),
            ("%all_to_all.3 = s32[4,1,4096] all-to-all(...)", 30, 34),
            ("conditional", 40, 90),
            ("fused_col_update_kernel_call", 41, 80), ("copy", 80, 90)]
    assert xtrace.containers(scan) == {0, 3}
    assert xtrace.exposed_collective_ns(scan) == 4


def test_idle_gaps_named_by_host_span():
    tr = xtrace.Trace({0: [("a", 0, 10), ("b", 30, 40), ("c", 45, 50)]},
                      [("bench.wait", 8, 33), ("bench.dispatch", 40, 44)])
    assert xtrace.idle_gaps(tr) == [["bench.wait", 20e-9],
                                    ["bench.dispatch", 5e-9]]
    assert xtrace.top_ops(tr, 2) == [["a", 10e-9], ["b", 10e-9]]


# -------------------------------------------------------------- work counts

def test_work_matches_a_hand_count():
    # 4 HCUs of 64 x 16 (the repo's test scale): 3 touched rows, 2 fired
    rows_ops, rows_bytes = work.row_work(3, 16)
    assert rows_bytes == 3 * (16 * (4 + 5) * 4 + 8 * 4)
    assert rows_ops == 3 * (16 * 31 + 25)
    col_ops, col_bytes = work.col_kernel_work(2, 64)
    assert col_bytes == 2 * 64 * (36 + 8) and col_ops == 2 * 64 * 31
    ops, nbytes = work.tick_work(n_ticks=5, n_hcu=4, cols=16, rows=64,
                                 slots=10, fanout=8, n_rows=3, n_fired=2)
    assert nbytes == (rows_bytes + 2 * 64 * (36 + 16)
                      + 5 * 4 * (16 * 32 + 10 * 4) + 2 * 8 * 8)
    assert ops == rows_ops + 2 * 64 * (31 + 25) + 5 * 4 * 16 * 30
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(50, 30, peak) == (3.0, "bytes")
    assert work.roofline_s(500, 3, peak) == (5.0, "ops")


# ----------------------------------------------------------------- generator

def test_generator_rate_seed_and_padding():
    mix = {"width": 24, "buffer_ticks": 256, "rate_schedule": [[1, 20.0]]}
    a = generator.external_rows(mix, 64, 1200, 2**31 + 5, recurrent=10.0)
    b = generator.external_rows(mix, 64, 1200, 2**31 + 5, recurrent=10.0)
    c = generator.external_rows(mix, 64, 1200, 6, recurrent=10.0)
    assert a.shape == (mix["buffer_ticks"], 64, mix["width"])
    assert a.dtype == np.int32 and np.array_equal(a, b)
    assert not np.array_equal(a, c)
    valid = a < 1200
    assert a.min() >= 0 and a.max() <= 1200
    # padding only after the drawn rows, and the mean at the arrivals the
    # recurrent spikes leave to the external drive
    assert not (~valid[..., :-1] & valid[..., 1:]).any()
    assert abs(valid.sum(-1).mean() - 10.0) < 0.1


def test_poisson_mix_is_all_recurrent():
    """The cells' mix: every HCU's 10 arrivals per ms come from the cell's
    own fan-out (out_rate x fanout), so no external row is staged."""
    mix = json.loads((BENCH / "traffic" / "poisson.json").read_text())
    for f in (BENCH / "configs").glob("*.json"):
        p = json.loads(f.read_text())["params"]
        rec = p["out_rate"] * p["fanout"]
        assert rec == p["in_rate"] == generator.rates(mix).max()
        ext = generator.external_rows(mix, 8, p["rows"], 3, recurrent=rec)
        assert ext.shape == (mix["buffer_ticks"], 8, 0)
    with pytest.raises(ValueError):
        generator.external_rows(mix, 8, 1200, 3, recurrent=5.0)


def test_generator_rate_schedule():
    mix = {"width": 64, "buffer_ticks": 200,
           "rate_schedule": [[10, 36.0], [90, 2.0]]}
    lam = generator.rates(mix)
    assert lam[:10].tolist() == [36.0] * 10 and lam[10:100].max() == 2.0
    assert lam[100:110].tolist() == [36.0] * 10


# -------------------------------------------------------- found by its name

def test_new_cell_config_mix_and_metric_are_found_by_name(tmp_path):
    """Adding a configuration, a traffic mix, a per-layer metric and a cell
    takes new files and entries only."""
    root = tmp_path
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "rodent_share1152.json").read_text())
    cfg["name"] = "rodent_quiet64"
    cfg["params"]["n_hcu"] = 64
    (root / "bench/configs/rodent_quiet64.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/quiet.json").write_text(json.dumps(
        {"why": "t", "width": 8, "buffer_ticks": 32,
         "rate_schedule": [[1, 2.0]]}))
    (root / "bench/metrics/ticks_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['ticks'])\n")
    spec["configs"].append({"name": "rodent_quiet64", "source": "t",
                            "file": "bench/configs/rodent_quiet64.json",
                            "reduced": ["n_hcu"], "why": "t"})
    spec["workloads"].append({"name": "rodent64.quiet",
                              "config": "rodent_quiet64", "traffic": "quiet",
                              "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "ticks_seen", "unit": "ticks",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "sim_ms_per_s",
                              "workloads": ["rodent64.quiet"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    spec = harness.load_spec(root)
    c = harness.cell(spec, root, "rodent64.quiet")
    assert c["config"]["params"]["n_hcu"] == 64
    assert generator.external_rows(c["mix"], 64, 1200, 1).shape == (32, 64, 8)
    names = [m["name"] for m in harness.metrics_of(spec, "rodent64.quiet",
                                                   "per_layer")]
    assert "ticks_seen" in names and "exchange_exposed_ms_per_tick" \
        not in names
    assert "ticks_seen" not in [m["name"] for m in harness.metrics_of(
        spec, "human128.poisson", "per_layer")]
    assert harness.metric_reader(root, "ticks_seen")({"ticks": 7}) == 7.0
    with pytest.raises(SystemExit):
        harness.cell(spec, root, "no.such")


# ------------------------------------------------------ no chip, no result

def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "rodent1152.poisson", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300, cwd=REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "human128.poisson", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       timeout=300, cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        harness.peak_of("TPU v0 imaginary")
    assert harness.peak_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# ------------------------------------------------------ BENCHMARK.json shape

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_its_files():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert spec["command"][1] == "bench/run.py" and spec["paths"] == ["bench"]
    for c in spec["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"] and NAME.match(c["name"])
        assert set(c["reduced"]) <= set(cfg["params"])
        assert set(cfg["limits"]) == {"state_err", "wta_gap",
                                      "fire_mismatch"}
    names = set()
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["name"] not in names
        names.add(w["name"])
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= 1
    for m in spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", names)) <= names
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.25
                                    for m in spec["end_to_end"])


# ------------------------------------------- a trace recorded on the chip

def test_reduction_of_a_recorded_chip_trace():
    """One 16-tick scan chunk of human128.poisson traced on a TPU v5e (op
    names cut at their ' = '): the while loop nests every op of the chunk,
    the conditional nests the column phase."""
    tr = xtrace.Trace.read(BENCH / "testdata" / "human128_chunk.json.gz")
    ev = tr.devices[0]
    assert len(ev) == 4536
    assert xtrace.busy_ns(ev) == 1_109_640_450
    assert xtrace.time_of(ev, "fused_row_update_kernel_call") == 32_470_153
    assert xtrace.time_containing(ev, "col_update_kernel_call") == 409_778_824
    assert xtrace.exposed_collective_ns(ev) == 0
    top = dict(xtrace.top_ops(tr))
    # self time: the container ops fall out, the column kernel leads
    assert next(iter(top)) == "fused_col_update_kernel_call"
    assert "while" not in top and "conditional" not in top
    assert abs(sum(t for _, t in xtrace.self_times(ev)) * 1e-9 -
               1.10964045) < 1e-6

    peak = harness.peak_of("TPU v5 lite")
    ctx = dict(trace=tr, window_s=1.2, ticks=16, chips=1, peak=peak,
               m=type("M", (), dict(n_hcu=128, rows=10000, cols=100,
                                    fanout=100))(),
               n_rows=16 * 2500, n_fired=16 * 13, slots=60)
    read = lambda name: harness.metric_reader(REPO, name)(ctx)
    assert abs(read("device_idle_share") - 100 * (1 - 1.10964045 / 1.2)) \
        < 1e-9
    assert abs(read("non_kernel_ms_per_tick")
               - (1_109_640_450 - 442_248_977) / 1e6 / 16) < 1e-9
    row = work.row_work(16 * 2500, 100)[1] / 819e9
    assert abs(read("row_kernel_roofline") - 100 * row / 0.032470153) < 1e-9
    col = work.col_kernel_work(16 * 13, 10000)[1] / 819e9
    assert abs(read("col_kernel_roofline") - 100 * col / 0.409778824) < 1e-9
    assert read("exchange_exposed_ms_per_tick") is None
    assert 0 < read("tick_mfu") < 1
