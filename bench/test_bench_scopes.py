"""CPU tests of the scope reduction (`scopes.py`), the phase readers and the
compile clock (`compile_time.py`): the XSpace decoding on a trace built
here, self time under nested events, and the traces recorded on the chip."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import compile_time  # noqa: E402
import harness  # noqa: E402
import scopes  # noqa: E402
import xtrace  # noqa: E402

PHASE_METRICS = ("queue", "rows", "wta", "cols", "lane_pad")


# ------------------------------------------------------- a protobuf by hand

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def fld(num: int, val) -> bytes:
    if isinstance(val, int):
        return _varint(num << 3) + _varint(val)
    val = val.encode() if isinstance(val, str) else val
    return _varint(num << 3 | 2) + _varint(len(val)) + val


def plane(name, events, stat_names, pid=1):
    """An XPlane with event metadata only: events are (id, name, {stat id:
    str or bytes or int})."""
    b = fld(1, pid) + fld(2, name)
    for sid, sname in stat_names.items():
        b += fld(5, fld(1, sid) + fld(2, fld(1, sid) + fld(2, sname)))
    for eid, ename, stats in events:
        meta = fld(1, eid) + fld(2, ename)
        for sid, v in stats.items():
            kind = 5 if isinstance(v, str) else 6 if isinstance(v, bytes) \
                else 3
            meta += fld(5, fld(1, sid) + fld(kind, v))
        b += fld(4, fld(1, eid) + fld(2, meta))
    return b


def hlo(module: str, ops: dict) -> bytes:
    """A serialized HloProto of one computation holding `ops`
    ({instruction name: op_name})."""
    comp = fld(1, "main")
    for name, op_name in ops.items():
        comp += fld(2, fld(1, name) + fld(2, "op") +
                    fld(7, fld(1, "op") + fld(2, op_name)))
    return fld(1, fld(1, module) + fld(3, comp))


STATS = {1: "tf_op", 2: "program_id", 3: "Hlo Proto"}


def xspace() -> bytes:
    dev0 = plane("/device:TPU:0", [
        (7, "%fusion.3 = f32[] fusion()",
         {1: "jit(f)/while/body/tick/rows/mul:", 2: 5}),
        (8, "%while.7 = (f32[]) while()", {2: 5}),
        (9, "%copy.1 = f32[] copy()", {2: 5}),
        (10, "%pad.2 = f32[] pad()", {2: 6}),
    ], STATS)
    # a second program whose pad.2 sits elsewhere: a clash
    dev1 = plane("/device:TPU:1", [
        (7, "%pad.2 = f32[] pad()",
         {1: "jit(g)/tick/cols/lane_pad/pad:", 2: 6}),
        (8, "%fusion.3 = f32[] fusion()",
         {1: "jit(f)/while/body/tick/rows/mul:", 2: 5}),
    ], STATS, pid=2)
    meta = plane(scopes.METADATA_PLANE, [
        (5, "jit_f(5)", {3: hlo("jit_f", {"while.7": "jit(f)/while",
                                          "copy.1": ""})}),
        (6, "jit_g(6)", {3: hlo("jit_g", {"pad.2": "jit(g)/tick/rows/pad"})}),
    ], STATS, pid=3)
    host = plane("/host:CPU", [(1, "%fusion.3 = f32[] fusion()",
                                {1: "host/tick/wta:"})], STATS, pid=4)
    return fld(1, dev0) + fld(1, dev1) + fld(1, meta) + fld(1, host)


def test_scope_map_from_tf_op_and_the_hlo_module():
    data = xspace()
    from jax.profiler import ProfileData
    assert [p.name for p in ProfileData.from_serialized_xspace(data).planes] \
        == ["/device:TPU:0", "/device:TPU:1", scopes.METADATA_PLANE,
            "/host:CPU"]
    assert scopes.scopes_of_xspace(data) == {
        "fusion.3": "jit(f)/while/body/tick/rows/mul",   # tf_op
        "while.7": "jit(f)/while",                        # the HLO module
        "copy.1": "",                                     # inserted by XLA
        "pad.2": "",                                      # two programs
    }
    assert scopes.hlo_op_names(hlo("m", {"a.1": "x/y"})) == {"a.1": "x/y"}


# ------------------------------------------------------ self time by scope

EVENTS = [("%while.1 = (...) while()", 0, 100),
          ("%fusion.1", 10, 30),
          ("%conditional.3", 40, 90),
          ("%fused_col_update_kernel_call.4", 45, 70),
          ("%while.8", 71, 81),
          ("%dynamic-update-slice.9", 72, 80),
          ("%pad.5", 82, 88),
          ("%copy.6", 92, 99),
          ("%fusion.7", 100, 105)]
SCOPES = {"while.1": "jit(c)/while",
          "fusion.1": "jit(c)/while/body/tick/rows/mul",
          "conditional.3": "jit(c)/while/body/tick/cols/cond",
          "fused_col_update_kernel_call.4":
              "jit(c)/while/body/tick/cols/cond/branch_1_fun/pallas_call",
          "pad.5": "jit(c)/while/body/tick/cols/cond/branch_1_fun/lane_pad/"
                   "pad",
          "while.8": "",
          "dynamic-update-slice.9": "",
          "copy.6": "",
          "fusion.7": "jit(c)/tick/queue/add"}


def test_phase_self_time_counts_nested_ops_once():
    got = {p: scopes.phase_ns(EVENTS, SCOPES, p)
           for p in scopes.PHASES + (scopes.LANE_PAD,)}
    # cols: the conditional's own 9, the kernel 25, the pad 6 and the loop
    # XLA made inside the conditional (2 + 8), which has no path of its own
    assert got == {"queue": 5, "rows": 20, "wta": 0, "cols": 50,
                   "lane_pad": 6}
    # the while's own 23 and the copy 7 it nests are in no phase
    assert scopes.unscoped(EVENTS, SCOPES) == [("while", 23), ("copy", 7)]
    assert sum(got[p] for p in scopes.PHASES) + 30 == xtrace.busy_ns(EVENTS)


def test_phase_readers_need_the_scopes():
    tr = xtrace.Trace({0: EVENTS, 1: EVENTS}, [])
    read = lambda name, ctx: harness.metric_reader(REPO, name)(ctx)
    ctx = dict(trace=tr, ticks=5)
    # a trace without a scope map (a program without the scopes): nothing
    assert all(read(f"{m}_ms_per_tick", ctx) is None for m in PHASE_METRICS)
    tr.scopes = {k: v.replace("tick/", "") for k, v in SCOPES.items()}
    assert read("cols_ms_per_tick", ctx) is None
    tr.scopes = SCOPES
    assert read("cols_ms_per_tick", ctx) == 50 / 1e6 / 5
    assert read("lane_pad_ms_per_tick", ctx) == 6 / 1e6 / 5
    assert read("wta_ms_per_tick", ctx) == 0.0
    assert read("setup_compile_s", ctx) is None
    assert read("setup_compile_s", dict(ctx, compile_s=3.5)) == 3.5


def test_compile_clock_merges_nested_spans():
    clock = compile_time.CompileClock()
    trace, lower, compile_ = compile_time.EVENTS
    for ev, s, e in ((trace, 0.0, 10.0), (trace, 2.0, 5.0),
                     (compile_, 20.0, 30.0), ("/jax/other", 40.0, 50.0),
                     (lower, 9.0, 12.0)):
        clock.on_span(ev, s, e, fun_name="f")
    assert clock.seconds(until=25.0) == 12.0 + 5.0
    assert clock.seconds(until=100.0) == 22.0

    import jax
    clock = compile_time.CompileClock().register()
    try:
        jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0)).block_until_ready()
    finally:
        jax.monitoring.unregister_event_time_span_listener(clock.on_span)
    assert len(clock.spans) >= 3 and clock.seconds() > 0


# ------------------------------------------- traces recorded on the chip

def test_a_trace_saved_without_scopes_reads_as_none():
    tr = scopes.read(BENCH / "testdata" / "human128_chunk.json.gz")
    assert tr.scopes == {} and len(tr.devices[0]) == 4536
    assert scopes.phase_ms_per_tick(dict(trace=tr, ticks=16), "cols") is None


# One scan chunk of each cell, traced on a TPU v5e (op names cut at their
# ' = ', the scope map cut to the chunk's ops): device self time per phase,
# and what XLA added outside every phase (the copies of the planes into the
# scan carry, at the top of the `while` body).
RECORDED = {
    "human128": dict(ticks=16, events=4176, busy=1_096_800_120,
                     queue=2_333_753, rows=183_076_135, wta=143_495,
                     cols=732_647_078, lane_pad=479_030_586,
                     unscoped=178_599_659, copy=177_633_740),
    "rodent1152": dict(ticks=8, events=12364, busy=1_518_476_264,
                       queue=10_359_083, rows=174_572_388, wta=303_572,
                       cols=1_261_038_699, lane_pad=86_621_564,
                       unscoped=72_202_522, copy=68_908_953),
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_phases_of_a_recorded_chip_chunk(cell):
    want = RECORDED[cell]
    tr = scopes.read(BENCH / "testdata" / f"{cell}_scoped_chunk.json.gz")
    ev = tr.devices[0]
    assert len(ev) == want["events"] and xtrace.busy_ns(ev) == want["busy"]
    got = {p: scopes.phase_ns(ev, tr.scopes, p)
           for p in scopes.PHASES + (scopes.LANE_PAD,)}
    assert got == {p: want[p] for p in got}
    un = dict(scopes.unscoped(ev, tr.scopes, 10**6))
    assert sum(un.values()) == want["unscoped"]
    assert max(un, key=un.get) == "copy" and un["copy"] == want["copy"]
    # the phases and the unscoped rest partition the chunk's busy time
    assert sum(got[p] for p in scopes.PHASES) + want["unscoped"] \
        == want["busy"]
    # lane padding is only ever part of the row or the column phase
    for _, _, path in scopes.attributed(ev, tr.scopes):
        if scopes.has(path, scopes.LANE_PAD):
            assert scopes.has(path, "rows") or scopes.has(path, "cols")
    ctx = dict(trace=tr, ticks=want["ticks"])
    for m in PHASE_METRICS:
        assert harness.metric_reader(REPO, f"{m}_ms_per_tick")(ctx) == \
            want[m] / 1e6 / want["ticks"]
