"""Device time by the program's named scopes.

The tick body opens one `jax.named_scope` per phase (`tick/queue`,
`tick/rows`, `tick/wta`, `tick/cols`, and `lane_pad` inside the kernel
wrappers); the compiler keeps each HLO instruction's scope path as its
`metadata.op_name`, e.g. `jit(_run_chunk)/while/body/closed_call/tick/cols/
lane_pad/pad`. A TPU trace carries that path as the `tf_op` stat of an op's
event metadata, which `jax.profiler.ProfileData` does not expose, so `load`
reads the `.xplane.pb` itself (a protobuf `XSpace`, decoded field by field
below). Ops whose metadata has no `tf_op` (a scan's `while`, a cond's
`conditional`) take the `op_name` of their instruction in the HLO module
that the trace's `/host:metadata` plane holds for the op's program. The
result is a map from instruction name (`fusion.84`) to scope path, kept
beside `xtrace.Trace`'s (name, start_ns, end_ns) events; an op XLA made
itself (a layout copy, the loop it expands a scatter into) has the empty
path.

The trace nests a container's body ops inside its event (a scan's
`while`, a cond's `conditional`, the loop of an expanded scatter), so an
op without a path of its own takes the path of the innermost op it is
nested in: the loop XLA builds for a scatter under `tick/cols` counts as
`cols`, while the copies XLA adds at the top of the scan body stay outside
every phase. `phase_ns` sums the self time (`xtrace.self_times`: a
`while` does not count its body twice) of the ops whose path so found has
a given component. Nothing here recompiles: the reduction is a function of
the saved trace.
"""
from __future__ import annotations

import gzip
import json
import struct
from pathlib import Path

import xtrace

TICK = "tick"
PHASES = ("queue", "rows", "wta", "cols")
LANE_PAD = "lane_pad"
METADATA_PLANE = "/host:metadata"


# ---------------------------------------------------------------------------
# protobuf wire format, just enough for XSpace and HloProto
# ---------------------------------------------------------------------------

def _varint(b: bytes, i: int):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes):
    """(field number, value) of a serialized message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(v):
    """(key, value) of a protobuf map entry."""
    key = val = None
    for f, x in _fields(v):
        if f == 1:
            key = x
        elif f == 2:
            val = x
    return key, val


def _stat(v, stat_names: dict):
    """(name, value) of an XStat."""
    mid, val = 0, None
    for f, x in _fields(v):
        if f == 1:
            mid = x
        elif f == 2:
            val = struct.unpack("<d", x)[0]
        elif f in (3, 4, 7):
            val = x
        elif f == 5:
            val = _text(x)
        elif f == 6:
            val = bytes(x)
    return stat_names.get(mid, ""), val


def _planes(data: bytes):
    """(name, {event metadata id: (name, {stat: value})}) per XPlane."""
    for f, plane in _fields(data):
        if f != 1:
            continue
        name, raw_events, stat_names = "", [], {}
        for g, x in _fields(plane):
            if g == 2:
                name = _text(x)
            elif g == 4:
                raw_events.append(x)
            elif g == 5:
                k, meta = _map_entry(x)
                stat_names[k] = next(
                    (_text(y) for h, y in _fields(meta or b"") if h == 2), "")
        events = {}
        for x in raw_events:
            k, meta = _map_entry(x)
            ev_name, stats = "", {}
            for h, y in _fields(meta or b""):
                if h == 2:
                    ev_name = _text(y)
                elif h == 5:
                    s, val = _stat(y, stat_names)
                    stats[s] = val
            events[k] = (ev_name, stats)
        yield name, events


def hlo_op_names(hlo_proto: bytes) -> dict:
    """{instruction name: metadata.op_name} over every computation of a
    serialized HloProto (HloModuleProto 1 > computations 3 > instructions 2
    > name 1, metadata 7 > op_name 2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, ins in _fields(comp):
                if h != 2:
                    continue
                name = op_name = ""
                for k, x in _fields(ins):
                    if k == 1:
                        name = _text(x)
                    elif k == 7:
                        op_name = next((_text(y) for m, y in _fields(x)
                                        if m == 2), "")
                out[name] = op_name
    return out


# ---------------------------------------------------------------------------
# the scope map of a trace
# ---------------------------------------------------------------------------

def instruction(name: str) -> str:
    """HLO instruction name of a trace event (`%fusion.84 = f32[...] ...` ->
    `fusion.84`)."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def scopes_of_xspace(data: bytes) -> dict:
    """{instruction name: scope path} of every op on a device plane of a
    serialized XSpace. An instruction name that two programs in the trace
    give different paths maps to the empty path."""
    planes = list(_planes(data))
    modules = {}
    for name, events in planes:
        if name == METADATA_PLANE:
            for pid, (_, stats) in events.items():
                if isinstance(stats.get("Hlo Proto"), bytes):
                    modules[pid] = stats["Hlo Proto"]
    parsed = {}
    out, clash = {}, set()
    for name, events in planes:
        if not xtrace.DEVICE_PLANE.match(name):
            continue
        for ev_name, stats in events.values():
            op = instruction(ev_name)
            tf_op = stats.get("tf_op")
            if isinstance(tf_op, str):
                path = tf_op.rsplit(":", 1)[0]
            else:
                pid = stats.get("program_id")
                if pid in modules and pid not in parsed:
                    parsed[pid] = hlo_op_names(modules[pid])
                path = parsed.get(pid, {}).get(op, "")
            if out.get(op, path) != path:
                clash.add(op)
            out[op] = path
    for op in clash:
        out[op] = ""
    return out


def load(log_dir) -> dict:
    """The scope map of the newest `.xplane.pb` under `log_dir`."""
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return scopes_of_xspace(files[-1].read_bytes())


def read(path) -> "xtrace.Trace":
    """A trace saved as `Trace.save` does, with its scope map under the key
    "scopes", as a Trace with `.scopes` (empty for a trace saved without
    one)."""
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    tr = xtrace.Trace.from_json(d)
    tr.scopes = d.get("scopes", {})
    return tr


# ---------------------------------------------------------------------------
# device time by scope
# ---------------------------------------------------------------------------

def has(path: str, component: str) -> bool:
    return component in path.split("/")


def attributed(events, scopes: dict):
    """[(op base name, self ns, scope path)] in `xtrace.self_times` order
    (sorted by start, longest first), an op without a path of its own
    taking that of the innermost event it is nested in. An op that XLA
    merged from several carries their paths joined by ";": it counts under
    the first."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    out, stack = [], []            # stack: [end, path]
    for (n, s, e), (b, t) in zip(evs, xtrace.self_times(evs)):
        while stack and stack[-1][0] <= s:
            stack.pop()
        path = scopes.get(instruction(n), "").split(";")[0] \
            or (stack[-1][1] if stack else "")
        out.append((b, t, path))
        stack.append([e, path])
    return out


def phase_ns(events, scopes: dict, phase: str) -> int:
    """Summed self time of the events whose scope path has the component
    `phase`."""
    return sum(t for _, t, path in attributed(events, scopes)
               if has(path, phase))


def unscoped(events, scopes: dict, k: int = 10):
    """[(op base name, self ns)] of the k longest ops outside every phase
    of the tick, summed by base name."""
    tot = {}
    for b, t, path in attributed(events, scopes):
        if not any(has(path, p) for p in PHASES):
            tot[b] = tot.get(b, 0) + t
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


def phase_ms_per_tick(ctx: dict, phase: str):
    """Device self time under `phase`, averaged over the chips, per traced
    tick; None where the trace has no scope map or no op under `tick` (a
    program without the scopes)."""
    scopes = getattr(ctx["trace"], "scopes", None)
    devs = ctx["trace"].devices
    if not scopes or not devs or ctx["ticks"] <= 0 \
            or not any(has(p, TICK) for p in scopes.values()):
        return None
    ns = [phase_ns(ev, scopes, phase) for ev in devs.values()]
    return sum(ns) / len(ns) / 1e6 / ctx["ticks"]
