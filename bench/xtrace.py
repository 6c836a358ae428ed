"""From a profiler trace to device intervals, and the interval arithmetic
the per-layer metrics share.

`load` reads the `.xplane.pb` that `jax.profiler` writes and keeps, per
device plane (`/device:TPU:<n>`), the events of its op line as
(name, start_ns, end_ns), and the host's named spans. Everything after
that works on plain lists, so the reduction is checked on a small recorded
trace without the profiler (`testdata/`).
"""
from __future__ import annotations

import gzip
import json
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(           # the TPU names an op `all_to_all.22`
    r"^%?(all[-_]to[-_]all|all[-_]gather|all[-_]reduce|reduce[-_]scatter"
    r"|collective[-_]permute|send|recv)")


class Trace:
    """Device op events per device id and the host's spans, each a list of
    (name, start_ns, end_ns) sorted by start."""

    def __init__(self, devices: dict, host: list):
        self.devices = {int(k): sorted(map(tuple, v), key=lambda e: e[1])
                        for k, v in devices.items()}
        self.host = sorted(map(tuple, host), key=lambda e: e[1])

    def to_json(self) -> dict:
        return {"devices": {str(k): [list(e) for e in v]
                            for k, v in self.devices.items()},
                "host": [list(e) for e in self.host]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(d["devices"], d["host"])

    @classmethod
    def read(cls, path) -> "Trace":
        """A trace saved with `save` (gzip JSON)."""
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))

    def save(self, path):
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)


def load(log_dir, host_names=None) -> Trace:
    """Read the newest `.xplane.pb` under `log_dir`. Host spans are kept only
    where `host_names(name)` is true (default: none)."""
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    evs.extend((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in line.events)
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:") and host_names is not None:
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events if host_names(e.name))
    return Trace(devices, host)


def union(intervals) -> list:
    """Merged, sorted, disjoint [start, end) intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals `a` not covered by `b`."""
    b = union(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def busy_ns(events) -> int:
    """Union of the intervals in which some op runs."""
    return length(union((s, e) for _, s, e in events))


def base_name(name: str) -> str:
    """HLO op name of a trace event without its leading %, its numeric
    suffix and the instruction text the TPU trace appends
    (`%fused_row_update_kernel_call.3 = (f32[...]) custom-call(...)` ->
    `fused_row_update_kernel_call`)."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


def time_of(events, name: str) -> int:
    """Summed duration of the events whose base name is `name`."""
    return sum(e - s for n, s, e in events if base_name(n) == name)


def time_containing(events, part: str) -> int:
    """Summed duration of the events whose base name contains `part` (a
    Pallas kernel keeps its name inside what jit and vmap wrap around it:
    `vmap_jit_col_update_kernel_call__`)."""
    return sum(e - s for n, s, e in events if part in base_name(n))


def containers(events) -> set:
    """Indices into `events` of those that nest another event whole (a
    scan's `while` nests its body's ops, a cond's `conditional` its
    branch's)."""
    out, stack = set(), []         # stack: indices, by start
    for i in sorted(range(len(events)),
                    key=lambda i: (events[i][1], -events[i][2])):
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            out.add(stack[-1])
        stack.append(i)
    return out


def exposed_collective_ns(events) -> int:
    """Time in which a collective runs on the device and no other op does;
    an op that nests others (the scan's `while` around the whole chunk)
    does not count as running beside it."""
    nest = containers(events)
    coll = union((s, e) for n, s, e in events if COLLECTIVE.match(n))
    other = [(s, e) for i, (n, s, e) in enumerate(events)
             if i not in nest and not COLLECTIVE.match(n)]
    return length(subtract(coll, other))


def self_times(events):
    """[(base name, self ns)]: each event's duration less that of the
    events nested in it (the op line nests a scan's body ops in its
    `while`, and a cond's branch in its `conditional`)."""
    out, stack = [], []            # stack: [end, index into out]
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(e, stack[-1][0]) - s
        out.append([base_name(n), e - s])
        stack.append([e, len(out) - 1])
    return out


def top_ops(trace: Trace, k: int = 10):
    """[(op base name, self seconds per device)] of the k longest in total,
    averaged over the traced devices."""
    tot = {}
    for evs in trace.devices.values():
        for b, t in self_times(evs):
            tot[b] = tot.get(b, 0) + t
    nd = max(len(trace.devices), 1)
    return [[n, v / nd / 1e9] for n, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10):
    """[(what the host was doing, seconds)] of the k longest idle gaps on
    device 0 between its first and last op, named by the host span that
    overlaps the gap most ("none" where no span does)."""
    if not trace.devices:
        return []
    dev = trace.devices[min(trace.devices)]
    busy = union((s, e) for _, s, e in dev)
    if not busy:
        return []
    gaps = subtract([[busy[0][0], busy[-1][1]]], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        best, over = "none", 0
        for n, hs, he in trace.host:
            ov = min(e, he) - max(s, hs)
            if ov > over:
                best, over = n, ov
        out.append([best, (e - s) / 1e9])
    return out
