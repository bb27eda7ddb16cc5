"""Reduction of a profiler trace of the measured window to device numbers.

Planes named `/device:TPU:<n>` are the chips; their `XLA Ops` line holds one
event per operation the chip ran, named by the operation's HLO text
(`%sort_columns.22 = f32[32,10,51200]{...} custom-call(...)`); an event's
name here is the instruction's name without `%` and its numeric suffix
(`sort_columns`), and its `text` keeps the whole HLO line, shapes included.
Control-flow ops (`while`, `conditional`, `call`) span the ops of their
bodies, which have events of their own, so they are left out.  The window
is the host span the harness opened around it (`bench.window`).  For each
chip:

  busy        the union of its operation intervals inside the window
  op time     the summed durations of its operations, by name
  idle gaps   the stretches of the window no operation covers, each
              labelled with the innermost host span (any host thread) that
              covers the gap's midpoint, or "no host span"
  collective  the time in which a collective operation runs and no other
              operation overlaps it

Busy, op times and collective time are averaged over the chips; gaps are
summed over them by label.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import pathlib
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[int, int]

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINERS = frozenset({"while", "conditional", "call"})
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter|send|recv", re.IGNORECASE)


@dataclasses.dataclass
class Event:
    name: str
    start_ns: int
    end_ns: int
    text: str = ""


def op_name(text: str) -> str:
    """`%sort_columns.22 = f32[...] custom-call(...)` -> `sort_columns`."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


SHAPE = re.compile(r"\b(pred|[fsu]\d+|bf16)\[([\d,]*)\]")


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every (dtype, shape) in an HLO line: the outputs, then the operands."""
    return [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in SHAPE.findall(text)]


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
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
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                  # mean over chips
    busy_per_chip: List[float]
    op_s: Dict[str, float]         # mean over chips, by op name
    op_events: Dict[str, List[Event]]   # every event of each op, all chips
    gap_s: Dict[str, float]        # summed over chips, by host label
    collective_only_s: float       # mean over chips
    collective_s: float            # mean over chips
    chips: int

    def top_ops(self, n: int):
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int):
        return [[k, v] for k, v in sorted(self.gap_s.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _labels(mids: List[int], host: List[Event]) -> List[str]:
    """The innermost (shortest) host span covering each midpoint, by one
    sweep over the spans sorted by start."""
    import heapq
    spans = sorted(host, key=lambda ev: ev.start_ns)
    order = sorted(range(len(mids)), key=lambda i: mids[i])
    out, heap, j = [""] * len(mids), [], 0
    for i in order:
        m = mids[i]
        while j < len(spans) and spans[j].start_ns <= m:
            ev = spans[j]
            heapq.heappush(heap, (ev.end_ns - ev.start_ns, ev.end_ns, j, ev.name))
            j += 1
        while heap and heap[0][1] <= m:
            heapq.heappop(heap)
        out[i] = heap[0][3] if heap else "no host span"
    return out


def reduce_planes(planes: List[Plane], window: Sequence[str] = ("bench.window",),
                  devices: int | None = None) -> Summary:
    host = [ev for p in planes if not DEVICE_PLANE.search(p.name)
            for evs in p.lines.values() for ev in evs]
    spans = [ev for ev in host if ev.name in window]
    if not spans:
        raise ValueError(f"no host span named {list(window)} in the trace")
    lo = min(ev.start_ns for ev in spans)
    hi = max(ev.end_ns for ev in spans)
    inner = [ev for ev in host if ev.name not in window]
    chips = sorted((int(DEVICE_PLANE.search(p.name).group(1)), p)
                   for p in planes if DEVICE_PLANE.search(p.name))
    if devices is not None:
        chips = chips[:devices]
    if not chips:
        raise ValueError("no /device:TPU:n plane in the trace")
    busy, ops, gaps = [], collections.defaultdict(float), collections.defaultdict(float)
    op_events = collections.defaultdict(list)
    coll_only, coll = [], []
    for _, plane in chips:
        evs = [ev for ev in plane.lines.get(OPS_LINE, [])
               if ev.end_ns > lo and ev.start_ns < hi
               and ev.name not in CONTAINERS]
        u = clip(union([(ev.start_ns, ev.end_ns) for ev in evs]), lo, hi)
        busy.append(length(u) / 1e9)
        for ev in evs:
            ops[ev.name] += (min(ev.end_ns, hi) - max(ev.start_ns, lo)) / 1e9
            op_events[ev.name].append(ev)
        idle = subtract([(lo, hi)], u)
        for (s, e), label in zip(idle, _labels([(s + e) // 2 for s, e in idle],
                                               inner)):
            gaps[label] += (e - s) / 1e9
        c_iv = union([(ev.start_ns, ev.end_ns) for ev in evs
                      if COLLECTIVE.search(ev.name)])
        other = [(ev.start_ns, ev.end_ns) for ev in evs
                 if not COLLECTIVE.search(ev.name)]
        coll.append(length(clip(c_iv, lo, hi)) / 1e9)
        coll_only.append(length(clip(subtract(c_iv, other), lo, hi)) / 1e9)
    n = len(chips)
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n, busy_per_chip=busy,
        op_s={k: v / n for k, v in ops.items()}, op_events=dict(op_events),
        gap_s=dict(gaps), collective_only_s=sum(coll_only) / n,
        collective_s=sum(coll) / n, chips=n)


def load_dir(trace_dir: str) -> List[Plane]:
    """Every plane of every .xplane.pb file under trace_dir."""
    from jax.profiler import ProfileData

    planes = []
    for f in sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb")):
        for p in ProfileData.from_file(str(f)).planes:
            device = bool(DEVICE_PLANE.search(p.name))
            lines = {}
            for line in p.lines:
                lines.setdefault(line.name, []).extend(
                    Event(op_name(e.name) if device else e.name,
                          int(e.start_ns), int(e.end_ns),
                          e.name if device else "")
                    for e in line.events)
            planes.append(Plane(p.name, lines))
    return planes


def reduce_dir(trace_dir: str, window: Sequence[str] = ("bench.window",),
               devices: int | None = None) -> Summary:
    return reduce_planes(load_dir(trace_dir), window, devices)


def peaks(device_kind: str) -> dict:
    """The chip's peaks from peaks.json; a device not in the table is an
    error, never a default."""
    table = json.loads((pathlib.Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][device_kind]
