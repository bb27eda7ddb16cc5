"""The sweep program's own tracing, read from a profiler trace.

The program (`fl/sweep.py`) marks three things:

  layer scopes  `sweep_grads`, `sweep_combine`, `sweep_screen`, `sweep_loss`,
                `sweep_eval`: HLO op_name metadata, which the TPU trace keeps
                as the `tf_op` stat of each device op's event metadata
                (`jit(sweep_run)/while/body/sweep_grads/...`).  The programs
                are named `jit_sweep_run`, `jit_sweep_chunk` and
                `jit_sweep_finalize` (the trace's `XLA Modules` line).
  host spans    `sweep.spec`, `sweep.init`, `sweep.run` (its arguments: run
                index, lanes, rounds) and, nested in it, `sweep.stage`,
                `sweep.prepare`, `sweep.dispatch`, `sweep.checkpoint`,
                `sweep.fetch`: TraceMe spans on the trace's clock.
  one counter   the jax.monitoring scalar `/repro/sweep/h2d_bytes`, once per
                run: the bytes staged from host arrays in that run.

`jax.profiler.ProfileData` shows an event's own stats but not those of its
metadata, so `tf_op` is read here from the XSpace's wire format (a few lines
of protobuf decoding, nothing installed beyond JAX).  `load_dir` returns
`trace_reduce.load_dir`'s planes with each device op a `ScopedEvent` (the
same name, times and text, plus its scope path and module), so
`trace_reduce.reduce_planes` reads them exactly as before.  `reduce_layers`
then gives, over a window:

  device_s  op seconds by layer, summed over chips: the outermost `sweep_*`
            component of the op's scope path; `unscoped` for an op of a
            `jit_sweep_*` program with none (XLA's layout copies, the
            group concatenation, pads); "outside the sweep program" else
  span_s    host seconds of the `sweep.*` spans, by name
  idle_s    device-idle seconds inside the calls (`bench.call` spans),
            summed over chips, by the innermost `sweep.*` span at each gap's
            midpoint, or "no sweep span"

`probe` traces one call of a benchmark cell's system and reduces it; the
per-layer metric readers (`metrics/_layers.py`) run it once, after the
window, in the traced run.  By hand, on the chip:

    python3 perfbench/program_trace.py --workload <cell> --seed <n>
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib
import re
import sys
from typing import Dict, Iterator, List, Sequence, Tuple

import trace_reduce
from trace_reduce import Event, Plane

UNSCOPED = "unscoped"
OUTSIDE = "outside the sweep program"
NO_SPAN = "no sweep span"
H2D_BYTES = "/repro/sweep/h2d_bytes"
MODULES_LINE = "XLA Modules"
MODULE = re.compile(r"^(.*)\((\d+)\)$")
KERNELS = frozenset(p.stem for p in
                    (pathlib.Path(__file__).parent / "kernels").glob("*.py"))


@dataclasses.dataclass
class ScopedEvent(Event):
    scope: str = ""     # the op's tf_op path, without its ":type" suffix
    module: str = ""    # the program it ran in (`jit_sweep_run`)


# ------------------------------------------------ XSpace wire format
#
# XSpace      1: planes (XPlane)
# XPlane      2: name, 3: lines (XLine), 4: event_metadata (map int64 ->
#             XEventMetadata), 5: stat_metadata (map int64 -> XStatMetadata)
# XLine       2: name, 3: timestamp_ns, 4: events (XEvent)
# XEvent      1: metadata_id, 2: offset_ps, 3: duration_ps
# XEventMetadata  1: id, 2: name, 5: stats (XStat)
# XStatMetadata   1: id, 2: name
# XStat       1: metadata_id, 2: double, 3: uint64, 4: int64, 5: string,
#             6: bytes, 7: ref (an XStatMetadata id whose name is the value)


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint or fixed
    field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entries(entries) -> Iterator[Tuple[int, memoryview]]:
    for entry in entries:
        key, value = 0, memoryview(b"")
        for f, v in _fields(entry):
            if f == 1:
                key = _signed(v)
            elif f == 2:
                value = v
        yield key, value


def _op_meta(entries, stat_names) -> Dict[int, Tuple[str, str, int]]:
    """Event metadata id -> (name, tf_op, program_id)."""
    out = {}
    for key, value in _map_entries(entries):
        name, stats = "", {}
        for f, v in _fields(value):
            if f == 2:
                name = _text(v)
            elif f == 5:
                sid, sval = None, None
                for sf, sv in _fields(v):
                    if sf == 1:
                        sid = _signed(sv)
                    elif sf in (3, 4):
                        sval = sv    # raw: a program id reads as unsigned
                    elif sf == 5:
                        sval = _text(sv)
                    elif sf == 7:
                        sval = stat_names.get(_signed(sv), "")
                stats[stat_names.get(sid)] = sval
        tf_op = str(stats.get("tf_op", ""))
        out[key] = (name, tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op,
                    stats.get("program_id"))
    return out


def _line(buf) -> Tuple[str, int, List[Tuple[int, int, int]]]:
    """One XLine: (name, timestamp_ns, [(metadata id, offset_ps,
    duration_ps)])."""
    name, ts, events = "", 0, []
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            ts = _signed(v)
        elif f == 4:
            ev = dict.fromkeys((1, 2, 3), 0)
            for ef, evalue in _fields(v):
                if ef in ev:
                    ev[ef] = _signed(evalue)
            events.append((ev[1], ev[2], ev[3]))
    return name, ts, events


def _device_plane(buf: memoryview) -> Dict[str, List[ScopedEvent]]:
    """{line name: events} of one device XPlane, each op with its scope and
    the program it ran in."""
    lines, ev_meta, stat_meta = [], [], []
    for f, v in _fields(buf):
        if f == 3:
            lines.append(_line(v))
        elif f == 4:
            ev_meta.append(v)
        elif f == 5:
            stat_meta.append(v)
    stat_names = {key: next((_text(v) for f, v in _fields(value) if f == 2),
                            "")
                  for key, value in _map_entries(stat_meta)}
    meta = _op_meta(ev_meta, stat_names)
    modules = {}
    for lname, _, events in lines:
        if lname == MODULES_LINE:
            for mid, _, _ in events:
                m = MODULE.match(meta.get(mid, ("",))[0])
                if m:
                    modules[int(m.group(2)) % (1 << 64)] = m.group(1)
    out = {}
    for lname, ts, events in lines:
        evs = out.setdefault(lname, [])
        for mid, offset, dur in events:
            name, scope, program = meta.get(mid, ("", "", None))
            # whole nanoseconds, as jax.profiler.ProfileData gives them
            start = ts + offset // 1000
            evs.append(ScopedEvent(
                trace_reduce.op_name(name), start, start + dur // 1000, name,
                scope=scope, module=modules.get(program, "")))
    return out


def device_planes(path: str) -> List[Tuple[str, Dict[str, List[ScopedEvent]]]]:
    """[(device plane name, {line name: ScopedEvents})] of one .xplane.pb,
    in the file's order."""
    out = []
    for f, v in _fields(memoryview(pathlib.Path(path).read_bytes())):
        if f != 1:
            continue
        name = next((_text(pv) for pf, pv in _fields(v) if pf == 2), "")
        if trace_reduce.DEVICE_PLANE.search(name):
            out.append((name, _device_plane(v)))
    return out


def load_dir(trace_dir: str) -> List[Plane]:
    """trace_reduce.load_dir's planes, each device op a ScopedEvent."""
    planes = trace_reduce.load_dir(trace_dir)
    scoped = iter([plane for f in sorted(
        pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        for plane in device_planes(str(f))])
    for p in planes:
        if not trace_reduce.DEVICE_PLANE.search(p.name):
            continue
        name, lines = next(scoped, (None, {}))
        if name != p.name:
            raise ValueError(f"device plane {p.name!r} read as {name!r}")
        if trace_reduce.OPS_LINE in p.lines:
            p.lines[trace_reduce.OPS_LINE] = lines.get(trace_reduce.OPS_LINE,
                                                       [])
    return planes


# ------------------------------------------------ reduction


def layer(ev: Event) -> str:
    """The outermost `sweep_*` component of the op's scope path; UNSCOPED
    for an op of a sweep program with none; OUTSIDE for every other op."""
    scope = getattr(ev, "scope", "")
    for part in scope.split("/"):
        if part.startswith("sweep_"):
            return part
    module = getattr(ev, "module", "")
    if module.startswith("jit_sweep_") or scope.startswith("jit(sweep_"):
        return UNSCOPED
    return OUTSIDE


@dataclasses.dataclass
class Layers:
    op_layer_s: Dict[str, Dict[str, float]]   # op name -> layer -> seconds
    span_s: Dict[str, float]     # host seconds by sweep.* span name
    span_n: Dict[str, int]       # spans by name
    idle_s: Dict[str, float]     # idle inside the calls, summed over chips
    calls: int                   # bench.call spans in the window
    chips: int

    @property
    def device_s(self) -> Dict[str, float]:
        """Device seconds by layer, summed over chips."""
        out = collections.defaultdict(float)
        for by_layer in self.op_layer_s.values():
            for lay, sec in by_layer.items():
                out[lay] += sec
        return dict(out)

    @property
    def program_s(self) -> float:
        """Device seconds of the sweep programs' ops."""
        return sum(v for k, v in self.device_s.items() if k != OUTSIDE)

    def covered_share(self) -> float | None:
        """The share of the idle time inside the calls that a sweep.* span
        covers."""
        total = sum(self.idle_s.values())
        if total <= 0:
            return None
        return 1.0 - self.idle_s.get(NO_SPAN, 0.0) / total


def reduce_layers(planes: List[Plane], window: Sequence[str] = ("bench.window",),
                  calls: Sequence[str] = ("bench.call",),
                  devices: int | None = None) -> Layers:
    host = [ev for p in planes if not trace_reduce.DEVICE_PLANE.search(p.name)
            for evs in p.lines.values() for ev in evs]
    win = [ev for ev in host if ev.name in window]
    if not win:
        raise ValueError(f"no host span named {list(window)} in the trace")
    lo = min(ev.start_ns for ev in win)
    hi = max(ev.end_ns for ev in win)
    sweep_spans = [ev for ev in host if ev.name.startswith("sweep.")
                   and ev.end_ns > lo and ev.start_ns < hi]
    span_s, span_n = collections.defaultdict(float), collections.Counter()
    for ev in sweep_spans:
        span_s[ev.name] += (min(ev.end_ns, hi) - max(ev.start_ns, lo)) / 1e9
        span_n[ev.name] += 1
    call_iv = trace_reduce.union(
        [(ev.start_ns, ev.end_ns) for ev in host if ev.name in calls])
    call_iv = trace_reduce.clip(call_iv, lo, hi) or [(lo, hi)]
    chips = sorted((int(trace_reduce.DEVICE_PLANE.search(p.name).group(1)), p)
                   for p in planes if trace_reduce.DEVICE_PLANE.search(p.name))
    if devices is not None:
        chips = chips[:devices]
    idle_s = collections.defaultdict(float)
    op_layer_s = collections.defaultdict(lambda: collections.defaultdict(float))
    for _, plane in chips:
        evs = [ev for ev in plane.lines.get(trace_reduce.OPS_LINE, [])
               if ev.end_ns > lo and ev.start_ns < hi
               and ev.name not in trace_reduce.CONTAINERS]
        for ev in evs:
            op_layer_s[ev.name][layer(ev)] += (min(ev.end_ns, hi)
                                               - max(ev.start_ns, lo)) / 1e9
        busy = trace_reduce.union([(ev.start_ns, ev.end_ns) for ev in evs])
        gaps = trace_reduce.subtract(call_iv, busy)
        labels = trace_reduce._labels([(s + e) // 2 for s, e in gaps],
                                      sweep_spans)
        for (s, e), label in zip(gaps, labels):
            idle_s[NO_SPAN if label == "no host span" else label] += \
                (e - s) / 1e9
    return Layers(op_layer_s={k: dict(v) for k, v in op_layer_s.items()},
                  span_s=dict(span_s), span_n=dict(span_n),
                  idle_s=dict(idle_s),
                  calls=len([ev for ev in host if ev.name in calls
                             and ev.end_ns > lo and ev.start_ns < hi]),
                  chips=len(chips))


def table(values: Dict[str, float], scale: float = 1.0) -> List[list]:
    return [[k, v * scale] for k, v in sorted(values.items(),
                                              key=lambda kv: -kv[1])]


# ------------------------------------------------ one traced call


@dataclasses.dataclass
class Probe:
    layers: Layers
    h2d_bytes: List[float]   # one value per sweep run in the call
    lane_rounds: int         # of the traced call

    def per_lane_round_us(self, *names: str) -> float | None:
        """Device microseconds per lane-round in these layers, summed over
        chips; None where no sweep program ran."""
        if self.layers.program_s <= 0 or not self.lane_rounds:
            return None
        s = sum(self.layers.device_s.get(n, 0.0) for n in names)
        return 1e6 * s / self.lane_rounds

    def per_call_ms(self, *spans: str) -> float | None:
        """Host milliseconds per call in these spans; None where no sweep
        span was recorded."""
        if not self.layers.span_n.get("sweep.run") or not self.layers.calls:
            return None
        return (1e3 * sum(self.layers.span_s.get(n, 0.0) for n in spans)
                / self.layers.calls)

    def h2d_mb_per_call(self) -> float | None:
        if not self.h2d_bytes or not self.layers.calls:
            return None
        return sum(self.h2d_bytes) / 1e6 / self.layers.calls

    def summary(self) -> dict:
        ly = self.layers
        return {
            "lane_rounds": self.lane_rounds, "calls": ly.calls,
            "chips": ly.chips,
            "device_us_per_lane_round": table(
                ly.device_s, 1e6 / max(self.lane_rounds, 1)),
            "program_device_s": ly.program_s,
            "span_ms_per_call": table(ly.span_s, 1e3 / max(ly.calls, 1)),
            "idle_ms_by_sweep_span": table(ly.idle_s, 1e3),
            "idle_covered_share": ly.covered_share(),
            "h2d_bytes": self.h2d_bytes,
            "top_ops_us_per_lane_round_by_layer": {
                lay: table({op: v[lay] for op, v in ly.op_layer_s.items()
                            if lay in v}, 1e6 / max(self.lane_rounds, 1))[:6]
                for lay in ly.device_s},
            # the benchmark's kernels (kernels/<name>.py): seconds by layer
            "kernel_layers": {k: v for k, v in ly.op_layer_s.items()
                              if k in KERNELS}}


def probe(system, chips: int) -> Probe:
    """Trace one call of a benchmark cell's `system` (harness.System) and
    reduce it: its device time by layer, its sweep.* spans, its idle time
    by span and its h2d_bytes counter."""
    import shutil
    import tempfile

    import jax

    seen = []

    def listener(name, value, **kw):
        if name == H2D_BYTES:
            seen.append(value)

    trace_dir = tempfile.mkdtemp(prefix="perfbench-layers-")
    jax.monitoring.register_scalar_listener(listener)
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        call = system.traffic.next_call()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                with jax.profiler.TraceAnnotation("bench.call"):
                    system.call(call)
        finally:
            jax.profiler.stop_trace()
        layers = reduce_layers(load_dir(trace_dir), devices=chips)
    finally:
        jax.monitoring.unregister_scalar_listener(listener)
        shutil.rmtree(trace_dir, ignore_errors=True)
    return Probe(layers, seen, system.lanes_per_call * system.rounds)


def main() -> int:
    """Set a cell up as the benchmark does, warm it up with one call, then
    probe one call; print the breakdown as JSON."""
    import argparse
    import json
    import os
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=main.__doc__.split(";")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    bench = pathlib.Path(__file__).resolve().parent
    root = bench.parent
    cache = root / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path[:0] = [str(root / "src"), str(bench)]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import harness

    cell = harness.Cell(args.workload)
    devices = jax.devices()[:cell.chips]
    system = harness.System(cell, args.seed, devices)
    system.call(system.traffic.next_call())
    setup_s = time.perf_counter() - t_start
    got = probe(system, len(devices))
    system.close()
    out = {"workload": args.workload, "seed": args.seed,
           "device": devices[0].device_kind, "setup_s": setup_s,
           **got.summary()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
