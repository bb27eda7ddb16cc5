"""One run of one benchmark cell: set-up, the measured window, the check of
what the window produced against the plain reference, and the result line.

Everything a cell is made of is found by name: `BENCHMARK.json` names the
cell's configuration and traffic mix; `configs/<config>.json` holds the
configuration's sizes and `configs/<config>.py` its program binding and plain
reference; `mixes/<traffic>.json` the traffic; `limits/<cell>.json` the
limit of each number the check compares; `metrics/<metric>.py` the reader of
each metric, end-to-end or per-layer; `kernels/<kernel>.py` a kernel's operation and byte
counts; `peaks.json` the chip's peaks.
"""
from __future__ import annotations

import collections
import gc
import importlib.util
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

# jax.monitoring durations that make up a call's trace / lower / compile /
# compile-cache work.
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def compiles(counts: dict) -> int:
    """Programs compiled, not loaded from the compile cache (JAX times a
    cache load as a backend compile too)."""
    return counts.get(BACKEND_COMPILE, 0) - counts.get(CACHE_HIT, 0)


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


class Cell:
    """A cell of BENCHMARK.json with its configuration, mix and limits."""

    def __init__(self, name: str, bench: dict | None = None,
                 overrides: dict | None = None):
        bench = bench or load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(have {sorted(cells)})")
        self.bench = bench
        self.spec = cells[name]
        self.name = name
        self.chips = self.spec["chips"]
        configs = {c["name"]: c for c in bench["configs"]}
        centry = configs[self.spec["config"]]
        self.cfg = load_json(ROOT / centry["file"])
        self.cfg_mod = load_module((ROOT / centry["file"]).with_suffix(".py"))
        self.mix = load_json(BENCH / "mixes" / f"{self.spec['traffic']}.json")
        overrides = overrides or {}
        self.cfg.update(overrides.get("cfg", {}))
        self.mix.update(overrides.get("mix", {}))
        lim = BENCH / "limits" / f"{name}.json"
        self.limits = load_json(lim)["limits"] if lim.exists() else {}
        self.limits.update(overrides.get("limits", {}))

    def metric_specs(self, kind: str) -> list:
        """The cell's end_to_end or per_layer entries."""
        e2e = {m["name"] for m in self.bench["end_to_end"]
               if "workloads" not in m or self.name in m["workloads"]}
        out = []
        for m in self.bench[kind]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif kind == "end_to_end" or m["moves"] in e2e:
                out.append(m)
        return out


class Monitor:
    """Counts and sums jax.monitoring events while active."""

    def __init__(self):
        import jax
        self.active = False
        self.durations = collections.defaultdict(float)
        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if self.active:
            self.durations[name] += secs
            self.counts[name] += 1

    def _event(self, name, **kw):
        if self.active:
            self.counts[name] += 1

    def reset(self, active: bool) -> None:
        self.durations.clear()
        self.counts.clear()
        self.active = active


# ------------------------------------------------------------- the system


class System:
    """The program under test, driven the way the cell's mix calls it."""

    def __init__(self, cell: Cell, seed: int, devices):
        from repro.fl import ExecutionPlan, SweepEngine, SweepSpec, run_sweep
        import traffic

        self.cell, self.mix = cell, cell.mix
        self.model = cell.cfg_mod.build(cell.cfg, cell.mix, seed)
        self.traffic = traffic.Traffic(cell.mix, cell.cfg, self.model["dim"],
                                       seed)
        # The mix's `mesh` holds make_sweep_mesh's keywords and its `plan`
        # ExecutionPlan's; `"checkpoint": true` there asks for a checkpoint
        # directory, made under TMPDIR and removed with the run.
        mesh = None
        if "mesh" in self.mix:
            from repro.launch.mesh import make_sweep_mesh
            mesh = make_sweep_mesh(num_devices=len(devices), **self.mix["mesh"])
        plan = dict(self.mix.get("plan", {}))
        self.checkpoint_dir = None
        if plan.pop("checkpoint", False):
            self.checkpoint_dir = tempfile.mkdtemp(prefix="perfbench-ckpt-")
            plan["checkpoint_dir"] = self.checkpoint_dir
        self.plan = ExecutionPlan(mesh=mesh, **plan)
        self.eval_every = self.mix.get("eval_every", 1)
        self._run_sweep, self._spec_of = run_sweep, SweepSpec.build
        self._to_cases = traffic.to_cases
        self.engine = None
        if self.mix["call"] == "engine":
            first = self.traffic.lanes()
            spec = SweepSpec.build(self._to_cases(first, range(len(first))))
            self.engine = SweepEngine(
                self.model["loss_fn"], spec, eval_fn=self.model["eval_fn"],
                eval_every=self.eval_every, plan=self.plan)
        elif self.mix["call"] != "run_sweep":
            raise ValueError(f"unknown call pattern {self.mix['call']!r}")

    @property
    def lanes_per_call(self) -> int:
        return self.traffic.num_lanes

    @property
    def rounds(self) -> int:
        return self.traffic.rounds

    def call(self, call: dict):
        """One grid call; returns once every output is on the host or ready."""
        import jax
        m = self.model
        if self.engine is not None:
            res = self.engine.run(m["params0"], m["batches"], keys=call["keys"])
        else:
            spec = self._spec_of(self._to_cases(call["lanes"], call["seeds"]))
            res = self._run_sweep(m["loss_fn"], m["params0"], m["batches"],
                                  spec, eval_fn=m["eval_fn"],
                                  eval_every=self.eval_every, plan=self.plan)
        jax.block_until_ready(res.params)
        return res

    def close(self) -> None:
        self.engine = None
        if self.checkpoint_dir is not None:
            shutil.rmtree(self.checkpoint_dir, ignore_errors=True)


def kept_result(res, keep_params: bool) -> dict:
    """Host copy of what the check compares from one call's result."""
    acc = res.metrics.get("accuracy")
    out = {"loss": np.asarray(res.loss, np.float64),
           "grad_norm": np.asarray(res.grad_norm, np.float64),
           "accuracy1": None if acc is None else np.asarray(acc[:, 0])}
    if keep_params:
        import jax
        out["params"] = jax.tree_util.tree_map(np.asarray, res.params)
    return out


# ------------------------------------------------------------- the check


def reference_run(model: dict, lanes: list, keys, rounds: int, dtype="float32",
                  fault=None, devices=()) -> list:
    """Follow every lane of one call with the plain reference, over the
    cell's devices."""
    import fedref

    staged = fedref.Staged(model["params0"], model["batches"], rounds, dtype,
                           model["cast_batch"], devices)
    return [fedref.run_lane(lane, key, staged, model["ref_loss"], rounds,
                            eval_fn=model["ref_eval"], fault=fault)
            for lane, key in zip(lanes, keys)]


def _lane_gaps(a, b) -> np.ndarray:
    """Per lane, the largest |a - b| / |b| over the rounds ([S, R] inputs);
    a value the reference also reads as the same non-finite number is no
    gap, any other non-finite one is an infinite gap."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    both_bad = ~np.isfinite(a) & ~np.isfinite(b) & (a == b)
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.abs(a - b) / np.maximum(np.abs(b), 1e-30)
    gap = np.where(both_bad, 0.0, gap)
    gap = np.where(np.isnan(gap), np.inf, gap)
    return np.max(gap, axis=1)


KRUM_FAMILY = ("krum", "multi_krum")


def readings(prog: dict, ref: list, params0, rounds_checked: int,
             grid: list) -> dict:
    """The numbers the check can compare.

    loss_gap      the worst lane's |loss - ref| / |ref| over rounds
                  1..rounds_checked
    gnorm_gap     the same for the norm of each round's aggregate (the
                  step the parameter server applies, divided by alpha)
    loss_gap_median, gnorm_gap_median
                  the median lane's: steady from seed to seed where the
                  worst lane's swings (a Krum selection that flips on a
                  near-tie moves one family's lanes by a whole step)
    loss_gap_family, gnorm_gap_family
                  the largest, over the families of lanes that share a
                  defense and power policy outside the Krum family, of the
                  family's median lane: a fault confined to one family
                  (such as the sort behind median and trimmed-mean lanes)
                  moves it where it leaves the median lane alone; only
                  where the grid has two such families or more
    acc1_gap, acc1_gap_median
                  the worst and the median lane's |accuracy - ref| after
                  round 1 (share of the test set)
    dparam_gap    per leaf, | |w_R - w_0| - |ref w_R - w_0| | over the
                  larger of the reference's change of that leaf and of the
                  median leaf; leaves whose round-1 aggregate is below a
                  thousandth of the median leaf's are left out
    """
    import jax
    r = rounds_checked
    out = {}
    for name, key in (("loss_gap", "loss"), ("gnorm_gap", "grad_norm")):
        lanes = _lane_gaps(prog[key][:, :r],
                           np.stack([x[key][:r] for x in ref]))
        out[name] = float(np.max(lanes))
        out[name + "_median"] = float(np.median(lanes))
        families = collections.defaultdict(list)
        for g, lane in zip(lanes, grid):
            if lane["defense"] not in KRUM_FAMILY:
                families[(lane["defense"], lane["policy"])].append(g)
        if len(families) > 1:
            out[name + "_family"] = float(
                max(np.median(v) for v in families.values()))
    if prog.get("accuracy1") is not None and ref[0]["accuracy1"] is not None:
        acc = np.abs(prog["accuracy1"]
                     - np.array([x["accuracy1"] for x in ref]))
        out["acc1_gap"] = float(np.max(acc))
        out["acc1_gap_median"] = float(np.median(acc))
    if "params" in prog:
        p0 = [np.asarray(x, np.float64)
              for x in jax.tree_util.tree_leaves(params0)]
        worst = 0.0
        for i, x in enumerate(ref):
            pl = [np.asarray(v[i], np.float64)
                  for v in jax.tree_util.tree_leaves(prog["params"])]
            rl = [np.asarray(v, np.float64)
                  for v in jax.tree_util.tree_leaves(x["params"])]
            dp = np.array([np.linalg.norm(a - b) for a, b in zip(pl, p0)])
            dr = np.array([np.linalg.norm(a - b) for a, b in zip(rl, p0)])
            g = np.asarray(x["agg_leaf_norms"])
            moved = g >= 1e-3 * np.median(g)
            floor = np.median(dr[moved])
            gap = np.abs(dp - dr) / np.maximum(dr, floor)
            worst = max(worst, float(np.max(np.where(moved, gap, 0.0))))
        out["dparam_gap"] = worst
    return out


def worst_lanes(prog: dict, ref: list, lanes: list, rounds_checked: int,
                n: int = 3) -> dict:
    """The lanes with the largest loss and aggregate-norm gaps, for the log."""
    r = rounds_checked
    out = {}
    for key in ("loss", "grad_norm"):
        gap = _lane_gaps(prog[key][:, :r], np.stack([x[key][:r] for x in ref]))
        order = np.argsort(-gap)[:n]
        out[key] = [(lanes[i]["name"], float(gap[i])) for i in order]
    return out


def judge(values: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every number that has a limit."""
    return {k: {"value": values[k], "limit": limits[k]}
            for k in sorted(limits) if k in values}


# ------------------------------------------------------------- one run


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(name: str, seed: int, seconds: float, trace: bool, t_start: float,
             devices, overrides: dict | None = None) -> dict:
    import jax

    cell = Cell(name, overrides=overrides)
    monitor = Monitor()
    log(f"cell {name}: config {cell.spec['config']}, mix {cell.spec['traffic']}"
        f", {len(devices)} chip(s), seed {seed}")
    system = System(cell, seed, devices)
    rounds = system.rounds
    ref_rounds = min(cell.mix.get("ref_rounds", 3), rounds)
    keep_params = ref_rounds == rounds

    # Set-up ends with one call on the window's own path: it compiles the
    # program (or loads it from the compile cache) and warms every shape.
    t0 = time.perf_counter()
    monitor.reset(True)
    with jax.profiler.TraceAnnotation("bench.warmup"):
        system.call(system.traffic.next_call())
    setup_counts = dict(monitor.counts)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s (warm-up call {time.perf_counter() - t0:.3f} s,"
        f" {compiles(setup_counts)} programs compiled, "
        f"{setup_counts.get(CACHE_HIT, 0)} loaded from the compile cache)")

    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    walls, attempted, failed, last = [], 0, 0, None
    monitor.reset(True)
    if trace:
        # Host spans come from TraceMe annotations (the harness's bench.*
        # spans and JAX's own); the Python function tracer stays off.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    tw = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            call = system.traffic.next_call()
            last = None   # the previous result's device buffers go first
            c0 = time.perf_counter()
            attempted += 1
            try:
                with jax.profiler.TraceAnnotation("bench.call"):
                    last = (call, system.call(call))
            except Exception as e:  # a call that raises is a failed call
                failed += 1
                log(f"call {attempted} failed: {type(e).__name__}: {e}")
            walls.append(time.perf_counter() - c0)
            if time.perf_counter() - tw >= seconds:
                break
    window_s = time.perf_counter() - tw
    if trace:
        jax.profiler.stop_trace()
    monitor.active = False
    win_durations, win_counts = dict(monitor.durations), dict(monitor.counts)
    done = attempted - failed
    lane_rounds = done * system.lanes_per_call * rounds
    dev = device_info(devices)
    log(f"memory stats of the first chip: {devices[0].memory_stats()}")
    log(f"window {window_s:.3f} s: {attempted} calls ({failed} failed), "
        f"call wall median {statistics.median(walls):.4f} s, worst "
        f"{max(walls):.4f} s; {lane_rounds} lane-rounds; programs compiled "
        f"in the window {compiles(win_counts)}, loaded from the compile cache "
        f"{win_counts.get(CACHE_HIT, 0)}; memory peak "
        f"{dev['memory_peak_bytes']} B")

    # Every metric, end-to-end or per-layer, is read by metrics/<name>.py
    # from what the run recorded; a reader that finds nothing returns None.
    # `system` lets a reader drive the program after the window (a resume).
    ctx = {"calls": done, "window_s": window_s, "lane_rounds": lane_rounds,
           "setup_s": setup_s, "chips": len(devices), "system": system}
    breakdown = None
    if trace:
        import trace_reduce
        summary = trace_reduce.reduce_dir(
            trace_dir, window=("bench.window",), devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.update({
            "summary": summary, "peaks": trace_reduce.peaks(dev["kind"]),
            "flops_per_lane_round": system.model["flops_per_lane_round"],
            "durations": win_durations, "compile_events": COMPILE_EVENTS,
            "load_kernel": lambda k: load_module(BENCH / "kernels" / f"{k}.py"),
        })
    sys.path.insert(0, str(BENCH / "metrics"))
    metrics = {}
    for m in cell.metric_specs("per_layer" if trace else "end_to_end"):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    ctx = None
    if trace:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops(10),
                     "idle_gaps": summary.top_gaps(10)}
        log(f"trace: busy {summary.busy_s:.6f} s of {summary.window_s:.6f} s; "
            f"top ops {summary.top_ops(5)}")

    # The check: the last call of the window, against the plain reference,
    # after the program's state is freed.
    checks, values = {}, {}
    have_last = last is not None   # the window's last call, if it succeeded
    model = system.model
    system.close()
    del system
    if have_last:
        call, res = last
        last = None
        prog = kept_result(res, keep_params)
        del res
        # The weights leave the chip: the reference stages its own copy,
        # split over the cell's chips.
        model["params0"] = jax.device_get(model["params0"])
        gc.collect()
        jax.clear_caches()
        t_ref = time.perf_counter()
        ref = reference_run(model, call["lanes"], call["keys"], ref_rounds,
                            devices=devices)
        values = readings(prog, ref, model["params0"], ref_rounds,
                          call["lanes"])
        log(f"reference: {len(ref)} lanes x {ref_rounds} rounds in "
            f"{time.perf_counter() - t_ref:.3f} s; widest gaps "
            f"{worst_lanes(prog, ref, call['lanes'], ref_rounds)}")
        checks = judge(values, cell.limits)
    correct = (have_last and failed == 0 and bool(checks)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    for k, v in values.items():
        if k not in checks:
            log(f"reading {k} {v!r} (no limit)")
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line
