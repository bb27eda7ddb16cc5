"""Sweep-engine throughput: looped FLTrainer vs scan vs tree-state vs flat.

Runs the same S-scenario x R-round grid (fig-4 style: CI/BEV x attacker
count on the paper MLP, D=50890) through the execution strategies:

  looped      FLTrainer.run        — one jitted dispatch per round, and one
                                     fresh compile per scenario (the config
                                     is baked into each trainer's closure):
                                     the seed repo's only mode
  scan        FLTrainer.run_scan   — rounds compiled into one lax.scan,
                                     still one program (compile) per scenario
  scan+vmap   SweepEngine(flat_state=False)
                                   — rounds scanned AND scenarios stacked
                                     into one vmapped lane axis (the PR 1
                                     engine): per round it still pays the
                                     [S, U, D] flatten/concat and a per-leaf
                                     unflatten + update
  flat        SweepEngine          — the flat-state warm path: params stay
                                     one [S, D] matrix across the scan and
                                     the combine + PS update fuse into
                                     `batched_floa_step`
  flat+chunk  SweepEngine(chunk_rounds=C)
                                   — scan-of-chunks: outer Python loop over
                                     ceil(R/C) inner C-round scans (same
                                     trajectories; [C, ...] batch blocks
                                     staged per chunk instead of the whole
                                     [R, ...] stack living on device)
  flat+chunk+async
              SweepEngine(chunk_rounds=C, async_staging=True)
                                   — chunked with double-buffered staging:
                                     chunk k+1's block is sliced host-side
                                     and device_put (async) while chunk k
                                     computes; the A/B against flat+chunk
                                     isolates the input-pipeline overlap
                                     (expect wins on data-bound configs —
                                     large batch blocks relative to round
                                     compute — and noise-level parity on
                                     compute-bound ones like this MLP grid)
  flat+shmap  SweepEngine(mesh=...)
                                   — the flat scan shard_mapped over a
                                     ("data",) mesh (enable with --sharded;
                                     on CPU hosts set
                                     XLA_FLAGS=--xla_force_host_platform_device_count=8
                                     BEFORE launching to fan the lane axis
                                     over 8 fake devices)

Two aggregate rounds/sec (S*R / wall) numbers per engine:

  cold   end-to-end including compilation — what a figure script actually
         pays to produce its grid once.  The looped/scan baselines pay S
         compiles; the sweep engines pay one, so their advantage GROWS with S.
  warm   steady-state rerun of the already-compiled program(s) — isolates
         per-round dispatch/batching efficiency (best of --reps reruns, since
         shared CI boxes are noisy).

--defenses additionally benches the defense-code lane axis: one flat-state
engine per defense family (analog FLOA reference, mean, median, trimmed-mean,
(multi-)Krum, geometric median) plus the mixed all-families grid — under the
default GROUPED dispatch ("mixed": static lane partition by defense code,
each family's kernel runs once over its own sub-slab) and the PR-3 per-lane
lax.switch reference ("mixed_switch": every family computed for every lane) —
each at --defense-scenarios lanes x --defense-rounds rounds (its own knobs —
the screening kernels add sort/pairwise-distance work per round, so the
defense section is sized explicitly rather than inheriting the headline
shape), with per-defense cold/warm rounds-per-sec recorded under the JSON's
"defenses" key and the grouped-vs-switch warm speedup at the top level.

--scenario-axes benches the adaptive-adversary lane axes: one engine each
for the legacy CI/BEV x STRONGEST grid, Gauss-Markov fading (the (state, h)
scan-carry tuple), K-of-U participation (masked stats/combine/screening),
colluding/omniscient directional cohorts (post-combine payload injection),
and the all-axes mixed spec — recorded under the JSON's "scenario_axes" key so
the cross-axis trace tax is tracked (each axis is a trace-time decision
for the whole sweep).

--resume benches the preemption-safety machinery: the checkpointed chunked
engine (ExecutionPlan(checkpoint_dir=...) committing the full resume carry
at every chunk boundary) A/B'd against the plain chunked engine on the same
grid — the warm-rows ratio is the checkpoint tax — plus the wall time of a
`run(resume=True)` restoring off the latest committed boundary.  Recorded
under the JSON's "resume" key; the perf gate checks the
chunked/chunked_ckpt warm rows shape-aware (lanes/rounds/chunk_rounds/dim
must match the baseline, else skipped).

--workers benches the worker-population scaling series: the mixed-defense
worker grid (analog FLOA + median / trimmed-mean / Krum lanes) at each U in
--workers-series (default 10,1000,10000) on a deliberately tiny MLP, both
unsharded and worker-sharded over every visible device
(ExecutionPlan(mesh=make_sweep_mesh(n, worker_shards=n)) — the OTA combine
as a psum over worker shards), recorded under the JSON's "workers" key.
The perf gate skips workers rows whose (u, lanes, rounds, dim,
worker_shards) shape differs from the baseline's instead of failing them.

Results are printed as CSV and written to a machine-readable JSON
(--out, default BENCH_sweep.json) so the perf trajectory is tracked across
PRs; the CI sweep-sharded job uploads it as a workflow artifact AND gates on
it: --check-against BASELINE.json --tolerance 0.5 compares every fresh warm
rounds/sec row against the committed baseline and exits non-zero when a row
drops below baseline * (1 - tolerance) — silent throughput regressions in
the defense hot path fail the build instead of landing.

  PYTHONPATH=src:. python benchmarks/sweep_bench.py [--rounds R] [--scenarios S]
      [--sharded] [--reps N] [--skip-looped] [--defenses]
      [--defense-rounds R] [--defense-scenarios S] [--chunk-rounds C]
      [--resume] [--resume-rounds R] [--resume-lanes S]
      [--out BENCH_sweep.json]
      [--check-against BENCH_sweep.json] [--tolerance 0.5]

See docs/benchmarks.md for how to read BENCH_sweep.json, what the CI
`--check-against --tolerance 0.5` perf gate does, and how to regenerate the
committed baseline when a PR legitimately changes throughput.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    Experiment,
    Policy,
    experiment_floa,
    figure_setup,
)
from repro import make_sweep_mesh
from repro.core import AttackConfig, AttackType, ChannelConfig, FLOAConfig
from repro.core import DefenseSpec, PowerConfig, first_n_mask
from repro.data import FederatedSampler
from repro.fl import (
    ExecutionPlan,
    FLTrainer,
    ScenarioCase,
    SweepEngine,
    SweepSpec,
)
from repro.models import mlp_loss

DEFENSE_FAMILIES = [
    ("floa", None),  # analog reference lanes (BEV policy)
    ("mean", DefenseSpec(name="mean")),
    ("median", DefenseSpec(name="median")),
    ("trimmed_mean", DefenseSpec(name="trimmed_mean", trim=3)),
    ("krum", DefenseSpec(name="krum", num_byzantine=3)),
    ("multi_krum", DefenseSpec(name="multi_krum", num_byzantine=3, multi=3)),
    ("geometric_median", DefenseSpec(name="geometric_median")),
]


def defense_grid(mc, family: str, spec, num: int):
    """`num` lanes of one defense family across attacker counts 0..4."""
    u, d = mc.num_workers, mc.dim
    cases = []
    for i in range(num):
        n = i % 5
        floa = FLOAConfig(
            channel=ChannelConfig(num_workers=u, sigma=1.0, noise_std=0.0),
            power=PowerConfig(num_workers=u, dim=d, p_max=mc.p_max,
                              policy=Policy.BEV if spec is None else Policy.EF),
            attack=AttackConfig(
                attack=AttackType.STRONGEST if n else AttackType.NONE,
                byzantine_mask=first_n_mask(u, n)))
        cases.append(ScenarioCase(
            f"{family}@N{n}#{i}", floa, 0.05, seed=300 + i,
            defense=spec if spec is not None else DefenseSpec()))
    return cases


def bench_defenses(mc, shards, params, rounds: int, scenarios: int,
                   reps: int) -> dict:
    """Per-defense-family engine throughput (cold + interleaved best-of warm),
    plus the mixed grid with every family as lanes of ONE program — under the
    default grouped dispatch ("mixed") and the PR-3 per-lane lax.switch
    reference ("mixed_switch"), so BENCH_sweep.json records the grouped-
    dispatch speedup on the grid where it matters."""
    batches = FederatedSampler(shards, mc.batch_per_worker,
                               seed=1).stack_rounds(rounds)
    grids = [(name, defense_grid(mc, name, spec, scenarios), ExecutionPlan())
             for name, spec in DEFENSE_FAMILIES]
    mixed = [c for _, cases, _ in grids for c in cases[:max(1, scenarios // 2)]]
    grids.append(("mixed", mixed, ExecutionPlan()))
    grids.append(("mixed_switch", mixed, ExecutionPlan(grouped_dispatch=False)))

    cold, runners = {}, []
    for name, cases, plan in grids:
        engine = SweepEngine(mlp_loss, SweepSpec.build(cases), plan=plan)
        run_once = (lambda e=engine: e.run(params, batches))
        t0 = time.perf_counter()
        run_once()
        cold[name] = time.perf_counter() - t0
        runners.append((name, len(cases), run_once))

    best = {name: float("inf") for name, _, _ in runners}
    for _ in range(reps):
        for name, _, run_once in runners:
            t0 = time.perf_counter()
            run_once()
            best[name] = min(best[name], time.perf_counter() - t0)

    print(f"# defense lanes: R={rounds} rounds x S={scenarios} lanes/family "
          f"(mixed: {len(mixed)}), D={mc.dim}, U={mc.num_workers}")
    print("defense,lanes,cold_rounds_per_sec,warm_rounds_per_sec")
    out = {}
    for name, lanes, _ in runners:
        total = lanes * rounds
        out[name] = dict(
            lanes=lanes, rounds=rounds,
            cold_rounds_per_sec=round(total / cold[name], 2),
            warm_rounds_per_sec=round(total / best[name], 2))
        print(f"{name},{lanes},{out[name]['cold_rounds_per_sec']:.1f},"
              f"{out[name]['warm_rounds_per_sec']:.1f}")
    return out


MARKOV_RHO = 0.9


def scenario_axes_grid(mc, axes: str, num: int):
    """`num` lanes exercising one adaptive-adversary axis (or all of them):
    `legacy` is the plain CI/BEV x STRONGEST grid, `markov` adds rho=0.9
    Gauss-Markov fading, `participation` samples K=U-3 of U clients per
    round, `directional` alternates COLLUDING/OMNISCIENT cohorts, and
    `mixed_axes` stacks all of it in one spec (the worst-case trace)."""
    u, d = mc.num_workers, mc.dim
    cases = []
    for i in range(num):
        n = i % 4 + (0 if axes in ("legacy", "markov", "participation")
                     else 1)
        rho = MARKOV_RHO if axes in ("markov", "mixed_axes") and i % 2 else 0.0
        part = u - 3 if axes in ("participation", "mixed_axes") and i % 3 \
            else None
        if axes == "directional" or (axes == "mixed_axes" and i % 2):
            attack = (AttackType.COLLUDING if i % 4 < 2
                      else AttackType.OMNISCIENT)
        else:
            attack = AttackType.STRONGEST if n else AttackType.NONE
        floa = FLOAConfig(
            channel=ChannelConfig(num_workers=u, sigma=1.0, noise_std=0.05,
                                  markov_rho=rho),
            power=PowerConfig(num_workers=u, dim=d, p_max=mc.p_max,
                              policy=Policy.BEV if i % 2 else Policy.CI),
            attack=AttackConfig(attack=attack,
                                byzantine_mask=first_n_mask(u, n)))
        cases.append(ScenarioCase(f"{axes}@N{n}#{i}", floa, 0.05,
                                  seed=500 + i, participants=part))
    return cases


def bench_scenario_axes(mc, shards, params, rounds: int, scenarios: int,
                        reps: int) -> dict:
    """Adaptive-adversary axis throughput (--scenario-axes): what each new
    lane axis costs on top of the legacy grid.  `markov` pays the (state, h)
    scan-carry tuple, `participation` the masked stats/combine/screening
    reductions, `directional` the post-combine payload injection, and
    `mixed_axes` all three in one program — each axis is a trace-time
    decision for the whole sweep, so these rows bound the cross-axis tax."""
    batches = FederatedSampler(shards, mc.batch_per_worker,
                               seed=1).stack_rounds(rounds)
    grids = [(name, scenario_axes_grid(mc, name, scenarios))
             for name in ("legacy", "markov", "participation", "directional",
                          "mixed_axes")]
    cold, runners = {}, []
    for name, cases in grids:
        engine = SweepEngine(mlp_loss, SweepSpec.build(cases))
        run_once = (lambda e=engine: e.run(params, batches))
        t0 = time.perf_counter()
        run_once()
        cold[name] = time.perf_counter() - t0
        runners.append((name, len(cases), run_once))

    best = {name: float("inf") for name, _, _ in runners}
    for _ in range(reps):
        for name, _, run_once in runners:
            t0 = time.perf_counter()
            run_once()
            best[name] = min(best[name], time.perf_counter() - t0)

    print(f"# scenario axes: R={rounds} rounds x S={scenarios} lanes/axis, "
          f"D={mc.dim}, U={mc.num_workers}")
    print("axis,lanes,cold_rounds_per_sec,warm_rounds_per_sec")
    out = {}
    for name, lanes, _ in runners:
        total = lanes * rounds
        out[name] = dict(
            lanes=lanes, rounds=rounds,
            cold_rounds_per_sec=round(total / cold[name], 2),
            warm_rounds_per_sec=round(total / best[name], 2))
        print(f"{name},{lanes},{out[name]['cold_rounds_per_sec']:.1f},"
              f"{out[name]['warm_rounds_per_sec']:.1f}")
    return out


def worker_grid(u: int, dim: int):
    """Mixed-defense lanes at worker population U: one analog FLOA (BEV)
    lane plus median / trimmed-mean / Krum screening lanes, U//10 STRONGEST
    attackers — the large-U showdown in miniature, exercising the psum OTA
    combine and every large-U defense routing tier at once."""
    n_atk = max(1, u // 10)
    fams = [None,
            DefenseSpec(name="median"),
            DefenseSpec(name="trimmed_mean", trim=n_atk),
            DefenseSpec(name="krum", num_byzantine=n_atk)]
    cases = []
    for i, spec in enumerate(fams):
        floa = FLOAConfig(
            channel=ChannelConfig(num_workers=u, sigma=1.0,
                                  noise_std=0.05 if spec is None else 0.0),
            power=PowerConfig(num_workers=u, dim=dim, p_max=1.0,
                              policy=Policy.BEV if spec is None
                              else Policy.EF),
            attack=AttackConfig(attack=AttackType.STRONGEST,
                                byzantine_mask=first_n_mask(u, n_atk)))
        name = "floa" if spec is None else spec.name
        cases.append(ScenarioCase(f"{name}@U{u}", floa, 0.05, seed=400 + i,
                                  defense=spec if spec is not None
                                  else DefenseSpec()))
    return cases


def bench_workers(series, rounds: int, reps: int) -> dict:
    """U-scaling series (--workers): the mixed-defense worker grid at each
    U in `series`, unsharded AND worker-sharded over every visible device
    (the sharded row is skipped on single-device hosts).  A deliberately
    tiny MLP (D~260) keeps the model-side work flat so the rows isolate how
    the engine scales with the worker population: per-worker gradient
    production, the standardization handshake, the OTA combine, and the
    large-U defense kernels (U=10 unrolled sort / direct Krum, U=1e3
    bitonic / blocked Krum, U=1e4 jnp.sort fallback / blocked Krum).
    Timing reps are capped at 2 for this section: the U=1e4 rows are
    minutes-per-rep on a CPU box and best-of-2 is enough for a gate with
    0.5 tolerance.  On a CPU backend the sharded row is additionally
    skipped from U=1e4 up (marked `sharded_skipped` in the record): the
    digital screening lanes recompute their defense on every shard after
    the sub-slab all-gather, so 8 emulated devices on a 2-core box do 8x
    the work serially — tens of minutes for a row that measures thread
    thrash, not the engine."""
    d_in, d_h = 16, 4
    dim = d_in * d_h + d_h
    reps = min(reps, 2)

    def loss(params, b):
        pred = jax.nn.relu(b["x"] @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - b["y"]) ** 2)

    k = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(k, (d_in, d_h)),
              "w2": jax.random.normal(k, (d_h, 1))}
    shards_w = jax.device_count()
    out = {}
    print(f"# worker scaling: U series {list(series)}, D={dim}, "
          f"R={rounds} rounds, worker_shards={shards_w}")
    print("u,engine,lanes,cold_rounds_per_sec,warm_rounds_per_sec")
    for u in series:
        rng = np.random.default_rng(u)
        batches = {
            "x": rng.normal(size=(rounds, u, d_in)).astype(np.float32),
            "y": rng.normal(size=(rounds, u, 1)).astype(np.float32)}
        spec = SweepSpec.build(worker_grid(u, dim))
        engines = {"unsharded": SweepEngine(loss, spec)}
        row = dict(u=u, lanes=len(spec), rounds=rounds, dim=dim,
                   worker_shards=shards_w)
        if shards_w > 1:
            if u >= 10_000 and jax.default_backend() == "cpu":
                row["sharded_skipped"] = "cpu-emulated collectives"
                print(f"{u},sharded,{len(spec)},skipped (cpu-emulated "
                      "collectives)")
            else:
                engines["sharded"] = SweepEngine(
                    loss, spec, plan=ExecutionPlan(
                        mesh=make_sweep_mesh(shards_w,
                                             worker_shards=shards_w)))
        for name, engine in engines.items():
            t0 = time.perf_counter()
            engine.run(params, batches)
            cold = time.perf_counter() - t0
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                engine.run(params, batches)
                best = min(best, time.perf_counter() - t0)
            total = len(spec) * rounds
            row[name] = dict(cold_rounds_per_sec=round(total / cold, 2),
                             warm_rounds_per_sec=round(total / best, 2))
            print(f"{u},{name},{len(spec)},"
                  f"{row[name]['cold_rounds_per_sec']:.1f},"
                  f"{row[name]['warm_rounds_per_sec']:.1f}")
        out[f"U{u}"] = row
    return out


def lm_grid(u: int, dim: int, n_atk: int):
    """The LM-lane showdown in miniature: one analog BEV lane plus a median
    screening lane, STRONGEST attackers in both — the two defense routing
    tiers (shard-local columnwise vs analog OTA) that dominate the
    real-model lanes."""
    lanes = [("floa", None), ("median", DefenseSpec(name="median"))]
    cases = []
    for i, (name, spec) in enumerate(lanes):
        floa = FLOAConfig(
            channel=ChannelConfig(num_workers=u, sigma=1.0,
                                  noise_std=0.05 if spec is None else 0.0),
            power=PowerConfig(num_workers=u, dim=dim, p_max=1.0,
                              policy=Policy.BEV if spec is None
                              else Policy.EF),
            attack=AttackConfig(attack=AttackType.STRONGEST,
                                byzantine_mask=first_n_mask(u, n_atk)))
        cases.append(ScenarioCase(f"{name}@D{dim}", floa, 0.05, seed=500 + i,
                                  defense=spec if spec is not None
                                  else DefenseSpec()))
    return cases


def bench_lm(series, rounds: int, reps: int) -> dict:
    """D-scaling series (--lm): the big-D regime the real-model LM lanes
    live in, at each D in `series`, unsharded AND ("model",)-sharded over
    every visible device.  The state is a single [D] leaf with a linear
    loss whose per-worker gradient is O(D) to produce, so — mirroring the
    tiny-MLP philosophy of --workers — the rows isolate how the ENGINE
    scales with the flat dimension: the [S, U, D] slab, the standardize
    stats reduction (psum-of-partials when sharded), the OTA combine, the
    columnwise screening sort at D past the kernel-routing thresholds, and
    the TILE_D ghost-column padding.  Real-model wall time (transformer
    fwd/bwd flops) is the LM lane's own business, measured end to end by
    examples/train_floa_lm.py; timing it here would drown the engine ops
    the gate is meant to guard.  Timing reps are capped at 2: the D=1e7
    rows move ~GB slabs per round on a CPU box."""
    u, n_atk = 8, 2
    reps = min(reps, 2)

    def loss(params, b):
        # [D]-state linear probe: grad_w = (mean(w) - t) / D * ones — O(D)
        # per worker with a per-worker batch scalar, no [B, D] features to
        # stage (at D=1e7 a feature matrix would be the benchmark).
        return 0.5 * jnp.mean((jnp.mean(params["w"]) - b["t"]) ** 2)

    shards_m = jax.device_count()
    out = {}
    print(f"# lm d-scaling: D series {list(series)}, U={u}, "
          f"R={rounds} rounds, model_shards={shards_m}")
    print("d,engine,lanes,cold_rounds_per_sec,warm_rounds_per_sec")
    for d in series:
        rng = np.random.default_rng(d % (1 << 31))
        params = {"w": jnp.asarray(rng.normal(size=(d,)).astype(np.float32)
                                   / np.sqrt(d))}
        batches = {"t": rng.normal(size=(rounds, u, 1)).astype(np.float32)}
        spec = SweepSpec.build(lm_grid(u, d, n_atk))
        engines = {"unsharded": SweepEngine(loss, spec)}
        row = dict(d=d, u=u, lanes=len(spec), rounds=rounds,
                   model_shards=shards_m)
        if shards_m > 1:
            engines["model_sharded"] = SweepEngine(
                loss, spec, plan=ExecutionPlan(
                    mesh=make_sweep_mesh(shards_m, model_shards=shards_m)))
        for name, engine in engines.items():
            t0 = time.perf_counter()
            engine.run(params, batches)
            cold = time.perf_counter() - t0
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                engine.run(params, batches)
                best = min(best, time.perf_counter() - t0)
            total = len(spec) * rounds
            row[name] = dict(cold_rounds_per_sec=round(total / cold, 2),
                             warm_rounds_per_sec=round(total / best, 2))
            print(f"{d},{name},{len(spec)},"
                  f"{row[name]['cold_rounds_per_sec']:.1f},"
                  f"{row[name]['warm_rounds_per_sec']:.1f}")
        out[f"D{d}"] = row
    return out


def bench_resume(mc, shards, params, rounds: int, scenarios: int, reps: int,
                 chunk: int) -> dict:
    """Preemption-safety machinery (--resume): checkpoint tax and resume
    restore.

    `chunked` vs `chunked_ckpt` is the same chunked grid with and without
    a checkpoint_dir (every chunk boundary commits the full resume carry
    atomically) — the warm ratio is what preemption safety costs per
    round.  `resume_latest_s` times `run(resume=True)` restoring off the
    last committed boundary and finishing the run: the wall a preempted
    fleet pays to get back to where it died."""
    batches = FederatedSampler(shards, mc.batch_per_worker,
                               seed=1).stack_rounds(rounds)
    exps = grid(scenarios, rounds)
    spec = SweepSpec.build([
        ScenarioCase(e.name, floa, alpha, seed=e.seed)
        for e, (floa, alpha) in zip(exps,
                                    [experiment_floa(e, mc) for e in exps])])
    chunk = max(1, min(chunk, rounds))
    total = len(spec) * rounds
    out = dict(lanes=len(spec), rounds=rounds, chunk_rounds=chunk,
               dim=mc.dim)
    print(f"# resume: R={rounds} rounds x S={len(spec)} lanes, "
          f"chunk={chunk}, D={mc.dim}")
    with tempfile.TemporaryDirectory() as ckpt_dir:
        engines = [
            ("chunked", SweepEngine(mlp_loss, spec, plan=ExecutionPlan(
                chunk_rounds=chunk))),
            ("chunked_ckpt", SweepEngine(mlp_loss, spec, plan=ExecutionPlan(
                chunk_rounds=chunk, checkpoint_dir=ckpt_dir))),
        ]
        cold, best = {}, {}
        for name, eng in engines:
            t0 = time.perf_counter()
            eng.run(params, batches)
            cold[name] = time.perf_counter() - t0
            best[name] = float("inf")
        for _ in range(reps):
            for name, eng in engines:
                t0 = time.perf_counter()
                eng.run(params, batches)
                best[name] = min(best[name], time.perf_counter() - t0)
        print("engine,cold_rounds_per_sec,warm_rounds_per_sec")
        for name, _ in engines:
            out[name] = dict(
                cold_rounds_per_sec=round(total / cold[name], 2),
                warm_rounds_per_sec=round(total / best[name], 2))
            print(f"{name},{out[name]['cold_rounds_per_sec']:.1f},"
                  f"{out[name]['warm_rounds_per_sec']:.1f}")
        out["checkpoint_tax"] = round(best["chunked_ckpt"]
                                      / best["chunked"], 3)
        # Resume off the last committed boundary: restore + final chunk(s).
        t0 = time.perf_counter()
        engines[1][1].run(params, batches, resume=True)
        out["resume_latest_s"] = round(time.perf_counter() - t0, 4)
        print(f"# checkpoint tax (warm chunked_ckpt/chunked wall): "
              f"{out['checkpoint_tax']:.2f}x; resume off latest boundary: "
              f"{out['resume_latest_s']:.2f}s")
    return out


def check_regressions(fresh: dict, baseline: dict,
                      tolerance: float) -> (list, list):
    """Per-row warm-throughput regression gate (the CI perf check).

    Compares fresh warm rounds/sec against a committed baseline record for
    every engine and defense row present in BOTH; a row fails when

        fresh_warm < baseline_warm * (1 - tolerance)

    (tolerance is generous — CI boxes are shared and the committed baseline
    may come from different hardware; the gate catches structural collapses
    like the grouped dispatch silently falling back to the switch path, not
    single-digit noise).  Rows whose shape parameters differ between the
    records are skipped, not failed.  Returns (failures, notes).
    """
    fails, notes = [], []

    def gate(section, name, f_row, b_row):
        f_w, b_w = f_row["warm_rounds_per_sec"], b_row["warm_rounds_per_sec"]
        floor = b_w * (1.0 - tolerance)
        if f_w < floor:
            fails.append(f"{section}/{name}: warm {f_w:.1f} r/s < floor "
                         f"{floor:.1f} (baseline {b_w:.1f}, "
                         f"tolerance {tolerance})")

    if all(fresh.get(k) == baseline.get(k) for k in ("scenarios", "rounds")):
        chunk_mismatch = (fresh.get("chunk_rounds")
                          != baseline.get("chunk_rounds"))
        for name, b_row in (baseline.get("engines") or {}).items():
            f_row = (fresh.get("engines") or {}).get(name)
            if f_row is None:
                notes.append(f"engines/{name}: not in fresh run, skipped")
            elif "chunk" in name and chunk_mismatch:
                # A different chunk size is a different program shape (e.g.
                # --chunk-rounds 1 is per-chunk dispatch overhead x R); like
                # the defense rows' lanes/rounds guard, skip rather than
                # report a phantom regression.
                notes.append(f"engines/{name}: chunk_rounds differs from "
                             "baseline, skipped")
            else:
                gate("engines", name, f_row, b_row)
    else:
        notes.append("engine rows skipped: scenarios/rounds differ from "
                     "baseline")
    for name, b_row in (baseline.get("defenses") or {}).items():
        f_row = (fresh.get("defenses") or {}).get(name)
        if f_row is None:
            notes.append(f"defenses/{name}: not in fresh run, skipped")
        elif (f_row.get("lanes"), f_row.get("rounds")) != (
                b_row.get("lanes"), b_row.get("rounds")):
            notes.append(f"defenses/{name}: lane/round shape differs, skipped")
        else:
            gate("defenses", name, f_row, b_row)
    for name, b_row in (baseline.get("scenario_axes") or {}).items():
        f_row = (fresh.get("scenario_axes") or {}).get(name)
        if f_row is None:
            notes.append(f"scenario_axes/{name}: not in fresh run, skipped")
        elif (f_row.get("lanes"), f_row.get("rounds")) != (
                b_row.get("lanes"), b_row.get("rounds")):
            notes.append(f"scenario_axes/{name}: lane/round shape differs, "
                         "skipped")
        else:
            gate("scenario_axes", name, f_row, b_row)
    b_res = baseline.get("resume")
    if b_res:
        f_res = fresh.get("resume")
        if f_res is None:
            notes.append("resume: not in fresh run, skipped")
        elif any(f_res.get(k) != b_res.get(k)
                 for k in ("lanes", "rounds", "chunk_rounds", "dim")):
            # A different grid/chunk shape is a different program — skip,
            # don't fail (mirrors the workers-series guard).
            notes.append("resume: lanes/rounds/chunk shape differs, skipped")
        else:
            for sub in ("chunked", "chunked_ckpt"):
                if sub in b_res and sub in f_res:
                    gate("resume", sub, f_res[sub], b_res[sub])
    for name, b_row in (baseline.get("workers") or {}).items():
        f_row = (fresh.get("workers") or {}).get(name)
        if f_row is None:
            notes.append(f"workers/{name}: not in fresh run, skipped")
        elif any(f_row.get(k) != b_row.get(k)
                 for k in ("u", "lanes", "rounds", "dim", "worker_shards")):
            # A different U series / device count is a different program
            # shape (e.g. CI's reduced --workers-series, or a sharded row
            # timed at another worker_shards) — skip, don't fail.
            notes.append(f"workers/{name}: U-series shape differs, skipped")
        else:
            for sub in ("unsharded", "sharded"):
                if sub in b_row:
                    if sub not in f_row:
                        notes.append(f"workers/{name}/{sub}: not in fresh "
                                     "run, skipped")
                    else:
                        gate(f"workers/{name}", sub, f_row[sub], b_row[sub])
    for name, b_row in (baseline.get("lm") or {}).items():
        f_row = (fresh.get("lm") or {}).get(name)
        if f_row is None:
            notes.append(f"lm/{name}: not in fresh run, skipped")
        elif any(f_row.get(k) != b_row.get(k)
                 for k in ("d", "u", "lanes", "rounds", "model_shards")):
            # A different D series / device count is a different program
            # shape (mirrors the workers guard).
            notes.append(f"lm/{name}: D-series shape differs, skipped")
        else:
            for sub in ("unsharded", "model_sharded"):
                if sub in b_row:
                    if sub not in f_row:
                        notes.append(f"lm/{name}/{sub}: not in fresh run, "
                                     "skipped")
                    else:
                        gate(f"lm/{name}", sub, f_row[sub], b_row[sub])
    return fails, notes


def grid(num: int, rounds: int):
    """CI/BEV x attacker-count grid, fig-4 style, cycled to `num` lanes."""
    cells = [(pol, n) for n in (0, 1, 2, 3, 4)
             for pol in (Policy.CI, Policy.BEV)]
    return [Experiment(name=f"{cells[i % len(cells)][0].value}"
                            f"@N{cells[i % len(cells)][1]}#{i}",
                       policy=cells[i % len(cells)][0],
                       n_attackers=cells[i % len(cells)][1],
                       alpha_hat=0.1, rounds=rounds, seed=100 + i)
            for i in range(num)]


def main(rounds: int = 25, scenarios: int = 16, sharded: bool = False,
         reps: int = 3, skip_looped: bool = False, defenses: bool = False,
         defense_rounds: int = 10, defense_scenarios: int = 6,
         chunk_rounds: int = 5, scenario_axes: bool = False,
         scenario_rounds: int = 10, scenario_lanes: int = 8,
         workers: bool = False,
         workers_series: str = "10,1000,10000", workers_rounds: int = 3,
         lm: bool = False, lm_series: str = "50000,1000000,10000000",
         lm_rounds: int = 3,
         resume: bool = False, resume_rounds: int = 10,
         resume_lanes: int = 8,
         out_path: str = "BENCH_sweep.json",
         check_against: str = "", tolerance: float = 0.5) -> dict:
    base_record = None
    if check_against:
        # Load BEFORE running: --out may point at the same file (the CI job
        # regenerates the committed BENCH_sweep.json it gates against).
        with open(check_against) as f:
            base_record = json.load(f)
    mc, shards, params, _ = figure_setup()
    exps = grid(scenarios, rounds)
    cfgs = [experiment_floa(e, mc) for e in exps]
    batches = FederatedSampler(shards, mc.batch_per_worker,
                               seed=1).stack_rounds(rounds)

    class Replay:
        """Feed the looped trainer the same pre-staged batches the scan
        engines consume, so the timers isolate engine overhead rather than
        charging host-side numpy sampling to the looped path only."""

        def __init__(self):
            self.t = 0

        def next_round(self):
            out = {k: v[self.t % rounds] for k, v in batches.items()}
            self.t += 1
            return out

    total = len(exps) * rounds
    cold, warm = {}, {}
    runners = []  # (name, run_once); cold-timed on registration

    def measure(name, run_once):
        t0 = time.perf_counter()
        run_once()
        cold[name] = time.perf_counter() - t0
        runners.append((name, run_once))

    def run_looped(trainers):
        for tr, e in zip(trainers, exps):
            p, _ = tr.run(params, Replay(), rounds,
                          jax.random.PRNGKey(e.seed), eval_every=0)
            jax.block_until_ready(p)

    def run_scans(trainers):
        for tr, e in zip(trainers, exps):
            # run_scan syncs internally (round losses come back as np arrays)
            tr.run_scan(params, batches, jax.random.PRNGKey(e.seed),
                        eval_every=0)

    # --- looped: fresh trainers => one compile per scenario, then per-round
    # dispatch; warm rerun reuses the compiled round_steps.
    if not skip_looped:
        trainers = [FLTrainer(loss_fn=mlp_loss, floa=floa, alpha=alpha)
                    for floa, alpha in cfgs]
        measure("looped", lambda t=trainers: run_looped(t))

        # --- scan: one lax.scan program (compile) per scenario.
        trainers = [FLTrainer(loss_fn=mlp_loss, floa=floa, alpha=alpha)
                    for floa, alpha in cfgs]
        measure("scan", lambda t=trainers: run_scans(t))

    spec = SweepSpec.build([
        ScenarioCase(e.name, floa, alpha, seed=e.seed)
        for e, (floa, alpha) in zip(exps, cfgs)
    ])

    # --- scan+vmap: the PR 1 tree-state engine — whole grid, one program.
    engine = SweepEngine(mlp_loss, spec, plan=ExecutionPlan(flat_state=False))
    measure("scan+vmap", lambda e=engine: e.run(params, batches))

    # --- flat: flat-state scan + fused combine/update (this PR's warm path).
    engine = SweepEngine(mlp_loss, spec)
    measure("flat", lambda e=engine: e.run(params, batches))

    # --- flat+chunk(+async): scan-of-chunks execution, with and without the
    # double-buffered host->device staging — the A/B isolates the input-
    # pipeline overlap from the chunking itself.
    chunk = max(1, min(chunk_rounds, rounds))
    engine = SweepEngine(mlp_loss, spec,
                         plan=ExecutionPlan(chunk_rounds=chunk))
    measure("flat+chunk", lambda e=engine: e.run(params, batches))
    engine = SweepEngine(mlp_loss, spec, plan=ExecutionPlan(
        chunk_rounds=chunk, async_staging=True))
    measure("flat+chunk+async", lambda e=engine: e.run(params, batches))

    # --- flat+shmap: the same flat scan sharded over every visible device.
    if sharded:
        engine = SweepEngine(mlp_loss, spec,
                             plan=ExecutionPlan(mesh=make_sweep_mesh()))
        measure("flat+shmap", lambda e=engine: e.run(params, batches))

    # Warm reps are interleaved across engines (A B C A B C ...) and each
    # engine keeps its best: on shared/noisy boxes consecutive reps alias
    # the machine's slow phases onto whichever engine happens to be running,
    # while interleaving spreads them evenly.
    best = {name: float("inf") for name, _ in runners}
    for _ in range(reps):
        for name, run_once in runners:
            t0 = time.perf_counter()
            run_once()
            best[name] = min(best[name], time.perf_counter() - t0)
    warm.update(best)

    print(f"# paper MLP (D={mc.dim}), S={len(exps)} scenarios x R={rounds} "
          f"rounds, backend={jax.default_backend()}, "
          f"devices={jax.device_count()}")
    print("engine,cold_rounds_per_sec,warm_rounds_per_sec,"
          "cold_speedup_vs_baseline,warm_speedup_vs_baseline")
    baseline = "looped" if "looped" in cold else "scan+vmap"
    engines = {}
    for name in cold:
        c, w = total / cold[name], total / warm[name]
        engines[name] = dict(
            cold_rounds_per_sec=round(c, 2), warm_rounds_per_sec=round(w, 2),
            cold_speedup=round(cold[baseline] / cold[name], 3),
            warm_speedup=round(warm[baseline] / warm[name], 3))
        print(f"{name},{c:.1f},{w:.1f},"
              f"{engines[name]['cold_speedup']:.2f}x,"
              f"{engines[name]['warm_speedup']:.2f}x")

    record = dict(
        bench="sweep", scenarios=len(exps), rounds=rounds, dim=mc.dim,
        num_workers=mc.num_workers, backend=jax.default_backend(),
        devices=jax.device_count(), baseline=baseline, reps=reps,
        chunk_rounds=chunk, engines=engines,
    )
    if "scan+vmap" in engines and "flat" in engines:
        record["flat_vs_pr1_warm_speedup"] = round(
            warm["scan+vmap"] / warm["flat"], 3)
        if "flat+shmap" in engines:
            record["sharded_vs_pr1_warm_speedup"] = round(
                warm["scan+vmap"] / warm["flat+shmap"], 3)
    if "flat+chunk" in engines and "flat+chunk+async" in engines:
        # The input-pipeline A/B: >1 means the double-buffered staging won
        # warm wall time over synchronous per-chunk staging.
        record["async_staging_warm_speedup"] = round(
            warm["flat+chunk"] / warm["flat+chunk+async"], 3)
    if defenses:
        record["defenses"] = bench_defenses(
            mc, shards, params, defense_rounds, defense_scenarios, reps)
        d = record["defenses"]
        if "mixed" in d and "mixed_switch" in d:
            # The tentpole number: grouped dispatch vs the per-lane switch
            # on the mixed all-families grid.
            record["mixed_grouped_vs_switch_warm_speedup"] = round(
                d["mixed"]["warm_rounds_per_sec"]
                / d["mixed_switch"]["warm_rounds_per_sec"], 3)
            print(f"# mixed grid grouped vs switch warm speedup: "
                  f"{record['mixed_grouped_vs_switch_warm_speedup']:.2f}x")
    if scenario_axes:
        record["scenario_axes"] = bench_scenario_axes(
            mc, shards, params, scenario_rounds, scenario_lanes, reps)
    if workers:
        series = [int(s) for s in str(workers_series).split(",") if s]
        record["workers"] = bench_workers(series, workers_rounds, reps)
    if lm:
        series = [int(s) for s in str(lm_series).split(",") if s]
        record["lm"] = bench_lm(series, lm_rounds, reps)
    if resume:
        # The raw --chunk-rounds, re-clamped against the resume grid's own
        # rounds (the headline clamp above used the headline rounds).
        record["resume"] = bench_resume(
            mc, shards, params, resume_rounds, resume_lanes, reps,
            chunk_rounds)
    # Gate BEFORE writing --out so the persisted record (the CI artifact)
    # carries the regression verdict, not just the raw numbers.
    if base_record is not None:
        fails, notes = check_regressions(record, base_record, tolerance)
        for n in notes:
            print(f"# check: {n}")
        if fails:
            print(f"# PERF REGRESSION vs {check_against} "
                  f"(tolerance {tolerance}):")
            for msg in fails:
                print(f"#   {msg}")
            record["regressions"] = fails
        else:
            print(f"# perf check vs {check_against}: OK "
                  f"(tolerance {tolerance})")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {out_path}")
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=25)
    ap.add_argument("--scenarios", type=int, default=16)
    ap.add_argument("--sharded", action="store_true",
                    help="also bench SweepEngine(mesh=...) over all devices "
                         "(pair with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 on CPU)")
    ap.add_argument("--reps", type=int, default=3,
                    help="warm reruns per engine (best-of, for noisy boxes)")
    ap.add_argument("--skip-looped", action="store_true",
                    help="skip the per-scenario looped/scan baselines")
    ap.add_argument("--defenses", action="store_true",
                    help="also bench the defense-code lane axis (one engine "
                         "per defense family + the mixed grid)")
    ap.add_argument("--defense-rounds", type=int, default=10,
                    help="rounds per defense-family engine (--defenses)")
    ap.add_argument("--defense-scenarios", type=int, default=6,
                    help="lanes per defense-family engine (--defenses)")
    ap.add_argument("--chunk-rounds", type=int, default=5,
                    help="chunk size C for the flat+chunk(+async) rows "
                         "(clamped to [1, rounds])")
    ap.add_argument("--scenario-axes", action="store_true",
                    help="also bench the adaptive-adversary lane axes "
                         "(legacy / markov / participation / directional / "
                         "mixed_axes, one engine per axis)")
    ap.add_argument("--scenario-rounds", type=int, default=10,
                    help="rounds per scenario-axis engine (--scenario-axes)")
    ap.add_argument("--scenario-lanes", type=int, default=8,
                    help="lanes per scenario-axis engine (--scenario-axes)")
    ap.add_argument("--workers", action="store_true",
                    help="also bench the worker-population scaling series "
                         "(mixed-defense grid at each U, unsharded + "
                         "worker-sharded over every visible device)")
    ap.add_argument("--workers-series", default="10,1000,10000",
                    help="comma-separated U values for --workers")
    ap.add_argument("--workers-rounds", type=int, default=3,
                    help="rounds per worker-scaling engine (--workers)")
    ap.add_argument("--lm", action="store_true",
                    help="also bench the flat-dimension scaling series "
                         "(mixed analog/median grid at each D, unsharded + "
                         "model-sharded over every visible device — the "
                         "big-D regime of the real-model LM lanes)")
    ap.add_argument("--lm-series", default="50000,1000000,10000000",
                    help="comma-separated D values for --lm")
    ap.add_argument("--lm-rounds", type=int, default=3,
                    help="rounds per D-scaling engine (--lm)")
    ap.add_argument("--resume", action="store_true",
                    help="also bench the preemption-safety machinery: "
                         "checkpointed-chunked vs plain-chunked warm "
                         "throughput and resume-restore wall")
    ap.add_argument("--resume-rounds", type=int, default=10,
                    help="rounds for the --resume checkpoint A/B grid")
    ap.add_argument("--resume-lanes", type=int, default=8,
                    help="lanes for the --resume checkpoint A/B grid")
    ap.add_argument("--out", default="BENCH_sweep.json",
                    help="machine-readable output path ('' to disable)")
    ap.add_argument("--check-against", default="",
                    help="baseline BENCH_sweep.json to gate against: exits "
                         "non-zero if any engine/defense row's fresh warm "
                         "rounds/sec falls below baseline * (1 - tolerance)")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="allowed fractional warm-throughput drop vs the "
                         "--check-against baseline (generous by default: "
                         "shared CI runners are noisy)")
    args = ap.parse_args()
    rec = main(rounds=args.rounds, scenarios=args.scenarios,
               sharded=args.sharded, reps=args.reps,
               skip_looped=args.skip_looped, defenses=args.defenses,
               defense_rounds=args.defense_rounds,
               defense_scenarios=args.defense_scenarios,
               chunk_rounds=args.chunk_rounds,
               scenario_axes=args.scenario_axes,
               scenario_rounds=args.scenario_rounds,
               scenario_lanes=args.scenario_lanes, workers=args.workers,
               workers_series=args.workers_series,
               workers_rounds=args.workers_rounds, lm=args.lm,
               lm_series=args.lm_series, lm_rounds=args.lm_rounds,
               resume=args.resume,
               resume_rounds=args.resume_rounds,
               resume_lanes=args.resume_lanes, out_path=args.out,
               check_against=args.check_against, tolerance=args.tolerance)
    if rec.get("regressions"):
        raise SystemExit(1)
