"""Chip smoke test: the compiled scenario sweep, end to end, on a TPU.

  python chip_smoke.py                # one chip: the three phases below
  python chip_smoke.py --four-chips   # four chips: sharded vs one-chip sweeps

One chip, in one process (a process that has touched JAX holds the chip):

  showdown  examples/byzantine_showdown.py's grid through `run_sweep` at the
            paper's width (PAPER_MLP 784-64-10, D = 50,890, U = 10): every
            defense family, 0-4 attackers, adaptive-adversary lanes.  The
            median / trimmed-mean lanes take the `sort_columns` kernel.  Two
            digital lanes are checked against the looped `FLTrainer`.
  lm        the width-cut LM lane of examples/train_floa_lm.py (D ~ 2.95M,
            U = 8, 3 lanes): the analog pair takes the fused
            `floa_step_batched` kernel, the median lane `sort_columns`.
  kernels   floa_step_batched (S=16, U=10, D=2^20), sort_columns vmapped
            over S=16, and sort_columns_bitonic (U=1024, D=50,890) against
            their kernels/ref.py oracles.

`--four-chips` runs only the sharded paths, each against the same sweep on
one chip of the same process: the LM lane with model_shards=4 and the
showdown grid on a 4-device ("data",) mesh.

Each sweep phase dumps the StableHLO of the programs it compiles and checks
that the expected Mosaic kernels (`tpu_custom_call`, by kernel name) are in
them.  Timings printed are host-clock smoke timings, compile included; they
are not benchmark numbers.  The script exits non-zero, without the result
line, when JAX finds no TPU, when a phase raises, or when a check fails.
Its last line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import tempfile
import time
import traceback
from typing import NamedTuple

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

SHOWDOWN_ROUNDS = 10
LM_ROUNDS = 4
# Sweep-vs-reference tolerance on loss trajectories: both sides accumulate
# in f32 with the TPU's default matmul precision, in different orders.
TRAJ_RTOL = 1e-4


class Checks:
    """Collects named pass/fail checks; a phase keeps going after a failed
    check so one run reports every fault."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"check {name}: {'ok' if ok else 'FAIL'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)


def _rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def _programs_in(dump_dir: str):
    """(Mosaic kernel names, Shardy meshes and shardings) in the StableHLO
    modules dumped to dump_dir: `sdy.mesh @mesh = <["data"=4]>` gives
    '["data"=4]', `sdy.sharding<@mesh, [{"data"}, {}]>` '[{"data"}, {}]'."""
    names, shardings = set(), set()
    for f in pathlib.Path(dump_dir).glob("*.mlir"):
        text = f.read_text(errors="replace")
        if "tpu_custom_call" in text:
            names.update(re.findall(r'kernel_name = "(\w+)"', text))
        shardings.update(re.findall(r"sdy\.mesh @\w+ = <(\[[^\]]*\])>", text))
        shardings.update(re.findall(r"sdy\.sharding<@\w+, (\[[^>]*\])>",
                                    text))
    return names, shardings


def _device_busy_ms(trace_dir: str) -> dict:
    """Per-TPU busy time (ms) in a profiler trace: the summed durations of
    the programs ("XLA Modules" line) each device plane ran."""
    from jax.profiler import ProfileData
    busy, seen = {}, []
    for f in pathlib.Path(trace_dir).rglob("*.xplane.pb"):
        for plane in ProfileData.from_file(str(f)).planes:
            seen.append((plane.name, [line.name for line in plane.lines]))
            m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
            if not m:
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    busy[int(m[1])] = busy.get(int(m[1]), 0.0) + sum(
                        e.duration_ns for e in line.events) / 1e6
    if not busy:
        print(f"trace: no TPU plane with an 'XLA Modules' line in {seen}",
              flush=True)
    return busy


class Runs(NamedTuple):
    result: object      # what the first run returned
    kernels: set        # Mosaic kernel names in the compiled programs
    shardings: set      # sharding annotations in the compiled programs
    busy: dict          # device id -> busy ms in the traced run


def _timed_runs(label: str, run) -> Runs:
    """Run three times: the first compiles (with its StableHLO dumped), the
    second is timed warm, the third is traced."""
    import jax
    with tempfile.TemporaryDirectory() as dump:
        jax.config.update("jax_dump_ir_to", dump)
        try:
            t0 = time.perf_counter()
            res = run()
            cold = time.perf_counter() - t0
        finally:
            jax.config.update("jax_dump_ir_to", "")
        kernels, shardings = _programs_in(dump)
    t0 = time.perf_counter()
    run()
    warm = time.perf_counter() - t0
    with tempfile.TemporaryDirectory() as trace:
        with jax.profiler.trace(trace):
            run()
        busy = _device_busy_ms(trace)
    print(f"smoke timing {label}: first run {cold:.3f} s (compile + run), "
          f"second run {warm:.3f} s, so about {cold - warm:.3f} s compile; "
          f"kernels {sorted(kernels)}; traced run device busy ms "
          f"{ {k: round(v, 3) for k, v in sorted(busy.items())} }",
          flush=True)
    return Runs(res, kernels, shardings, busy)


# ------------------------------------------------------------ the workloads


def showdown_sweep(plan=None):
    """examples/byzantine_showdown.py's grid at the paper's width."""
    import jax
    import byzantine_showdown as SD
    from repro.configs import PAPER_MLP
    from repro.fl import SweepSpec, run_sweep
    from repro.models import init_mlp, mlp_accuracy, mlp_loss

    mc = PAPER_MLP.full()
    _, sampler, xt, yt = SD.setup(None)
    rounds = SHOWDOWN_ROUNDS
    batches = sampler.stack_rounds(rounds)
    params = init_mlp(jax.random.PRNGKey(0))
    cases = SD.build_cases(mc)

    def run():
        return run_sweep(mlp_loss, params, batches, SweepSpec.build(cases),
                         eval_fn=lambda p: {"accuracy": mlp_accuracy(p, xt,
                                                                     yt)},
                         eval_every=rounds, plan=plan)
    return mc, cases, params, batches, run


def lm_sweep(plan=None):
    """examples/train_floa_lm.py's three-lane LM sweep (width-cut lane)."""
    import jax
    import train_floa_lm as LM
    from repro.configs.registry import flat_param_dim, get_lm_sweep
    from repro.data import stack_token_rounds
    from repro.fl import ExecutionPlan, SweepEngine, SweepSpec
    from repro.models.transformer import init_lm, lm_loss

    cfg = get_lm_sweep()
    dim = flat_param_dim(cfg)
    u, batch, seq = 8, 2, 64
    spec = SweepSpec.build(LM.lm_lanes(u, dim, n_atk=2, lr=0.2))
    batches = {"tokens": stack_token_rounds(LM_ROUNDS, u * batch, seq + 1,
                                            cfg.vocab_size, seed=0)}
    params0, _ = init_lm(jax.random.PRNGKey(0), cfg)
    engine = SweepEngine(lambda p, b: lm_loss(p, b, cfg), spec,
                         plan=plan or ExecutionPlan())
    return dim, spec, lambda: engine.run(params0, batches)


# ------------------------------------------------------------ one chip


def phase_showdown(check: Checks) -> None:
    import jax
    import numpy as np
    from repro.fl import FLTrainer
    from repro.models import mlp_loss

    mc, cases, params, batches, run = showdown_sweep()
    print(f"showdown: {len(cases)} lanes x {SHOWDOWN_ROUNDS} rounds, "
          f"U={mc.num_workers}, D={mc.dim:,}", flush=True)
    res, kernels, _, busy = _timed_runs("showdown", run)
    loss = np.asarray(res.loss)
    check("showdown/shape", loss.shape == (len(cases), SHOWDOWN_ROUNDS),
          str(loss.shape))
    check("showdown/finite", bool(np.all(np.isfinite(loss))))
    bev = loss[res.index("bev@N0")]
    check("showdown/bev-benign-loss-falls", bev[-3:].mean() < bev[0],
          f"{bev[0]:.4f} -> {bev[-3:].mean():.4f}")
    check("showdown/sort-kernel-in-program", "sort_columns" in kernels)
    check("showdown/device-ran", busy.get(0, 0.0) > 0)

    class Replay:
        def __init__(self):
            self.t = 0

        def next_round(self):
            self.t += 1
            return {k: v[self.t - 1] for k, v in batches.items()}

    for name, defense in (("digital mean (no defense)@N0", "mean"),
                          ("digital median@N1", "median")):
        case = cases[res.index(name)]
        tr = FLTrainer(loss_fn=mlp_loss, floa=case.floa,
                       alpha=case.alpha, mode="digital", defense=defense)
        _, logs = tr.run(params, Replay(), SHOWDOWN_ROUNDS,
                         jax.random.PRNGKey(case.seed), eval_every=1)
        err = _rel_err(loss[res.index(name)], [l.loss for l in logs])
        check(f"showdown/{defense}-lane-vs-looped-trainer", err <= TRAJ_RTOL,
              f"max rel err {err:.3e}, rtol {TRAJ_RTOL}")


def phase_lm(check: Checks) -> None:
    import numpy as np
    dim, spec, run = lm_sweep()
    print(f"lm: {len(spec)} lanes x {LM_ROUNDS} rounds, U=8, D={dim:,} "
          "(width-cut lane, not a published model)", flush=True)
    res, kernels, _, busy = _timed_runs("lm", run)
    loss = np.asarray(res.loss)
    check("lm/shape", loss.shape == (len(spec), LM_ROUNDS), str(loss.shape))
    check("lm/finite", bool(np.all(np.isfinite(loss))))
    clean = loss[res.index("bev-clean")]
    check("lm/clean-loss-falls", clean[-1] < clean[0],
          f"{clean[0]:.4f} -> {clean[-1]:.4f}")
    check("lm/fused-step-kernel-in-program", "floa_step_batched" in kernels)
    check("lm/sort-kernel-in-program", "sort_columns" in kernels)
    check("lm/device-ran", busy.get(0, 0.0) > 0)


def phase_kernels(check: Checks) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    def compiled(fn, *args):
        t0 = time.perf_counter()
        c = jax.jit(fn).lower(*args).compile()
        dt = time.perf_counter() - t0
        return c, dt, "tpu_custom_call" in c.as_text()

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    s_n, u, d = 16, 10, 1 << 20
    w = jax.random.normal(ks[0], (s_n, d))
    coeffs = jax.random.normal(ks[1], (s_n, u))
    grads = jax.random.normal(ks[2], (s_n, u, d))
    noise = jax.random.normal(ks[3], (s_n, d))
    bias, eps, alpha = (jax.random.normal(k, (s_n,)) for k in ks[4:7])
    args = (w, coeffs, grads, noise, bias, eps, alpha)
    c, dt, custom = compiled(ops.floa_step_batched, *args)
    w_new, gagg = c(*args)
    with jax.default_matmul_precision("highest"):
        w_ref, g_ref = jax.jit(ref.floa_step_batched_ref)(*args)
    err = max(float(jnp.max(jnp.abs(w_new - w_ref))),
              float(jnp.max(jnp.abs(gagg - g_ref))))
    print(f"smoke timing kernels/floa_step_batched: compile {dt:.3f} s; "
          f"max abs err vs oracle {err:.3e}", flush=True)
    check("kernels/floa_step_batched-custom-call", custom)
    check("kernels/floa_step_batched-vs-oracle", err <= 1e-4,
          f"max abs err {err:.3e} (values ~N(0, 10))")

    x = jax.random.normal(ks[7], (s_n, u, d))
    c, dt, custom = compiled(jax.vmap(ops.sort_columns), x)
    got = c(x)
    exact = bool(jnp.array_equal(got, ref.sort_columns_batched_ref(x)))
    print(f"smoke timing kernels/sort_columns(vmap S={s_n}): compile "
          f"{dt:.3f} s; equal to oracle: {exact}", flush=True)
    check("kernels/sort_columns-custom-call", custom)
    check("kernels/sort_columns-vs-oracle", exact)

    xb = jax.random.normal(ks[6], (1024, 50_890))
    c, dt, custom = compiled(ops.sort_columns_bitonic, xb)
    got = c(xb)
    exact = bool(jnp.array_equal(got, ref.sort_columns_ref(xb)))
    print(f"smoke timing kernels/sort_columns_bitonic(U=1024): compile "
          f"{dt:.3f} s; equal to oracle: {exact}", flush=True)
    check("kernels/sort_columns_bitonic-custom-call", custom)
    check("kernels/sort_columns_bitonic-vs-oracle", exact)


# ------------------------------------------------------------ four chips


def _every_device_worked(check: Checks, name: str, busy: dict) -> None:
    """Each of the 4 chips ran its share of the sharded program: every
    device plane of the traced run is busy, none idle next to the others."""
    per = [busy.get(i, 0.0) for i in range(4)]
    check(f"{name}/every-device-busy",
          min(per) > 0 and min(per) >= 0.25 * max(per),
          f"device busy ms {[round(v, 3) for v in per]}")


def _compare(check: Checks, name: str, res4, res1) -> None:
    import numpy as np
    l4, l1 = np.asarray(res4.loss), np.asarray(res1.loss)
    err = _rel_err(l4, l1)
    print(f"{name}: loss (first lanes) one chip {l1[:3].tolist()}\n"
          f"{name}: loss (first lanes) 4 chips  {l4[:3].tolist()}",
          flush=True)
    check(f"{name}/finite", bool(np.all(np.isfinite(l4))))
    check(f"{name}/sharded-vs-one-chip", err <= TRAJ_RTOL,
          f"max rel err {err:.3e}, rtol {TRAJ_RTOL}")


def phase_four_lm(check: Checks) -> None:
    import jax
    from repro.fl import ExecutionPlan
    from repro.launch.mesh import make_sweep_mesh

    mesh = make_sweep_mesh(model_shards=4)
    dim, spec, run4 = lm_sweep(ExecutionPlan(mesh=mesh))
    print(f"four/lm: mesh {dict(mesh.shape)}, {len(spec)} lanes x "
          f"{LM_ROUNDS} rounds, D={dim:,}", flush=True)
    res4, kernels, _, busy = _timed_runs("four/lm model_shards=4", run4)
    _every_device_worked(check, "four/lm", busy)
    check("four/lm/fused-step-kernel-in-program",
          "floa_step_batched" in kernels)
    # The final params leave the sharded program split over "model": each
    # chip must hold its own part of the widest leaf, not a full copy.
    leaf = max(jax.tree_util.tree_leaves(res4.params), key=lambda x: x.size)
    shards = leaf.addressable_shards
    check("four/lm/params-split-over-4-devices",
          len({s.device for s in shards}) == 4
          and all(s.data.size * 4 == leaf.size for s in shards),
          f"{leaf.shape} -> {[(str(s.device), s.data.shape) for s in shards]}")
    _, _, run1 = lm_sweep(ExecutionPlan())
    res1 = _timed_runs("four/lm one chip", run1).result
    _compare(check, "four/lm", res4, res1)


def phase_four_showdown(check: Checks) -> None:
    from repro.fl import ExecutionPlan
    from repro.launch.mesh import make_sweep_mesh

    mesh = make_sweep_mesh()
    _, cases, _, _, run4 = showdown_sweep(ExecutionPlan(mesh=mesh))
    print(f"four/showdown: mesh {dict(mesh.shape)}, {len(cases)} lanes x "
          f"{SHOWDOWN_ROUNDS} rounds", flush=True)
    res4, _, shardings, busy = _timed_runs("four/showdown data=4", run4)
    _every_device_worked(check, "four/showdown", busy)
    # The engine gathers the final params back into lane order (replicated),
    # so the split shows in the compiled program: a 4-chip "data" mesh, and
    # lane operands cut along it, one quarter of the lanes per chip.
    split = sorted(s for s in shardings if s.startswith('[{"data"}'))
    check("four/showdown/lanes-split-over-4-devices",
          '["data"=4]' in shardings and bool(split),
          f"meshes and lane-axis shardings {sorted(shardings)[:6]}")
    *_, run1 = showdown_sweep()
    res1 = _timed_runs("four/showdown one chip", run1).result
    _compare(check, "four/showdown", res4, res1)


# ------------------------------------------------------------ main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths on 4 chips, each "
                         "against the same sweep on one chip")
    args = ap.parse_args()

    import jax
    from repro import setup_compilation_cache

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    print(f"compile cache: {setup_compilation_cache()}", flush=True)

    phases = ([("four/lm", phase_four_lm),
               ("four/showdown", phase_four_showdown)] if args.four_chips
              else [("showdown", phase_showdown), ("lm", phase_lm),
                    ("kernels", phase_kernels)])
    check = Checks()
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(check)
        except Exception:
            traceback.print_exc()
            check(f"{name}/raised", False)
        print(f"smoke timing phase {name}: {time.perf_counter() - t0:.3f} s "
              "wall", flush=True)
    if check.failed:
        print(f"chip_smoke: failed checks: {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
