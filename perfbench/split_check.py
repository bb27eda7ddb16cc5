"""The plain reference followed over several chips against the same
reference on fewer, and the memory of its round program on each chip.

    python3 perfbench/split_check.py --workload <name> --seed <n> \\
        --chips 1,4 [--rounds 3] [--overrides '{"cfg": {...}, "mix": {...}}'] \\
        [--program-memory]

For each chip count the cell's weights, batches and the lanes of one call
are staged from the seed (`fedref.Staged` over the first n devices), the
first lane's round program is compiled and its `memory_analysis()` printed
per chip, and every lane is followed for `--rounds` rounds.  With two chip
counts, the gaps between them follow: per lane the largest relative gap of
the loss and of the aggregate's norm over the rounds, and per leaf the gap
of the norm of the parameters' change over the larger of the fewer-chip
run's change of that leaf and of the median leaf.

`--program-memory` compiles instead the program's window path for the
cell's mix (its `mesh` included) on the largest chip count and prints that
program's `memory_analysis()`; nothing runs.  The benchmark's own runs never
run this script; it prints one JSON line per result.
"""
import argparse
import json
import math
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def emit(rec):
    print(json.dumps(rec), flush=True)


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, f"{k}_size_in_bytes")) for k in
           ("argument", "output", "temp", "generated_code")}
    out["per_chip_gb"] = (out["argument"] + out["output"] + out["temp"]) / 1e9
    return out


def follow(model, call, devices, rounds: int) -> dict:
    """Every lane of the call over `devices`; the round program's memory."""
    import jax
    import jax.numpy as jnp
    import fedref

    staged = fedref.Staged(model["params0"], model["batches"], rounds,
                           cast_batch=model["cast_batch"], devices=devices)
    lane = call["lanes"][0]
    step = fedref._compiled(lane["defense"], lane["num_workers"],
                            lane["gm_iters"], model["ref_loss"],
                            staged.treedef, staged.shapes, staged.sizes,
                            staged.dt, None, staged.mesh)
    h = fedref._h0(jnp.asarray(call["keys"][0], jnp.uint32),
                   float(lane["sigma"]), lane["num_workers"], staged.dt)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        compiled = step.lower(staged.w0, h, jnp.asarray(call["keys"][0]),
                              staged.batches[0],
                              fedref.lane_numbers(lane, staged.dt)).compile()
    mem = memory(compiled)
    del compiled
    emit({"kind": "reference_memory", "chips": len(devices),
          "dim": sum(staged.sizes), "compile_s": time.perf_counter() - t0,
          **mem})
    t0 = time.perf_counter()
    ref = [fedref.run_lane(lane, key, staged, model["ref_loss"], rounds,
                           eval_fn=model["ref_eval"])
           for lane, key in zip(call["lanes"], call["keys"])]
    emit({"kind": "reference_run", "chips": len(devices), "rounds": rounds,
          "lanes": len(ref), "seconds": time.perf_counter() - t0,
          "loss": [x["loss"].tolist() for x in ref],
          "grad_norm": [x["grad_norm"].tolist() for x in ref]})
    return ref


def gaps(a: list, b: list, params0) -> dict:
    """b against a (a the run on fewer chips)."""
    import jax
    import numpy as np
    import harness

    out = {}
    for key in ("loss", "grad_norm"):
        out[key] = float(np.max(harness._lane_gaps(
            np.stack([x[key] for x in b]), np.stack([x[key] for x in a]))))
    p0 = [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(params0)]
    worst = 0.0
    for x, y in zip(a, b):
        da = np.array([np.linalg.norm(np.asarray(v, np.float64) - w) for v, w
                       in zip(jax.tree_util.tree_leaves(x["params"]), p0)])
        db = np.array([np.linalg.norm(np.asarray(v, np.float64) - w) for v, w
                       in zip(jax.tree_util.tree_leaves(y["params"]), p0)])
        floor = np.median(da)
        worst = max(worst, float(np.max(np.abs(db - da)
                                         / np.maximum(da, floor))))
    out["dparam"] = worst
    return out


def program_memory(cell, seed: int, devices) -> None:
    """The window path's program compiled for the cell's mix; not run."""
    import harness

    class Compiled(Exception):
        pass

    system = harness.System(cell, seed, devices)
    engine = system.engine
    build = engine._build

    def build_and_probe(template):
        build(template)
        jitted = engine._run_jit

        def probe(*args):
            t0 = time.perf_counter()
            compiled = jitted.lower(*args).compile()
            emit({"kind": "program_memory", "chips": len(devices),
                  "mesh": cell.mix.get("mesh"), "dim": system.model["dim"],
                  "compile_s": time.perf_counter() - t0, **memory(compiled)})
            raise Compiled
        engine._run_jit = probe

    engine._build = build_and_probe
    try:
        system.call(system.traffic.next_call())
    except Compiled:
        pass
    system.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", default="1,4")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--overrides", default="{}")
    ap.add_argument("--program-memory", action="store_true")
    args = ap.parse_args()

    cache = ROOT / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "perfbench")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import harness
    import traffic

    chips = [int(x) for x in args.chips.split(",")]
    devices = jax.devices()
    cell = harness.Cell(args.workload, overrides=json.loads(args.overrides))
    if args.program_memory:
        program_memory(cell, args.seed, devices[:max(chips)])
        return 0
    model = cell.cfg_mod.build(cell.cfg, cell.mix, args.seed)
    model["params0"] = jax.device_get(model["params0"])
    call = traffic.Traffic(cell.mix, cell.cfg, model["dim"],
                           args.seed).next_call()
    emit({"kind": "cell", "workload": args.workload, "seed": args.seed,
          "dim": model["dim"], "lanes": [x["name"] for x in call["lanes"]],
          "device": devices[0].device_kind})
    runs = [follow(model, call, devices[:n], args.rounds) for n in chips]
    for (n, a), (m, b) in zip(zip(chips, runs), zip(chips[1:], runs[1:])):
        emit({"kind": "gaps", "chips": [n, m],
              **gaps(a, b, model["params0"])})
    return 0 if all(math.isfinite(v) for r in runs for x in r
                    for v in x["loss"]) else 1


if __name__ == "__main__":
    sys.exit(main())
