"""Readings that the check's limits are set from, taken on the chip at the
cell's own size, in one process:

  program   `--calls` calls of the window's own path per seed, after one
            warm-up call, against the plain reference (the lower reading
            of each number);
  control   the reference computed in bfloat16, put in the program's place,
            against the reference in float32 (the upper reading);
  faults    the reference with a fault planted (`half_batch`: every worker's
            gradient on half its rows; `unchanged`: the step returns the
            weights unchanged), against the clean reference; on a mesh of
            lanes also `exchange_left_out`, the program's own result as a
            gather that read every lane from the first chip's block would
            return it, against the reference.

    python3 perfbench/calibrate.py --workload <name> --seeds 24 --calls 3 \\
        --control-seeds 3 --fault-seeds 3 [--seed-list a,b] [--out readings.jsonl]

The benchmark's own runs never run this; it prints one JSON line per
reading and exits non-zero when JAX finds no accelerator.
"""
import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def as_result(ref: list) -> dict:
    """The reference's lanes in the shape of a program result."""
    import jax
    out = {"loss": np.stack([x["loss"] for x in ref]),
           "grad_norm": np.stack([x["grad_norm"] for x in ref]),
           "accuracy1": (None if ref[0]["accuracy1"] is None
                         else np.array([x["accuracy1"] for x in ref]))}
    out["params"] = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                           *[x["params"] for x in ref])
    return out


def lane_table(prog: dict, ref: list, lanes: list, rounds: int) -> dict:
    """Per lane: name, loss and aggregate-norm gaps, and the aggregate norms
    of program and reference in each checked round (the look behind a
    number that swings)."""
    import harness
    ref_gn = np.stack([x["grad_norm"][:rounds] for x in ref])
    return {
        "name": [lane["name"] for lane in lanes],
        "loss_gap": harness._lane_gaps(
            prog["loss"][:, :rounds],
            np.stack([x["loss"][:rounds] for x in ref])).tolist(),
        "gnorm_gap": harness._lane_gaps(prog["grad_norm"][:, :rounds],
                                        ref_gn).tolist(),
        "gnorm_prog": prog["grad_norm"][:, :rounds].tolist(),
        "gnorm_ref": ref_gn.tolist(),
    }


def first_chip_only(prog: dict, engine):
    """prog as the program returns it when the gather of every chip's lanes
    into lane order is left out: each lane is read from the row at the same
    place in the first chip's block; None where lanes are not split."""
    groups = None if engine is None else engine._groups
    if groups is None or groups.shards < 2:
        return None
    src = (np.asarray(groups.perm)[np.asarray(groups.inverse)
                                   % groups.lanes_per_shard])
    return {k: v if v is None else v[src] for k, v in prog.items()
            if k != "params"}


def readings_for(name: str, seeds, control_seeds, fault_seeds, faults,
                 devices, emit, overrides=None, calls=1):
    import gc
    import jax
    import harness

    cell = harness.Cell(name, overrides=overrides)
    rounds = cell.mix["rounds"]
    ref_rounds = min(cell.mix.get("ref_rounds", 3), rounds)
    keep = ref_rounds == rounds

    def extra(i, seed, system, call, ref, prog):
        if i < control_seeds:
            ctl = harness.reference_run(system.model, call["lanes"],
                                        call["keys"], ref_rounds,
                                        dtype="bfloat16", devices=devices)
            emit({"kind": "control", "seed": seed,
                  **harness.readings(as_result(ctl), ref,
                                     system.model["params0"], ref_rounds,
                                     call["lanes"]),
                  "worst": harness.worst_lanes(as_result(ctl), ref,
                                               call["lanes"], ref_rounds)})
        if i < fault_seeds:
            for fault in faults:
                bad = harness.reference_run(system.model, call["lanes"],
                                            call["keys"], ref_rounds,
                                            fault=fault, devices=devices)
                emit({"kind": f"fault:{fault}", "seed": seed,
                      **harness.readings(as_result(bad), ref,
                                         system.model["params0"], ref_rounds,
                                         call["lanes"])})
            bad = first_chip_only(prog, system.engine)
            if bad is not None:
                emit({"kind": "fault:exchange_left_out", "seed": seed,
                      **harness.readings(bad, ref, system.model["params0"],
                                         ref_rounds, call["lanes"])})

    system = None
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if system is None or cell.mix["call"] != "engine":
            system = harness.System(cell, seed, devices)
        else:  # the same engine, the seed's own weights, data and keys
            import traffic
            system.model = cell.cfg_mod.build(cell.cfg, cell.mix, seed)
            system.traffic = traffic.Traffic(cell.mix, cell.cfg,
                                             system.model["dim"], seed)
        system.call(system.traffic.next_call())      # as the warm-up does
        for n in range(calls):
            call = system.traffic.next_call()
            res = system.call(call)
            prog = harness.kept_result(res, keep)
            del res
            gc.collect()
            t1 = time.perf_counter()
            ref = harness.reference_run(system.model, call["lanes"],
                                        call["keys"], ref_rounds,
                                        devices=devices)
            vals = harness.readings(prog, ref, system.model["params0"],
                                    ref_rounds, call["lanes"])
            emit({"kind": "program", "seed": seed, "call": n + 2, **vals,
                  "program_s": t1 - t0, "reference_s": time.perf_counter() - t1,
                  "worst": harness.worst_lanes(prog, ref, call["lanes"],
                                               ref_rounds),
                  "lanes": lane_table(prog, ref, call["lanes"], ref_rounds)})
            t0 = time.perf_counter()
            if n == 0:
                extra(i, seed, system, call, ref, prog)
            del ref, prog
            gc.collect()
    jax.clear_caches()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=900001)
    ap.add_argument("--seed-list", default="",
                    help="comma-separated seeds read before the --seeds ones")
    ap.add_argument("--calls", type=int, default=1,
                    help="calls read per seed after the warm-up (the window "
                         "checks its last call, whose index varies)")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--faults", default="half_batch,unchanged")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cache = ROOT / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "perfbench")]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import harness

    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("calibrate: JAX found no accelerator", file=sys.stderr)
        return 3
    chips = harness.Cell(args.workload).chips
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        rec = {"workload": args.workload, **rec}
        print(json.dumps(rec), flush=True)
        if out:
            out.write(json.dumps(rec) + "\n")
            out.flush()

    seeds = [int(x) for x in args.seed_list.split(",") if x]
    seeds += [args.first_seed + 7919 * i for i in range(args.seeds)]
    readings_for(args.workload, seeds, args.control_seeds, args.fault_seeds,
                 [f for f in args.faults.split(",") if f],
                 devices[:chips], emit, calls=args.calls)
    return 0


if __name__ == "__main__":
    sys.exit(main())
