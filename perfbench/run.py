"""Benchmark entry point: run one cell of BENCHMARK.json once, on the chips
of this machine, and print the result as the last line of standard output.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

With --trace 0 the line carries the cell's end-to-end metrics, with
--trace 1 its per-layer metrics read from a profiler trace of the window.
Set-up (imports, data and weights from the seed, the program's build and
its compile or compile-cache load, one warm-up call) is timed from the
start of this script.  The run exits non-zero and prints no result when JAX
finds no accelerator or fewer chips than the cell asks for, or when the
program under test (src/repro) is not in the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE = ROOT / ".jax_cache"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print("perfbench: the program under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"perfbench: no workload {args.workload!r}; have "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    chips = cells[args.workload]["chips"]

    # The compile cache lives at one fixed path inside the checkout.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "perfbench")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("perfbench: JAX found no accelerator", file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"perfbench: the cell needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3

    import harness

    line = harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START, devices[:chips])
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
