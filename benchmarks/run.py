"""Benchmark entrypoint: one function per paper table/figure.

  fig1  no attack (EF/CI/BEV)                §IV-A
  fig2  weakest attacker, alpha_hat sweep    §IV-B
  fig3  strongest attacker                   §IV-C
  fig4  N random attackers                   §IV-D
  defenses  digital screening baselines (beyond paper)
  kernels   Pallas kernel correctness/microbench (name,us_per_call,derived);
            add --interpret off the TPU
  roofline  40-pair dry-run roofline table   (deliverable g)

Set BENCH_ROUNDS to shrink FL rounds (CI smoke: BENCH_ROUNDS=30).
"""
from __future__ import annotations

import os
import sys
import time


def main() -> None:
    rounds = int(os.environ.get("BENCH_ROUNDS", "150"))
    interpret = "--interpret" in sys.argv
    which = [a for a in sys.argv[1:] if a != "--interpret"] or [
        "fig1", "fig2", "fig3", "fig4", "defenses", "kernels", "roofline"]
    from benchmarks import (defenses_bench, fig1_no_attack, fig2_weak_attacker,
                            fig3_strong_attacker, fig4_multi_attackers,
                            kernels_bench, roofline)

    t0 = time.time()
    if "fig1" in which:
        fig1_no_attack.main(rounds)
    if "fig2" in which:
        fig2_weak_attacker.main(rounds)
    if "fig3" in which:
        fig3_strong_attacker.main(rounds)
    if "fig4" in which:
        fig4_multi_attackers.main(rounds)
    if "defenses" in which:
        defenses_bench.main(min(rounds, 120))
    if "kernels" in which:
        kernels_bench.main(interpret=interpret)
    if "roofline" in which:
        roofline.main()
    print(f"# benchmarks done in {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
