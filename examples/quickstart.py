"""Quickstart: the paper in 60 seconds — as ONE compiled sweep.

Trains the paper's MLP (784-64-10, D=50890) over a simulated wireless MAC
with U=10 workers under five setups at once — error-free, CI, and BEV benign,
plus CI and BEV with 3 Byzantine workers mounting the strongest attack
(Thm 1).  All five are lanes of a single scan x vmap program (fl.sweep), so
the whole demo is one compile + one dispatch.  Reproduces the paper's
headline: CI ≈ EF when benign but collapses under attack; BEV pays ~2%
benign accuracy for robustness.

  PYTHONPATH=src python examples/quickstart.py
  REPRO_SMOKE=1 PYTHONPATH=src python examples/quickstart.py   # tiny CI mode
"""
import os

import jax
import jax.numpy as jnp

jax.config.update("jax_threefry_partitionable", True)

from repro import setup_compilation_cache
from repro.configs import PAPER_MLP
from repro.core import (
    AttackConfig, AttackType, ChannelConfig, FLOAConfig, Policy, PowerConfig,
    first_n_mask, noise_std_for_snr,
)
from repro.core import theory
from repro.data import FederatedSampler, make_dataset, worker_split
from repro.fl import ScenarioCase, SweepSpec, run_sweep
from repro.models import init_mlp, mlp_accuracy, mlp_loss

SMOKE = bool(os.environ.get("REPRO_SMOKE"))

# Persistent XLA compilation cache ($JAX_COMPILATION_CACHE_DIR, else the
# checkout's .jax_cache): a restarted demo skips the sweep recompile.  See
# docs/checkpointing.md.
setup_compilation_cache()


def case(name: str, policy: Policy, n_attackers: int, mc) -> ScenarioCase:
    u, d = mc.num_workers, mc.dim
    tp = theory.TheoryParams(num_workers=u, num_attackers=n_attackers, dim=d)
    pol = "ef" if policy == Policy.EF else policy.value
    alpha = theory.alpha_from_alpha_hat(tp, pol, alpha_hat=0.1)
    floa = FLOAConfig(
        channel=ChannelConfig(
            num_workers=u, sigma=mc.sigma,
            noise_std=0.0 if policy == Policy.EF
            else noise_std_for_snr(mc.p_max, d, mc.snr_db)),
        power=PowerConfig(num_workers=u, dim=d, p_max=mc.p_max, policy=policy),
        attack=AttackConfig(
            attack=AttackType.STRONGEST if n_attackers else AttackType.NONE,
            byzantine_mask=first_n_mask(u, n_attackers)),
    )
    return ScenarioCase(name, floa, alpha, seed=1)


def main(rounds: int = 120) -> dict:
    mc = PAPER_MLP.smoke() if SMOKE else PAPER_MLP.full()
    if SMOKE:
        rounds = min(rounds, 10)
    spec = SweepSpec.build([
        case("EF benign", Policy.EF, 0, mc),
        case("CI benign", Policy.CI, 0, mc),
        case("BEV benign", Policy.BEV, 0, mc),
        case("CI 3-attackers", Policy.CI, 3, mc),
        case("BEV 3-attackers", Policy.BEV, 3, mc),
    ])
    x, y = make_dataset(mc.train_samples, seed=0)
    xt, yt = make_dataset(mc.test_samples, seed=99)
    xt, yt = jnp.asarray(xt), jnp.asarray(yt)
    batches = FederatedSampler(worker_split(x, y, mc.num_workers),
                               mc.batch_per_worker).stack_rounds(rounds)
    result = run_sweep(
        mlp_loss, init_mlp(jax.random.PRNGKey(0)), batches, spec,
        eval_fn=lambda p: {"accuracy": mlp_accuracy(p, xt, yt)},
        eval_every=rounds)  # only the final accuracy matters here

    accs = {name: float(result.metrics["accuracy"][i, -1])
            for i, name in enumerate(result.names)}
    print("== benign (no attackers) ==")
    for name in ("EF benign", "CI benign", "BEV benign"):
        print(f"  {name:16s} test accuracy: {accs[name]:.3f}")
    print("== 3 Byzantine workers, strongest attack (Thm 1) ==")
    for name in ("CI 3-attackers", "BEV 3-attackers"):
        print(f"  {name:16s} test accuracy: {accs[name]:.3f}")
    print("-> BEV trades a sliver of benign accuracy for Byzantine robustness.")
    return accs


if __name__ == "__main__":
    main()
