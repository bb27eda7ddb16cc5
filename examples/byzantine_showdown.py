"""Byzantine showdown (beyond paper): FLOA-BEV vs FLOA-CI vs digital
screening defenses (median / trimmed-mean / Krum / multi-Krum / geometric
median) under increasing attacker counts — plus the adaptive-adversary
axes: colluding and omniscient cohorts, Gauss-Markov fading, and K-of-U
client sampling.  One table, every defense philosophy.

Digital defenses see per-worker gradients (U x uplink cost via an
all-gather, no privacy); FLOA sees only the analog superposition (1 x
uplink all-reduce, gradient-private) — the paper's whole trade-off,
quantified.

Execution: EVERY cell — analog (policy x attacker count), digital
(defense x attacker count), and every adaptive-adversary variant — is one
lane of a single compiled sweep: the defense-code lane axis
(core.scenario.DEFENSE_CODES) selects per lane between the OTA
`floa_step` combine and a screening defense, attack codes 4/5 inject the
colluding/omniscient directional payloads, `markov_rho` lanes thread the
Gauss-Markov fading carry, and `participants=K` lanes mask the
non-participants out of stats, combine, and screening — all on the same
[S, U, D] gradient slab, so the whole table is one XLA program, one
compile, one dispatch.  Zero per-defense programs.

  PYTHONPATH=src python examples/byzantine_showdown.py
  PYTHONPATH=src python examples/byzantine_showdown.py --dirichlet 0.3
  REPRO_SMOKE=1 PYTHONPATH=src python examples/byzantine_showdown.py  # tiny CI

Preemption-safe mode (docs/checkpointing.md): --checkpoint-dir snapshots the
sweep at chunk boundaries and --resume continues a killed run bit-identically:

  PYTHONPATH=src python examples/byzantine_showdown.py \
      --checkpoint-dir /tmp/showdown_ckpt --resume
"""
import argparse
import os

import jax

jax.config.update("jax_threefry_partitionable", True)

import jax.numpy as jnp

from repro import ExecutionPlan, setup_compilation_cache
from repro.configs import PAPER_MLP
from repro.core import (
    AttackConfig, AttackType, ChannelConfig, DefenseSpec, FLOAConfig, Policy,
    PowerConfig, first_n_mask, noise_std_for_snr,
)
from repro.core import theory
from repro.data import FederatedSampler, make_dataset, worker_split
from repro.fl import ScenarioCase, SweepSpec, run_sweep
from repro.models import init_mlp, mlp_accuracy, mlp_loss

# Smoke mode (CI): the same policy x defense x attacker-count grid — every
# defense family, every adaptive-adversary axis, mixed with the analog lanes
# through the grouped dispatch — on the tiny config with a handful of rounds.
SMOKE = bool(os.environ.get("REPRO_SMOKE"))

ROUNDS = 6 if SMOKE else 100
NS = [0, 1, 3, 4]
NS_ATK = [n for n in NS if n > 0]
MARKOV_RHO = 0.9
# K-of-U participation: K=7 of U=10 satisfies every digital lane's
# per-round hyper-parameter bound (2*trim < K, krum f <= K-3, m <= K).
PART_K = 7

DIGITAL = [
    ("digital mean (no defense)", DefenseSpec(name="mean")),
    ("digital median", DefenseSpec(name="median")),
    ("digital trimmed-mean(3)", DefenseSpec(name="trimmed_mean", trim=3)),
    ("digital Krum(f=3)", DefenseSpec(name="krum", num_byzantine=3)),
    ("digital multi-Krum(f=3,m=3)",
     DefenseSpec(name="multi_krum", num_byzantine=3, multi=3)),
    ("digital geometric-median", DefenseSpec(name="geometric_median")),
]
DIGITAL_PART = [
    ("digital median", DefenseSpec(name="median")),
    ("digital trimmed-mean(3)", DefenseSpec(name="trimmed_mean", trim=3)),
]
DIRECTIONAL = [("colluding", AttackType.COLLUDING),
               ("omniscient", AttackType.OMNISCIENT)]


def setup(dirichlet_alpha):
    mc = PAPER_MLP.smoke() if SMOKE else PAPER_MLP.full()
    x, y = make_dataset(mc.train_samples, seed=0)
    xt, yt = make_dataset(mc.test_samples, seed=99)
    if dirichlet_alpha is None:
        sampler = FederatedSampler(worker_split(x, y, mc.num_workers),
                                   mc.batch_per_worker, seed=1)
    else:
        sampler = FederatedSampler.dirichlet(
            x, y, mc.num_workers, dirichlet_alpha, mc.batch_per_worker, seed=1)
    return mc, sampler, jnp.asarray(xt), jnp.asarray(yt)


def floa_config(mc, n_atk: int, policy: Policy, noise: float,
                attack: AttackType = AttackType.STRONGEST,
                markov_rho: float = 0.0) -> FLOAConfig:
    u, d = mc.num_workers, mc.dim
    return FLOAConfig(
        channel=ChannelConfig(num_workers=u, sigma=1.0, noise_std=noise,
                              markov_rho=markov_rho),
        power=PowerConfig(num_workers=u, dim=d, p_max=mc.p_max, policy=policy),
        attack=AttackConfig(
            attack=attack if n_atk else AttackType.NONE,
            byzantine_mask=first_n_mask(u, n_atk)),
    )


def _theory_alpha(mc, n: int, policy: Policy) -> float:
    tp = theory.TheoryParams(num_workers=mc.num_workers, num_attackers=n,
                             dim=mc.dim)
    return theory.alpha_from_alpha_hat(tp, policy.value, 0.1)


def build_cases(mc):
    """The whole showdown grid — analog policies, digital defenses, and the
    adaptive-adversary variants — as lanes of one sweep.  Digital lanes ride
    an EF/noiseless channel config (their defense code ignores the channel;
    attackers are modelled as sign-flipped reported gradients, the
    digital-FL threat model)."""
    noise = noise_std_for_snr(mc.p_max, mc.dim, mc.snr_db)
    cases = []
    for policy in (Policy.BEV, Policy.CI):
        pv = policy.value
        for n in NS:
            alpha = _theory_alpha(mc, n, policy)
            cases.append(ScenarioCase(
                f"{pv}@N{n}", floa_config(mc, n, policy, noise),
                alpha, seed=5))
            # Gauss-Markov fading: same grid, correlated channel rounds.
            cases.append(ScenarioCase(
                f"{pv}/markov@N{n}",
                floa_config(mc, n, policy, noise, markov_rho=MARKOV_RHO),
                alpha, seed=5))
            # K-of-U client sampling: only PART_K workers transmit per round.
            cases.append(ScenarioCase(
                f"{pv}/K{PART_K}@N{n}", floa_config(mc, n, policy, noise),
                alpha, seed=5, participants=PART_K))
        # Colluding / omniscient cohorts (need at least one attacker).
        for tag, atk in DIRECTIONAL:
            for n in NS_ATK:
                cases.append(ScenarioCase(
                    f"{pv}/{tag}@N{n}",
                    floa_config(mc, n, policy, noise, attack=atk),
                    _theory_alpha(mc, n, policy), seed=5))
    for label, defense in DIGITAL:
        for n in NS:
            cases.append(ScenarioCase(
                f"{label}@N{n}", floa_config(mc, n, Policy.EF, 0.0),
                0.1, seed=5, defense=defense))
    # Screening under partial participation: the kernels reduce over the
    # round's K participants only.
    for label, defense in DIGITAL_PART:
        for n in NS:
            cases.append(ScenarioCase(
                f"{label}/K{PART_K}@N{n}", floa_config(mc, n, Policy.EF, 0.0),
                0.1, seed=5, defense=defense, participants=PART_K))
    return cases


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dirichlet", type=float, default=None, metavar="ALPHA",
                    help="partition training data by a Dirichlet(ALPHA) "
                         "label-skew split instead of the IID round-robin "
                         "(smaller = more skew)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="snapshot the sweep's resume carry at chunk "
                         "boundaries under DIR (preemption-safe; implies "
                         "chunked execution)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in "
                         "--checkpoint-dir (bit-identical to the "
                         "uninterrupted run; fresh start if none exists)")
    args = ap.parse_args()
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    setup_compilation_cache()  # $JAX_COMPILATION_CACHE_DIR, else .jax_cache

    mc, sampler, xt, yt = setup(args.dirichlet)
    eval_fn = lambda p: {"accuracy": mlp_accuracy(p, xt, yt)}
    params = init_mlp(jax.random.PRNGKey(0))
    batches = sampler.stack_rounds(ROUNDS)

    plan = ExecutionPlan()
    if args.checkpoint_dir:
        plan = ExecutionPlan(chunk_rounds=max(1, ROUNDS // 4),
                             checkpoint_dir=args.checkpoint_dir)
    cases = build_cases(mc)
    result = run_sweep(mlp_loss, params, batches, SweepSpec.build(cases),
                       eval_fn=eval_fn, eval_every=ROUNDS,  # final acc only
                       plan=plan, resume=args.resume)
    acc = {name: float(result.metrics["accuracy"][i, -1])
           for i, name in enumerate(result.names)}

    part = "IID" if args.dirichlet is None else f"Dirichlet({args.dirichlet})"
    print(f"# {len(cases)} lanes, one compiled sweep; data: {part}")
    print(f"{'defense':30s} " + " ".join(f"N={n:<4d}" for n in NS))
    rows = [("FLOA-BEV (analog, private)", f"{Policy.BEV.value}@N"),
            ("FLOA-CI  (analog, private)", f"{Policy.CI.value}@N")]
    for policy in (Policy.BEV, Policy.CI):
        pv = policy.value
        rows += [(f"FLOA-{pv.upper()} markov({MARKOV_RHO})",
                  f"{pv}/markov@N"),
                 (f"FLOA-{pv.upper()} K={PART_K} of U",
                  f"{pv}/K{PART_K}@N")]
        rows += [(f"FLOA-{pv.upper()} {tag}", f"{pv}/{tag}@N")
                 for tag, _ in DIRECTIONAL]
    rows += [(label, f"{label}@N") for label, _ in DIGITAL]
    rows += [(f"{label} K={PART_K}", f"{label}/K{PART_K}@N")
             for label, _ in DIGITAL_PART]
    for label, prefix in rows:
        cells = [acc.get(f"{prefix}{n}") for n in NS]
        print(f"{label:30s} " + " ".join(
            "--   " if a is None else f"{a:.3f}" for a in cells))


if __name__ == "__main__":
    main()
