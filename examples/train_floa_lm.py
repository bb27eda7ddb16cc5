"""End-to-end driver: the real-model LM sweep lane.

Trains a shrunk qwen3-shaped transformer (configs.qwen3_4b.lm_sweep,
D ~ 3.0M flat params — past every kernel-routing threshold) on the Markov
token stream for R FLOA rounds as ONE compiled sweep: three scenario lanes
(clean BEV, sign-flip attack, median screening of the same attack) share the
[S, D] flat state and run in a single `SweepEngine` dispatch.

  PYTHONPATH=src python examples/train_floa_lm.py --rounds 20

  # ("model",)-sharded big-D state over 4 devices (4 TPU chips, or on a
  # CPU host XLA_FLAGS=--xla_force_host_platform_device_count=4):
  python examples/train_floa_lm.py --model-shards 4

  # Preemption-safe: checkpoint at chunk boundaries, rerun with --resume.
  python examples/train_floa_lm.py --checkpoint-dir /tmp/lm_ckpt --resume

--smoke shrinks the model to D ~ 70k for a seconds-scale CPU sanity pass.
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

jax.config.update("jax_threefry_partitionable", True)

from repro.configs.registry import flat_param_dim, get_lm_sweep
from repro.core import (
    AttackConfig,
    AttackType,
    ChannelConfig,
    DefenseSpec,
    FLOAConfig,
    Policy,
    PowerConfig,
    first_n_mask,
)
from repro.data import stack_token_rounds
from repro.fl import ExecutionPlan, ScenarioCase, SweepEngine, SweepSpec
from repro.launch.mesh import make_sweep_mesh
from repro.models.transformer import init_lm, lm_loss


def lm_lanes(u: int, dim: int, n_atk: int, lr: float):
    """The three-lane showdown: no-attack BEV FLOA, the Thm-1 sign-flip
    attack on the same channel, and median screening of that attack."""
    def floa(policy, attack, n, noise=0.05):
        return FLOAConfig(
            channel=ChannelConfig(num_workers=u, sigma=1.0,
                                  noise_std=0.0 if policy == Policy.EF
                                  else noise),
            power=PowerConfig(num_workers=u, dim=dim, p_max=1.0,
                              policy=policy),
            attack=AttackConfig(attack=attack if n else AttackType.NONE,
                                byzantine_mask=first_n_mask(u, n)))

    return [
        ScenarioCase("bev-clean", floa(Policy.BEV, AttackType.NONE, 0),
                     lr, seed=11),
        ScenarioCase("bev-signflip",
                     floa(Policy.BEV, AttackType.STRONGEST, n_atk),
                     lr, seed=12),
        ScenarioCase("median-signflip",
                     floa(Policy.EF, AttackType.STRONGEST, n_atk, noise=0.0),
                     lr, seed=13, defense=DefenseSpec(name="median")),
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2, help="per-worker batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--byzantine", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--model-shards", type=int, default=1,
                    help="shard the flat [S, D] state's D axis over this "
                         "many devices (adds a ('model',) mesh axis)")
    ap.add_argument("--chunk-rounds", type=int, default=None,
                    help="scan-of-chunks execution (required with "
                         "--checkpoint-dir; defaults to rounds//4 then)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="preemption-safe resume checkpoints at chunk "
                         "boundaries")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint and run only the "
                         "remaining chunks (fresh run if none exists)")
    ap.add_argument("--smoke", action="store_true",
                    help="D ~ 70k seconds-scale variant of the same lane")
    args = ap.parse_args()

    cfg = get_lm_sweep()
    if args.smoke:
        cfg = dataclasses.replace(
            cfg, n_layers=1, d_model=64, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=128, vocab_size=256)
    dim = flat_param_dim(cfg)
    print(f"model {cfg.name}: {cfg.n_layers}L d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} -> flat D = {dim:,}")

    u = args.workers
    spec = SweepSpec.build(lm_lanes(u, dim, args.byzantine, args.lr))
    # One Markov token batch per round, [R, U*B, S+1]; per_worker_grads
    # splits the row axis into U workers of B sequences each.
    batches = {"tokens": stack_token_rounds(
        args.rounds, u * args.batch, args.seq + 1, cfg.vocab_size, seed=0)}
    params0, _ = init_lm(jax.random.PRNGKey(0), cfg)

    chunk = args.chunk_rounds
    if args.checkpoint_dir is not None and chunk is None:
        chunk = max(1, args.rounds // 4)
    mesh = (make_sweep_mesh(model_shards=args.model_shards)
            if args.model_shards > 1 else None)
    plan = ExecutionPlan(mesh=mesh, chunk_rounds=chunk,
                         checkpoint_dir=args.checkpoint_dir)
    engine = SweepEngine(lambda p, b: lm_loss(p, b, cfg), spec, plan=plan)

    t0 = time.time()
    res = engine.run(params0, batches, resume=args.resume)
    dt = time.time() - t0

    print(f"\n{args.rounds} rounds x {len(spec.cases)} lanes in one "
          f"compiled sweep ({dt:.1f}s):")
    tail = max(1, args.rounds // 5)
    for i, name in enumerate(res.names):
        ls = res.loss[i]
        print(f"  {name:16s} loss {ls[0]:7.4f} -> {np.mean(ls[-tail:]):7.4f}")
    clean = res.loss[list(res.names).index("bev-clean")]
    assert np.mean(clean[-tail:]) < clean[0], \
        "clean BEV lane failed to reduce LM loss"


if __name__ == "__main__":
    main()
