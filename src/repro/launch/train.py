"""Training driver: FLOA-federated LM training on a device mesh.

Runs REAL steps (allocating params), so on this CPU host it is meant for
reduced configs; on TPU pods the same entrypoint drives the full configs.

  python -m repro.launch.train --arch qwen3-4b --smoke --mesh 4x2 \
      --steps 20 --batch 8 --seq 64 --policy bev --byzantine 1
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_threefry_partitionable", True)

from repro import checkpoint as CK
from repro.configs import get_config, get_smoke
from repro.core.power_control import Policy
from repro.data import sample_tokens
from repro.launch.distributed import (initialize_distributed,
                                      setup_compilation_cache)
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.steps import init_floa_state, init_model, make_train_step


def main() -> None:
    # Multi-host bootstrap is a no-op on a plain single-process launch
    # (JAX_COORDINATOR_ADDRESS unset); the compile cache goes to
    # $JAX_COMPILATION_CACHE_DIR, else the checkout's .jax_cache.
    initialize_distributed()
    setup_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--mesh", default="1x1",
                    help="'RxC' debug mesh, or 'single'/'multi' production")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--alpha", type=float, default=0.02)
    ap.add_argument("--policy", default="bev", choices=["bev", "ci", "ef"])
    ap.add_argument("--byzantine", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args()

    if args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        mp = 16
    else:
        r, c = map(int, args.mesh.split("x"))
        mesh = make_debug_mesh((r, c), ("data", "model"))
        mp = c
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(cfg, model_parallel=mp)
    assert cfg.arch_type != "audio" or True

    shape = dict(seq_len=args.seq, global_batch=args.batch, kind="train")
    art = make_train_step(cfg, mesh, shape, alpha=args.alpha,
                          policy=Policy(args.policy),
                          n_byzantine=args.byzantine)
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    state = init_floa_state()
    print(f"arch={cfg.name} params={art.meta['dim']:,} workers="
          f"{art.meta['num_workers']} policy={args.policy} "
          f"byzantine={args.byzantine}")

    def make_batch(step: int):
        toks = sample_tokens(args.batch, args.seq + 1,
                             vocab=cfg.vocab_size, seed=step)
        b = {"tokens": jnp.asarray(toks)}
        if cfg.arch_type == "vlm":
            b["embeds_prefix"] = jnp.zeros(
                (args.batch, cfg.frontend.n_prefix, cfg.frontend.feature_dim),
                jnp.float32)
        if cfg.arch_type == "audio":
            b["frames"] = jax.random.normal(
                jax.random.PRNGKey(step),
                (args.batch, min(args.seq, cfg.encdec.enc_seq_cap),
                 cfg.frontend.feature_dim))
        return b

    with mesh:
        step_fn = jax.jit(art.fn, in_shardings=art.in_shardings)
        for t in range(args.steps):
            t0 = time.perf_counter()
            params, state, metrics = step_fn(params, state, make_batch(t),
                                             jnp.uint32(t))
            loss = float(metrics["loss"])
            print(f"step {t:4d} loss {loss:8.4f} "
                  f"({time.perf_counter() - t0:5.2f}s)", flush=True)
            assert np.isfinite(loss), "training diverged"
            if args.ckpt and args.ckpt_every and (t + 1) % args.ckpt_every == 0:
                CK.save(args.ckpt, t + 1, jax.device_get(params))
    if args.ckpt:
        CK.save(args.ckpt, args.steps, jax.device_get(params))
        print(f"checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
