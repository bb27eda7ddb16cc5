"""Kernel microbenchmarks: kernel-vs-oracle correctness + oracle wall-time.

The kernels compile for the TPU; --interpret runs them through the Pallas
interpreter instead, which is the only way they run on a CPU host.  The
wall-clock column times the ORACLE (jnp) path, and is a host timing unless
the run is on the chip; the printed `derived` column is the max abs error of
the kernel vs its oracle (the correctness contract that must hold before any
TPU deployment).

  PYTHONPATH=src python benchmarks/kernels_bench.py --tiny --interpret
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops


def _timeit(fn, *args, iters: int = 5) -> float:
    fn(*args)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def main(tiny: bool = False, interpret: bool = False) -> None:
    key = jax.random.PRNGKey(0)
    rows = []

    u, d = 16, (1 << 14 if tiny else 1 << 20)
    dtag = "16k" if tiny else "1M"
    ks = jax.random.split(key, 4)
    coeffs = jax.random.normal(ks[0], (u,))
    grads = jax.random.normal(ks[1], (u, d), jnp.float32)
    noise = jax.random.normal(ks[2], (d,))
    bias, eps = jnp.float32(0.1), jnp.float32(0.7)
    t = _timeit(ops.floa_aggregate_ref, coeffs, grads, noise, bias, eps)
    got = ops.floa_aggregate(coeffs, grads, noise, bias, eps,
                             interpret=interpret)
    want = ops.floa_aggregate_ref(coeffs, grads, noise, bias, eps)
    rows.append((f"floa_aggregate_u16_d{dtag}", t,
                 float(jnp.max(jnp.abs(got - want)))))

    # batched sweep variant: S scenario lanes over the same [U, D] slab size
    s_n = 2 if tiny else 8
    kb = jax.random.split(jax.random.PRNGKey(1), 5)
    bc = jax.random.normal(kb[0], (s_n, u))
    bg = jax.random.normal(kb[1], (s_n, u, d), jnp.float32)
    bz = jax.random.normal(kb[2], (s_n, d))
    bb = jax.random.normal(kb[3], (s_n,))
    be = jax.random.normal(kb[4], (s_n,))
    t = _timeit(ops.floa_aggregate_batched_ref, bc, bg, bz, bb, be)
    got = ops.floa_aggregate_batched(bc, bg, bz, bb, be,
                                     interpret=interpret)
    want = ops.floa_aggregate_batched_ref(bc, bg, bz, bb, be)
    rows.append((f"floa_aggregate_batched_s{s_n}_u16_d{dtag}", t,
                 float(jnp.max(jnp.abs(got - want)))))

    t = _timeit(ops.grad_stats_ref, grads)
    got = ops.grad_stats(grads, interpret=interpret)
    want = ops.grad_stats_ref(grads)
    err = float(jnp.max(jnp.abs(got - want) / (jnp.abs(want) + 1.0)))  # relative
    rows.append((f"grad_stats_u16_d{dtag}", t, err))

    b, h, kv, hd, s = (1, 4, 2, 64, 512) if tiny else (4, 16, 8, 128, 8192)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32)
    pos = jnp.int32(s - 1)
    t = _timeit(ops.decode_attention_ref, q, k, v, pos)
    err = float(jnp.max(jnp.abs(
        ops.decode_attention(q, k, v, pos, interpret=interpret)
        - ops.decode_attention_ref(q, k, v, pos))))
    rows.append((f"decode_attention_b{b}_s{s}", t, err))

    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.3e}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes for CI smoke (interpret mode is slow)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the kernels in the Pallas interpreter (CPU hosts)")
    args = ap.parse_args()
    main(tiny=args.tiny, interpret=args.interpret)
