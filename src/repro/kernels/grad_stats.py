"""Per-worker gradient statistics kernel (standardization round, eq. 3).

Computes, for each worker u, (sum_d G[u,d], sum_d G[u,d]^2) in one HBM pass
with f32 accumulators.  The mean/variance the workers report to the PS follow
as gbar = s1/D, eps2 = s2/D - gbar^2 on scalars.

Tiling: grid over D; the [U, 2] accumulator block is revisited by every grid
step (output index_map constant), a standard Pallas reduction: initialized at
step 0, accumulated thereafter.  On real TPUs the (U, 2) output pads to the
(8, 128) tile — negligible next to the [U, D] stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

TILE_D = 2048


def _kernel(g_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    g = g_ref[:].astype(jnp.float32)                # [U, TILE_D]
    s1 = jnp.sum(g, axis=1)
    s2 = jnp.sum(g * g, axis=1)
    o_ref[:] = o_ref[:] + jnp.stack([s1, s2], axis=1)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def grad_stats(grads: Array, interpret: bool = False,
               tile_d: int = TILE_D) -> Array:
    """grads [U, D] -> [U, 2] f32 (sum, sum of squares)."""
    u, d = grads.shape
    if d % tile_d:
        grads = jnp.pad(grads, ((0, 0), (0, tile_d - d % tile_d)))
        d = grads.shape[1]
    return pl.pallas_call(
        _kernel,
        grid=(d // tile_d,),
        in_specs=[pl.BlockSpec((u, tile_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((u, 2), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((u, 2), jnp.float32),
        name="grad_stats",
        interpret=interpret,
    )(grads)
