"""Public jit'd entry points for the Pallas kernels.

Every entry compiles its kernel to Mosaic for the TPU.  `interpret=True`
runs the kernel body through the Pallas interpreter instead, on any backend;
only callers that ask for it get it (the CPU test suite, the kernel
microbenchmark's --interpret mode), so a kernel never silently stops running
on the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_attention
from repro.kernels.floa_aggregate import floa_aggregate as _floa_aggregate
from repro.kernels.floa_aggregate import (
    floa_aggregate_batched as _floa_aggregate_batched,
)
from repro.kernels.floa_aggregate import floa_step_batched as _floa_step_batched
from repro.kernels.defense_sort import (
    BITONIC_MAX_U,
    UNROLL_MAX_U,
    sort_columns as _sort_columns,
    sort_columns_bitonic as _sort_columns_bitonic,
)
from repro.kernels.grad_stats import grad_stats as _grad_stats

Array = jax.Array


def floa_aggregate(coeffs, grads, noise, bias, eps, interpret=False) -> Array:
    return _floa_aggregate(coeffs, grads, noise, jnp.asarray(bias),
                           jnp.asarray(eps), interpret=interpret)


def floa_aggregate_batched(coeffs, grads, noise, bias, eps,
                           interpret=False) -> Array:
    return _floa_aggregate_batched(coeffs, grads, noise, jnp.asarray(bias),
                                   jnp.asarray(eps), interpret=interpret)


def floa_step_batched(w, coeffs, grads, noise, bias, eps, alpha,
                      interpret=False):
    """Fused [S, U, D] combine + PS update; returns (w_new, gagg)."""
    return _floa_step_batched(w, coeffs, grads, noise, jnp.asarray(bias),
                              jnp.asarray(eps), jnp.asarray(alpha),
                              interpret=interpret)


def sort_columns(x, interpret=False) -> Array:
    """[U, D] ascending sort along the worker axis (odd-even network,
    U <= UNROLL_MAX_U).  Batched use goes through `jax.vmap` (Pallas lifts
    it into a leading grid dimension); `sort_columns_batched_ref` is that
    route's oracle."""
    return _sort_columns(x, interpret=interpret)


def sort_columns_bitonic(x, interpret=False) -> Array:
    """[U, D] ascending sort along the worker axis — the large-U successor
    to `sort_columns`: O(log^2 U) bitonic stages instead of an O(U^2)
    unrolled network, U padded to a power of two (<= BITONIC_MAX_U).  Same
    oracle (`sort_columns_ref`) and vmap route as `sort_columns`."""
    return _sort_columns_bitonic(x, interpret=interpret)


def grad_stats(grads, interpret=False) -> Array:
    return _grad_stats(grads, interpret=interpret)


def decode_attention(q, k, v, pos, interpret=False) -> Array:
    return _decode_attention(q, k, v, pos, interpret=interpret)


# oracles re-exported for tests/benchmarks
floa_aggregate_ref = ref.floa_aggregate_ref
floa_aggregate_batched_ref = ref.floa_aggregate_batched_ref
floa_step_batched_ref = ref.floa_step_batched_ref
sort_columns_ref = ref.sort_columns_ref
sort_columns_batched_ref = ref.sort_columns_batched_ref
grad_stats_ref = ref.grad_stats_ref
decode_attention_ref = ref.decode_attention_ref
