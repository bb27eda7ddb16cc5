"""Fused FLOA aggregation kernels (the paper's hot spot, eq. 7-8).

`floa_aggregate` computes out[d] = sum_u s[u] * G[u, d] + bias + eps * z[d]
in one pass over the gradient: per-worker scale, over-the-air superposition,
de-standardization bias, and receiver-noise injection are fused so the [U, D]
gradient block is read exactly once from HBM (the op is bandwidth-bound:
U*D reads, D writes, 2*U*D flops -> arithmetic intensity ~1 flop/byte, so
fusion is the whole win).

`floa_step_batched` additionally fuses the PS update (eq. 8) into the same
pass: w_new[s] = w[s] - alpha[s] * (coeffs[s] @ G[s] + bias[s] + eps[s] z[s]).
The aggregate is emitted as a second output so callers can log grad norms;
writes grow from D to 2*D per scenario but the U*D gradient reads still
dominate, and the parameter row is read/written exactly once.

Tiling: grid over D in TILE_D (=2048, a multiple of the 128-lane VPU width)
steps; the [U, TILE_D] slab plus coefficient vector live in VMEM.  For
U<=32, TILE_D=2048, f32: 32*2048*4 = 256 KiB slab, 512 KiB double-buffered,
plus a few [1, TILE_D] rows (each padded to 8 sublanes, 64 KiB) — well
inside v5e's 16 MiB default scoped VMEM.  The batched kernels view their
[S, ...] operands with a unit middle axis and squeeze the scenario axis out
of every block, so each block's last two dims are either whole or
(1, TILE_D), as Mosaic requires (tests/test_tpu_compile.py compiles them
for v5e at real width).

D-padding happens once, in the un-jitted public wrappers, before the jitted
pallas_call core is entered (an earlier version recursed back into the jitted
entry point with re-padded operands, re-entering the jit trace; see the
non-multiple-of-TILE_D regression tests in tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

TILE_D = 2048


def _pad_last(x: Array, pad: int) -> Array:
    """Zero-pad the last axis by `pad` entries (no-op when pad == 0)."""
    if not pad:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


def _kernel(scal_ref, coeff_ref, g_ref, z_ref, o_ref):
    s = coeff_ref[:].astype(jnp.float32)            # [U]
    g = g_ref[:].astype(jnp.float32)                # [U, TILE_D]
    z = z_ref[:].astype(jnp.float32)                # [TILE_D]
    bias = scal_ref[0, 0]
    eps = scal_ref[0, 1]
    acc = jnp.sum(s[:, None] * g, axis=0)           # VPU reduce over workers
    o_ref[:] = (acc + bias + eps * z).astype(o_ref.dtype)


def _batched_kernel(scal_ref, coeff_ref, g_ref, z_ref, o_ref):
    # One (scenario, D tile) grid step; the scenario axis is squeezed out of
    # every block, so rows arrive as [1, TILE_D] and coefficients as [U, 1].
    s = coeff_ref[...]                              # [U, 1] scenario coeffs
    g = g_ref[...].astype(jnp.float32)              # [U, TILE_D]
    z = z_ref[...].astype(jnp.float32)              # [1, TILE_D]
    bias = scal_ref[0, 0]
    eps = scal_ref[0, 1]
    acc = jnp.sum(s * g, axis=0, keepdims=True)     # VPU reduce over workers
    o_ref[...] = (acc + bias + eps * z).astype(o_ref.dtype)


def _batched_step_kernel(scal_ref, coeff_ref, w_ref, g_ref, z_ref,
                         wo_ref, go_ref):
    s = coeff_ref[...]                              # [U, 1] scenario coeffs
    w = w_ref[...].astype(jnp.float32)              # [1, TILE_D] params
    g = g_ref[...].astype(jnp.float32)              # [U, TILE_D]
    z = z_ref[...].astype(jnp.float32)              # [1, TILE_D]
    bias = scal_ref[0, 0]
    eps = scal_ref[0, 1]
    alpha = scal_ref[0, 2]
    gagg = jnp.sum(s * g, axis=0, keepdims=True) + bias + eps * z
    go_ref[...] = gagg.astype(go_ref.dtype)
    wo_ref[...] = (w - alpha * gagg).astype(wo_ref.dtype)


def _batched_specs(u: int, tile_d: int, n_scal: int):
    """BlockSpecs for the batched kernels over [S, ...] operands viewed with
    a unit middle axis, so every block's last two dims are either full
    (scalars [1, n_scal], coefficients [U, 1], slab rows [U, ...]) or
    (1, TILE_D) — the shapes Mosaic accepts (a last-two block dim must
    divide by (8, 128) or equal the array's dim).  The leading scenario dim
    is squeezed (None)."""
    scal = pl.BlockSpec((None, 1, n_scal), lambda s, i: (s, 0, 0))
    coeff = pl.BlockSpec((None, u, 1), lambda s, i: (s, 0, 0))
    slab = pl.BlockSpec((None, u, tile_d), lambda s, i: (s, 0, i))
    row = pl.BlockSpec((None, 1, tile_d), lambda s, i: (s, 0, i))
    return scal, coeff, slab, row


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def _floa_aggregate_batched_core(coeffs: Array, grads: Array, noise: Array,
                                 bias: Array, eps: Array, interpret: bool,
                                 tile_d: int) -> Array:
    s_n, u, d = grads.shape
    assert d % tile_d == 0, "core requires pre-padded D (see public wrapper)"
    scal = jnp.stack([bias.astype(jnp.float32),
                      eps.astype(jnp.float32)], axis=1)[:, None]  # [S, 1, 2]
    scal_spec, coeff_spec, slab_spec, row_spec = _batched_specs(u, tile_d, 2)
    out = pl.pallas_call(
        _batched_kernel,
        grid=(s_n, d // tile_d),
        in_specs=[scal_spec, coeff_spec, slab_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, 1, d), grads.dtype),
        name="floa_aggregate_batched",
        interpret=interpret,
    )(scal, coeffs.astype(jnp.float32)[:, :, None], grads, noise[:, None])
    return out[:, 0]


def floa_aggregate_batched(coeffs: Array, grads: Array, noise: Array,
                           bias: Array, eps: Array, interpret: bool = False,
                           tile_d: int = TILE_D) -> Array:
    """Batched scenario-sweep variant of `floa_aggregate`.

    coeffs [S, U] f32, grads [S, U, D], noise [S, D], bias/eps [S] -> [S, D].
    Grid is (S, D // TILE_D): scenario-major so each scenario's coeff/bias/eps
    row is loaded once and reused across its D tiles; the [U, TILE_D] gradient
    slab per grid step is identical to the unbatched kernel, so the VMEM
    budget does not grow with S.
    """
    s_n, u, d = grads.shape
    assert coeffs.shape == (s_n, u) and noise.shape == (s_n, d)
    assert bias.shape == (s_n,) and eps.shape == (s_n,)
    pad = -d % tile_d  # single pad before the jitted core (D is huge anyway)
    out = _floa_aggregate_batched_core(
        coeffs, _pad_last(grads, pad), _pad_last(noise, pad), bias, eps,
        interpret=interpret, tile_d=tile_d)
    return out[:, :d] if pad else out


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def _floa_step_batched_core(w: Array, coeffs: Array, grads: Array,
                            noise: Array, bias: Array, eps: Array,
                            alpha: Array, interpret: bool, tile_d: int):
    s_n, u, d = grads.shape
    assert d % tile_d == 0, "core requires pre-padded D (see public wrapper)"
    scal = jnp.stack([bias.astype(jnp.float32),
                      eps.astype(jnp.float32),
                      alpha.astype(jnp.float32)], axis=1)[:, None]  # [S, 1, 3]
    scal_spec, coeff_spec, slab_spec, row_spec = _batched_specs(u, tile_d, 3)
    w_new, gagg = pl.pallas_call(
        _batched_step_kernel,
        grid=(s_n, d // tile_d),
        in_specs=[scal_spec, coeff_spec, row_spec, slab_spec, row_spec],
        out_specs=[row_spec, row_spec],             # new params, aggregate
        out_shape=[
            jax.ShapeDtypeStruct((s_n, 1, d), w.dtype),
            jax.ShapeDtypeStruct((s_n, 1, d), grads.dtype),
        ],
        name="floa_step_batched",
        interpret=interpret,
    )(scal, coeffs.astype(jnp.float32)[:, :, None], w[:, None], grads,
      noise[:, None])
    return w_new[:, 0], gagg[:, 0]


def floa_step_batched(w: Array, coeffs: Array, grads: Array, noise: Array,
                      bias: Array, eps: Array, alpha: Array,
                      interpret: bool = False, tile_d: int = TILE_D):
    """Fused combine + PS update over the [S, U, D] slab (eq. 7 + eq. 8).

    w [S, D], coeffs [S, U] f32, grads [S, U, D], noise [S, D],
    bias/eps/alpha [S] -> (w_new [S, D], gagg [S, D]).

    Same grid/VMEM layout as `floa_aggregate_batched` plus one parameter row
    in and two rows out per tile; the parameter state never leaves flat [S, D]
    form, which is what makes the sweep engine's flat-state scan one pass.
    """
    s_n, u, d = grads.shape
    assert w.shape == (s_n, d) and coeffs.shape == (s_n, u)
    assert noise.shape == (s_n, d)
    assert bias.shape == (s_n,) and eps.shape == (s_n,)
    assert alpha.shape == (s_n,)
    pad = -d % tile_d  # single pad before the jitted core
    w_new, gagg = _floa_step_batched_core(
        _pad_last(w, pad), coeffs, _pad_last(grads, pad),
        _pad_last(noise, pad), bias, eps, alpha,
        interpret=interpret, tile_d=tile_d)
    if pad:
        w_new, gagg = w_new[:, :d], gagg[:, :d]
    return w_new, gagg


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def _floa_aggregate_core(coeffs: Array, grads: Array, noise: Array,
                         bias: Array, eps: Array, interpret: bool,
                         tile_d: int) -> Array:
    u, d = grads.shape
    assert d % tile_d == 0, "core requires pre-padded D (see public wrapper)"
    scal = jnp.stack([bias.astype(jnp.float32),
                      eps.astype(jnp.float32)]).reshape(1, 2)
    return pl.pallas_call(
        _kernel,
        grid=(d // tile_d,),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i: (0, 0)),            # scalars
            pl.BlockSpec((u,), lambda i: (0,)),                # coeffs
            pl.BlockSpec((u, tile_d), lambda i: (0, i)),       # gradient slab
            pl.BlockSpec((tile_d,), lambda i: (i,)),           # noise
        ],
        out_specs=pl.BlockSpec((tile_d,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((d,), grads.dtype),
        name="floa_aggregate",
        interpret=interpret,
    )(scal, coeffs, grads, noise)


def floa_aggregate(coeffs: Array, grads: Array, noise: Array, bias: Array,
                   eps: Array, interpret: bool = False,
                   tile_d: int = TILE_D) -> Array:
    """coeffs [U] f32, grads [U, D], noise [D], bias/eps scalars -> [D]."""
    u, d = grads.shape
    pad = -d % tile_d  # single pad before the jitted core
    out = _floa_aggregate_core(
        coeffs, _pad_last(grads, pad), _pad_last(noise, pad), bias, eps,
        interpret=interpret, tile_d=tile_d)
    return out[:d] if pad else out
