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

Tiling of the batched kernels: rows stay [S, D] and the slab [S, U, D];
one grid step takes a lane block of s_blk lanes (all of S up to 8, else 8,
a whole sublane tile) and a column block of T columns: (s_blk, T) rows and
an (s_blk, U, T) slab, the worker axis reduced inside the block.  T is
sized in bytes from (S, U, itemsize): the widest multiple of 128 whose
double-buffered slab and row blocks, each padded to the sublane tile, fit
BATCHED_BLOCK_BUDGET (32 MiB), half of the 64 MiB scoped-VMEM limit the
kernels pass to Mosaic (v5e has 128 MiB); the rest is the body's f32
temporaries.  At S = 2, U = 4, f32 that is T = 87,296, 5.6 MB of HBM per
step: a step's fixed cost is small against its transfer, so the kernel
runs near HBM bandwidth instead of at the rate of grid steps.  The grid
is (cdiv(S, s_blk), cdiv(D, T)): the last lane and column blocks are
ragged, their out-of-range entries are never written, and no lane or
column reads another, so D is never padded and no output is sliced.  With
the rows 2-D, XLA passes every operand in the layout it already holds
(tests/test_tpu_compile.py compiles the step at the qwen3-4b cell's width
for v5e and finds no D-wide pad or copy).

The unbatched `floa_aggregate` keeps a fixed TILE_D (=2048) grid and pads
D once, in its un-jitted public wrapper, before the jitted pallas_call core
is entered (an earlier version recursed back into the jitted entry point
with re-padded operands, re-entering the jit trace).  TILE_D is also the
unit the model-sharded sweep pads D to (`fl.sweep._ModelShards`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

TILE_D = 2048
# Scoped VMEM the batched kernels ask for (v5e has 128 MiB; the default
# scope is 16 MiB), and the share of it their double-buffered blocks may
# take: the rest is the kernel body's f32 temporaries.
BATCHED_VMEM_LIMIT = 64 << 20
BATCHED_BLOCK_BUDGET = BATCHED_VMEM_LIMIT // 2


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
    # One (lane block, column block) grid step: rows arrive as [s_blk, T],
    # the slab as [s_blk, U, T], coefficients as [s_blk, U, 1].
    s = coeff_ref[...]                              # [s_blk, U, 1]
    g = g_ref[...].astype(jnp.float32)              # [s_blk, U, T]
    z = z_ref[...].astype(jnp.float32)              # [s_blk, T]
    bias = scal_ref[:, 0:1]                         # [s_blk, 1]
    eps = scal_ref[:, 1:2]
    acc = jnp.sum(s * g, axis=1)                    # VPU reduce over workers
    o_ref[...] = (acc + bias + eps * z).astype(o_ref.dtype)


def _batched_step_kernel(scal_ref, coeff_ref, w_ref, g_ref, z_ref,
                         wo_ref, go_ref):
    s = coeff_ref[...]                              # [s_blk, U, 1]
    w = w_ref[...].astype(jnp.float32)              # [s_blk, T] params
    g = g_ref[...].astype(jnp.float32)              # [s_blk, U, T]
    z = z_ref[...].astype(jnp.float32)              # [s_blk, T]
    bias = scal_ref[:, 0:1]                         # [s_blk, 1]
    eps = scal_ref[:, 1:2]
    alpha = scal_ref[:, 2:3]
    gagg = jnp.sum(s * g, axis=1) + bias + eps * z
    go_ref[...] = gagg.astype(go_ref.dtype)
    wo_ref[...] = (w - alpha * gagg).astype(wo_ref.dtype)


def _block_col_bytes(rows: int, itemsize: int) -> int:
    """VMEM bytes per column of a [rows, T] block: rows pad to the sublane
    tile, 8 rows of 32-bit words (16 of a 16-bit dtype)."""
    return -(-rows * itemsize // 32) * 32


def batched_vmem_bytes(s_blk: int, u: int, tile_d: int, itemsize: int,
                       n_rows: int) -> int:
    """VMEM of one batched grid step's blocks, double-buffered: the
    [s_blk, U, T] slab block plus `n_rows` [s_blk, T] row blocks."""
    per_col = (s_blk * _block_col_bytes(u, itemsize)
               + n_rows * _block_col_bytes(s_blk, itemsize))
    return 2 * per_col * tile_d


def batched_blocks(s: int, u: int, d: int, itemsize: int,
                   n_rows: int) -> tuple[int, int]:
    """(s_blk, T) of the batched kernels: all of S up to 8 lanes a block
    (else 8, a whole sublane tile), and the widest T, a multiple of 128
    and no wider than D needs, whose double-buffered blocks fit
    BATCHED_BLOCK_BUDGET."""
    s_blk = s if s <= 8 else 8
    per_t = batched_vmem_bytes(s_blk, u, 1, itemsize, n_rows)
    t = BATCHED_BLOCK_BUDGET // per_t // 128 * 128
    return s_blk, max(128, min(t, -(-d // 128) * 128))


def _batched_specs(s_n: int, u: int, d: int, itemsize: int, n_rows: int,
                   n_scal: int, tile_d: int | None):
    """Grid and BlockSpecs of the batched kernels over per-lane scalars
    [S, n_scal], coefficients viewed [S, U, 1], the slab [S, U, D] and rows
    [S, D].  Every block's last two dims are whole or (s_blk, T) with
    s_blk = S or 8 and T a multiple of 128, as Mosaic requires.  The grid's
    last lane and column blocks may be ragged: their out-of-range entries
    are never written, and no lane or column reads another."""
    s_blk, t = batched_blocks(s_n, u, d, itemsize, n_rows)
    t = t if tile_d is None else tile_d
    grid = (pl.cdiv(s_n, s_blk), pl.cdiv(d, t))
    scal = pl.BlockSpec((s_blk, n_scal), lambda s, i: (s, 0))
    coeff = pl.BlockSpec((s_blk, u, 1), lambda s, i: (s, 0, 0))
    slab = pl.BlockSpec((s_blk, u, t), lambda s, i: (s, 0, i))
    row = pl.BlockSpec((s_blk, t), lambda s, i: (s, i))
    return grid, scal, coeff, slab, row


_BATCHED_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=BATCHED_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def floa_aggregate_batched(coeffs: Array, grads: Array, noise: Array,
                           bias: Array, eps: Array, interpret: bool = False,
                           tile_d: int | None = None) -> Array:
    """Batched scenario-sweep variant of `floa_aggregate`.

    coeffs [S, U] f32, grads [S, U, D], noise [S, D], bias/eps [S] -> [S, D].
    Grid is (S / s_blk, D / T), lane-block-major so each block's
    coefficients and scalars are loaded once and reused across its column
    blocks.  tile_d overrides the derived T (a multiple of 128).
    """
    s_n, u, d = grads.shape
    assert coeffs.shape == (s_n, u) and noise.shape == (s_n, d)
    assert bias.shape == (s_n,) and eps.shape == (s_n,)
    scal = jnp.stack([bias.astype(jnp.float32),
                      eps.astype(jnp.float32)], axis=1)     # [S, 2]
    itemsize = max(grads.dtype.itemsize, noise.dtype.itemsize)
    grid, scal_spec, coeff_spec, slab_spec, row_spec = _batched_specs(
        s_n, u, d, itemsize, 2, 2, tile_d)
    return pl.pallas_call(
        _batched_kernel,
        grid=grid,
        in_specs=[scal_spec, coeff_spec, slab_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, d), grads.dtype),
        compiler_params=_BATCHED_PARAMS,
        name="floa_aggregate_batched",
        interpret=interpret,
    )(scal, coeffs.astype(jnp.float32)[:, :, None], grads, noise)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def floa_step_batched(w: Array, coeffs: Array, grads: Array, noise: Array,
                      bias: Array, eps: Array, alpha: Array,
                      interpret: bool = False, tile_d: int | None = None):
    """Fused combine + PS update over the [S, U, D] slab (eq. 7 + eq. 8).

    w [S, D], coeffs [S, U] f32, grads [S, U, D], noise [S, D],
    bias/eps/alpha [S] -> (w_new [S, D], gagg [S, D]).

    Same grid and blocks as `floa_aggregate_batched` plus one parameter row
    in and two rows out per block; the parameter state never leaves flat
    [S, D] form, which is what makes the sweep engine's flat-state scan one
    pass.
    """
    s_n, u, d = grads.shape
    assert w.shape == (s_n, d) and coeffs.shape == (s_n, u)
    assert noise.shape == (s_n, d)
    assert bias.shape == (s_n,) and eps.shape == (s_n,)
    assert alpha.shape == (s_n,)
    scal = jnp.stack([bias.astype(jnp.float32),
                      eps.astype(jnp.float32),
                      alpha.astype(jnp.float32)], axis=1)   # [S, 3]
    itemsize = max(w.dtype.itemsize, grads.dtype.itemsize,
                   noise.dtype.itemsize)
    grid, scal_spec, coeff_spec, slab_spec, row_spec = _batched_specs(
        s_n, u, d, itemsize, 4, 3, tile_d)
    w_new, gagg = pl.pallas_call(
        _batched_step_kernel,
        grid=grid,
        in_specs=[scal_spec, coeff_spec, row_spec, slab_spec, row_spec],
        out_specs=[row_spec, row_spec],             # new params, aggregate
        out_shape=[
            jax.ShapeDtypeStruct((s_n, d), w.dtype),
            jax.ShapeDtypeStruct((s_n, d), grads.dtype),
        ],
        compiler_params=_BATCHED_PARAMS,
        name="floa_step_batched",
        interpret=interpret,
    )(scal, coeffs.astype(jnp.float32)[:, :, None], w, grads, noise)
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
