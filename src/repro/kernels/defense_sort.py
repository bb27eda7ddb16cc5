"""Pallas coordinate-sort kernel for the digital screening defenses.

Coordinate-wise median and trimmed-mean both reduce a sorted-per-coordinate
view of the gathered [U, D] gradient slab (core/defenses.py).  `jnp.sort`
along the worker axis lowers to a generic variadic sort that moves the slab
through HBM more than once at large D; but U is tiny (the paper runs U=10)
and STATIC, so the sort is better expressed as a fixed odd-even transposition
network over the worker axis — U compare-exchange passes of `minimum`/
`maximum` on [TILE_D]-wide rows, fully unrolled at trace time, one pass over
the slab in VMEM.

The unrolled network is an O(U^2) trace, so it is CAPPED at U <=
UNROLL_MAX_U (32): at the paper's U=10 it is 45 min/max pairs, at U=1024 it
would be ~524k — a multi-minute trace for a worse schedule than a real
sort.  Above the cap, `sort_columns_bitonic` is the large-U successor: the
classic bitonic network expressed as O(log^2 U) whole-block stages, each
stage one roll + select + min/max over the [U_pad, TILE] block (U padded to
the next power of two with +inf, which ascending-sorts to the bottom rows
and is sliced away).  The stage count is static and tiny (log2(4096)^2 =
144), so the trace stays small while the data movement stays one VMEM pass
per tile.  Routing between the two (and the `jnp.sort` oracle) lives in
`core.defenses.sorted_columns`.

Shape contract and tiling mirror `floa_aggregate`:

  sort_columns          [U, D] -> [U, D]  ascending along axis 0 (U <= 32)
  sort_columns_bitonic  [U, D] -> [U, D]  ascending along axis 0
                                          (U padded to a power of two,
                                           U_pad <= BITONIC_MAX_U)

Grid is (D // TILE); the [U(_pad), TILE] block lives in VMEM (unrolled:
U<=32 x TILE_D=2048 f32 = 256 KiB per block).  The bitonic kernel's scoped
VMEM, as the v5e compiler reserves it, is about 16 blocks: the input and
output blocks double-buffered plus the stage body's temporaries (compiles
for v5e measured 9 blocks at [1024, 512] and 15.75 at [4096, 128] and
[8192, 128]).  `bitonic_tile_d` narrows the tile as U_pad grows so those
blocks stay within 16 MiB, and `bitonic_vmem_limit` asks for the blocks
plus a quarter of headroom, never less than the 16 MiB default.  Past the
128-lane floor (U_pad >= 4096) the tile cannot narrow and the limit grows
with U_pad, which caps U_pad at BITONIC_MAX_U=8192 (an 80 MiB limit of
v5e's 128 MiB VMEM; the next power of two would need 160 MiB).  The
unrolled network's blocks stay far below the default and it sets no
limit.  D is
padded to the tile once, in the
un-jitted public wrappers, before the jitted pallas_call core (columns sort
independently, so zero-padded columns cannot perturb real ones; see the
D-padding recursion note in floa_aggregate.py).
The sweep engine's defense kernels call this per lane under `jax.vmap`
(grouped dispatch vmaps one family over its lane group); Pallas's batching
rule lifts the vmap into a leading grid dimension, so there is no separate
hand-written [S, U, D] kernel to keep in lockstep — the vmap route is
pinned against the batched `jnp.sort` oracle in tests/test_defense_sort.py.

The network uses `jnp.minimum`/`jnp.maximum` compare-exchanges: on finite
inputs it agrees with the `jnp.sort` oracle exactly (ties keep values, not
worker identity — coordinate-wise reductions never look at identity).  NaN
ordering is NOT the oracle's (sort places NaNs last; min/max propagate them
everywhere) — gradient slabs are finite, and the oracle contract in
tests/test_defense_sort.py is pinned on finite values only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

TILE_D = 2048
# Largest U the fully-unrolled odd-even network may trace (O(U^2) min/max
# pairs); larger slabs route to the bitonic kernel or the jnp.sort oracle.
UNROLL_MAX_U = 32
# Largest padded U the bitonic kernel accepts: at the 128-lane minimum tile
# an [8192, 128] f32 block is 4 MiB and the kernel holds about 16 of them
# (see the module docstring) — twice that U would not fit v5e's VMEM.
BITONIC_MAX_U = 8192
# v5e's default scoped-VMEM limit, and the [u_pad, tile] f32 blocks the
# bitonic kernel holds in it per grid step (measured, see module docstring).
_SCOPED_VMEM = 16 << 20
_BITONIC_LIVE_BLOCKS = 16


def _pad_last(x: Array, pad: int) -> Array:
    """Zero-pad the last axis by `pad` entries (no-op when pad == 0)."""
    if not pad:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


def _odd_even_sort(x: Array) -> Array:
    """Odd-even transposition network over axis 0 of a [U, T] block.

    U passes of adjacent compare-exchanges (even pairs, then odd pairs,
    alternating) sort any input of length U — the classic transposition-sort
    bound.  U is static, so the whole network unrolls at trace time into
    O(U^2 / 2) vectorized min/max pairs on [1, T] rows; there is no data-
    dependent control flow, which is exactly what the VPU wants.
    """
    u = x.shape[0]
    rows = [x[i:i + 1] for i in range(u)]  # [1, T] each (2-D for Mosaic)
    for p in range(u):
        for i in range(p % 2, u - 1, 2):
            lo = jnp.minimum(rows[i], rows[i + 1])
            hi = jnp.maximum(rows[i], rows[i + 1])
            rows[i], rows[i + 1] = lo, hi
    return jnp.concatenate(rows, axis=0) if u > 1 else rows[0]


def _kernel(x_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)                # [U, TILE_D]
    o_ref[:] = _odd_even_sort(x).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def _sort_columns_core(x: Array, interpret: bool, tile_d: int) -> Array:
    u, d = x.shape
    assert d % tile_d == 0, "core requires pre-padded D (see public wrapper)"
    return pl.pallas_call(
        _kernel,
        grid=(d // tile_d,),
        in_specs=[pl.BlockSpec((u, tile_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((u, tile_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((u, d), x.dtype),
        name="sort_columns",
        interpret=interpret,
    )(x)


def sort_columns(x: Array, interpret: bool = False,
                 tile_d: int = TILE_D) -> Array:
    """[U, D] -> [U, D], ascending along the worker axis (axis 0).

    U is bounded by UNROLL_MAX_U — the network fully unrolls at trace time,
    so an unbounded U is an O(U^2) trace-size bomb.  Large-U slabs belong to
    `sort_columns_bitonic` (the `core.defenses.sorted_columns` router picks
    for you)."""
    u, d = x.shape
    if u > UNROLL_MAX_U:
        raise ValueError(
            f"sort_columns unrolls an O(U^2) network: U={u} exceeds the "
            f"U<={UNROLL_MAX_U} bound — use sort_columns_bitonic (or the "
            f"jnp.sort oracle) for large worker populations")
    pad = -d % tile_d  # single pad before the jitted core
    out = _sort_columns_core(_pad_last(x, pad), interpret=interpret,
                             tile_d=tile_d)
    return out[:, :d] if pad else out


# ---------------------------------------------------- large-U bitonic stages


def bitonic_tile_d(u_pad: int) -> int:
    """Widest D tile whose live [u_pad, tile] f32 blocks fit the default
    scoped VMEM, floored at the 128-lane minimum tile."""
    fit = _SCOPED_VMEM // (_BITONIC_LIVE_BLOCKS * 4 * u_pad)
    return max(128, min(TILE_D, fit))


def bitonic_vmem_limit(u_pad: int, tile_d: int) -> int:
    """Scoped-VMEM limit for one bitonic grid step: the live blocks plus a
    quarter for headroom, never below the default."""
    need = _BITONIC_LIVE_BLOCKS * 4 * u_pad * tile_d
    return max(_SCOPED_VMEM, need + need // 4)


def _bitonic_stages(x: Array) -> Array:
    """Bitonic sorting network over axis 0 of an [N, T] block, N a power of
    two; ascending.

    The pairwise compare-exchange with partner ``l = i ^ j`` is vectorized
    as whole-block rolls: rows with ``i & j == 0`` pair downward (partner at
    i + j, i.e. roll(-j)), the rest pair upward (roll(+j)); the merge
    direction flips with ``i & k``.  Each of the log2(N)*(log2(N)+1)/2
    stages is one roll + two selects + min/max over the block — no
    data-dependent control flow, no per-row slicing, so the trace is
    O(log^2 N) whole-block ops instead of the unrolled network's O(N^2)
    pairs.

    Same tie/NaN semantics as the odd-even network (min/max compare-
    exchanges): exact `jnp.sort` agreement on finite inputs, finite-only
    contract (see the module docstring).
    """
    n = x.shape[0]
    assert n & (n - 1) == 0, f"bitonic stages need a power-of-two N, got {n}"
    if n == 1:
        return x
    i = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            is_first = (i & j) == 0            # partner sits at i + j
            partner = jnp.where(is_first, jnp.roll(x, -j, axis=0),
                                jnp.roll(x, j, axis=0))
            asc = (i & k) == 0                 # merge direction of this block
            keep_lo = is_first == asc
            x = jnp.where(keep_lo, jnp.minimum(x, partner),
                          jnp.maximum(x, partner))
            j //= 2
        k *= 2
    return x


def _bitonic_kernel(x_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)                # [U_pad, tile]
    o_ref[:] = _bitonic_stages(x).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def _sort_columns_bitonic_core(x: Array, interpret: bool,
                               tile_d: int) -> Array:
    u, d = x.shape
    assert d % tile_d == 0, "core requires pre-padded D (see public wrapper)"
    return pl.pallas_call(
        _bitonic_kernel,
        grid=(d // tile_d,),
        in_specs=[pl.BlockSpec((u, tile_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((u, tile_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((u, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=bitonic_vmem_limit(u, tile_d)),
        name="sort_columns_bitonic",
        interpret=interpret,
    )(x)


def sort_columns_bitonic(x: Array, interpret: bool = False,
                         tile_d: int = 0) -> Array:
    """[U, D] -> [U, D], ascending along the worker axis — the large-U
    successor to `sort_columns`.

    U is padded to the next power of two with +inf rows (they ascending-sort
    to the bottom and are sliced away), D to the tile; both pads happen once
    here, outside the jitted core.  tile_d=0 picks the VMEM-fitting width
    via `bitonic_tile_d`."""
    u, d = x.shape
    u_pad = 1 << max(u - 1, 0).bit_length()         # next power of two
    if u_pad > BITONIC_MAX_U:
        raise ValueError(
            f"sort_columns_bitonic: padded U={u_pad} exceeds "
            f"BITONIC_MAX_U={BITONIC_MAX_U} (its [U_pad, 128] blocks no "
            f"longer fit VMEM) — use the jnp.sort oracle")
    tile_d = tile_d or bitonic_tile_d(u_pad)
    dpad = -d % tile_d
    xp = _pad_last(x, dpad)
    if u_pad > u:
        fill = jnp.full((u_pad - u, xp.shape[1]), jnp.inf, xp.dtype)
        xp = jnp.concatenate([xp, fill], axis=0)
    out = _sort_columns_bitonic_core(xp, interpret=interpret, tile_d=tile_d)
    return out[:u, :d]
