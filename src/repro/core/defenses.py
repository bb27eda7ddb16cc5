"""Digital-FL Byzantine-robust aggregation baselines (paper §I related work).

The paper's motivation: screening defenses (median/Krum/...) need *individual*
local gradients, which analog aggregation hides — so they cannot be applied to
FLOA.  We implement them anyway for the *digital* comparison mode (per-worker
gradients explicitly gathered), so experiments can quantify the robustness /
communication-cost trade-off the paper argues about:

  coordinate-wise median           [Yin et al. 2018]
  coordinate-wise trimmed mean     [Yin et al. 2018]
  Krum / Multi-Krum                [Blanchard et al. 2017]
  geometric median (Weiszfeld)     [Minsker 2015 / RFA]

The matrix-native `flat_*` kernels are the single implementation: they map one
[U, D] per-worker gradient slab to a [D] aggregate, take their hyper-params
(trim, f, multi) as TRACED scalars so one trace serves every lane of a sweep
(masked sorted-prefix reductions instead of Python slicing), and are what the
sweep engine's defense-code lane axis dispatches over (`DEFENSE_CODES` in
core/scenario.py, `make_flat_defense_selector` below).  Hyper-param bounds are
validated in the config layer (`scenario.DefenseSpec.validate`) because
`assert`s on traced values vanish under jit; the kernels only re-check
concrete Python ints.

The pytree API (`digital_aggregate` and the named wrappers) flattens to the
slab, runs the flat kernel, and unravels — the legacy entry point the digital
`FLTrainer` uses.

NOTE: in digital mode the [U, ...] stack must be gathered (an all-gather over
"data" instead of FLOA's all-reduce) — exactly the communication overhead the
paper's analog scheme avoids; the roofline benchmarks expose the difference.
"""
from __future__ import annotations

import functools
import logging
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scenario import DEFENSE_CODES

Array = jax.Array

logger = logging.getLogger(__name__)

# Defense families by data layout.  Column-wise defenses reduce each of the
# D coordinates independently over the worker axis, so under a ("model",)-
# sharded sweep they run unchanged on each shard's local column block; the
# row-geometry defenses (Krum / multi-Krum / geometric median) score whole
# [D]-rows by pairwise distance and need the full rows gathered first
# (fl/sweep.py routes on this split).
COLUMNWISE_CODES = frozenset(
    DEFENSE_CODES[n] for n in ("mean", "median", "trimmed_mean"))
ROW_GEOMETRY_CODES = frozenset(
    DEFENSE_CODES[n] for n in ("krum", "multi_krum", "geometric_median"))


def _flatten_u(grads_u):
    """[U, ...] pytree -> ([U, D] matrix, unravel fn)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads_u)
    u = leaves[0].shape[0]
    flat = jnp.concatenate([x.reshape(u, -1).astype(jnp.float32) for x in leaves], axis=1)

    def unravel(vec):
        out, off = [], 0
        for x in leaves:
            n = int(x.size) // u
            out.append(vec[off : off + n].reshape(x.shape[1:]).astype(x.dtype))
            off += n
        return jax.tree_util.tree_unflatten(treedef, out)

    return flat, unravel


# --------------------------------------------------------- flat [U, D] kernels

# Below this flat size (or off the TPU, where Mosaic kernels cannot run)
# jnp.sort's generic lowering is fine; above it the sorting-network kernels
# (kernels/defense_sort.py) sort the [U, TILE] block in one VMEM pass.
SORT_KERNEL_MIN_D = 1 << 14
# Worker-axis routing: up to this U the fully-unrolled odd-even network is
# the kernel (O(U^2) min/max pairs is cheap when U is tiny); above it the
# unrolled trace explodes quadratically, so large-U slabs take the bitonic
# stage kernel (O(log^2 U) whole-block ops, U padded to a power of two) up
# to its own VMEM ceiling, and the jnp.sort oracle beyond that.
SORT_UNROLL_MAX_U = 32


def sorted_columns(flat: Array, use_kernel: Optional[bool] = None,
                   interpret: bool = False) -> Array:
    """Ascending per-coordinate sort over the worker axis — the screening
    primitive coordinate-median and trimmed-mean share.  Routed to a Pallas
    sorting-network kernel on TPU at large D (same routing contract as
    `core.aggregation.batched_floa_combine`), `jnp.sort` elsewhere.

    The worker axis picks the kernel: U <= SORT_UNROLL_MAX_U takes the
    unrolled odd-even network, larger U the bitonic stage kernel (while its
    padded U fits VMEM).  The guard is unconditional — even with
    use_kernel=True a large-U slab is NEVER routed into the unrolled
    network, whose O(U^2) trace at U >= 1k would dwarf the sort itself;
    above BITONIC_MAX_U (padded) no VMEM-resident column block exists
    either, so the router falls back to `jnp.sort` explicitly and logs
    once (it used to fall through silently — ROADMAP bug)."""
    u = flat.shape[0]
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and flat.shape[-1] >= SORT_KERNEL_MIN_D)
    if use_kernel:
        from repro.kernels import ops
        if u <= SORT_UNROLL_MAX_U:
            return ops.sort_columns(flat, interpret=interpret)
        u_pad = 1 << max(u - 1, 0).bit_length()
        if u_pad <= ops.BITONIC_MAX_U:
            return ops.sort_columns_bitonic(flat, interpret=interpret)
        _log_sort_fallback_once(u, ops.BITONIC_MAX_U)
    return jnp.sort(flat, axis=0)


_sort_fallback_logged = False


def _log_sort_fallback_once(u: int, bitonic_max_u: int) -> None:
    """Explicit large-U fallback notice, emitted once per process: a kernel
    was requested (use_kernel resolved True) but U padded to a power of two
    exceeds the bitonic kernel's VMEM ceiling, so the sort takes `jnp.sort`'s
    generic lowering instead — correct, just not the Pallas path the caller
    asked for.  Logged (not warned): the test suite promotes warnings to
    errors, and this is routing telemetry, not a correctness hazard."""
    global _sort_fallback_logged
    if not _sort_fallback_logged:
        _sort_fallback_logged = True
        logger.warning(
            "sorted_columns: U=%d pads past BITONIC_MAX_U=%d — no "
            "VMEM-resident sorting-network kernel exists at this U, falling "
            "back to jnp.sort (XLA generic sort). Logged once per process.",
            u, bitonic_max_u)


def flat_mean(flat: Array) -> Array:
    return jnp.mean(flat, axis=0)


def flat_median(flat: Array) -> Array:
    # (srt[(u-1)//2] + srt[u//2]) / 2 == jnp.median: the middle element for
    # odd U ((x + x) / 2 is exact), the two-middle average for even U.
    u = flat.shape[0]
    srt = sorted_columns(flat)
    return (srt[(u - 1) // 2] + srt[u // 2]) / 2


def flat_trimmed_mean(flat: Array, trim) -> Array:
    """Drop the `trim` largest and smallest per coordinate, then mean.

    trim may be a traced int32 scalar (sweep lanes): the sorted column is
    reduced under an index mask instead of a Python slice, so the same trace
    serves every lane.  Concrete ints are range-checked here; traced values
    are the config layer's job (`DefenseSpec.validate`).
    """
    u = flat.shape[0]
    if isinstance(trim, (int, np.integer)) and not 0 <= 2 * int(trim) < u:
        raise ValueError(
            f"trimmed_mean trim={trim} invalid for U={u}: need 0 <= 2*trim < U")
    srt = sorted_columns(flat)
    idx = jnp.arange(u)
    keep = (idx >= trim) & (idx < u - trim)
    kept = jnp.sum(jnp.where(keep[:, None], srt, 0.0), axis=0)
    return kept / (u - 2 * trim)


def _krum_scores(flat: Array, num_byzantine) -> Array:
    """score_i = sum of the max(U-f-2, 1) smallest sq-distances to others.

    Exposed for the property-test suite (permutation equivariance of the
    scores is checkable even when near-ties make the selection itself
    fp-fragile).

    The broadcast difference materializes a [U, U, D] intermediate before
    XLA fuses — fine at the paper's U=10, catastrophic at U >= 1k (17 TB at
    U=4096, D=256) — so this is the SMALL-U path only; `flat_krum` routes
    U >= KRUM_BLOCK_MIN_U to `_krum_scores_blocked`.
    """
    u = flat.shape[0]
    closest = jnp.maximum(u - num_byzantine - 2, 1)
    d2 = jnp.sum((flat[:, None, :] - flat[None, :, :]) ** 2, axis=-1)  # [U,U]
    # Exclude self via a boolean mask: the seed's `d2 + eye * inf` poisoned
    # every OFF-diagonal entry with 0*inf = NaN, collapsing all scores to NaN
    # (and Krum to "always pick worker 0").  Pinned by the property suite.
    d2 = jnp.where(jnp.eye(u, dtype=bool), jnp.inf, d2)
    srt = jnp.sort(d2, axis=1)  # self-distance inf lands in the final column
    # closest <= U-2, so the masked prefix never touches the inf column.
    j = jnp.arange(u)
    return jnp.sum(jnp.where(j[None, :] < closest, srt, 0.0), axis=1)


# Above this U, Krum switches to the row-blocked distance path: the full
# [U, U] matrix (let alone the [U, U, D] broadcast intermediate) never
# materializes at once — only one [KRUM_BLOCK_ROWS, U] block at a time.
KRUM_BLOCK_MIN_U = 64
KRUM_BLOCK_ROWS = 128


def _krum_scores_blocked(flat: Array, num_byzantine,
                         block_rows: int = KRUM_BLOCK_ROWS) -> Array:
    """`_krum_scores` for large U, one [B, U] distance block at a time.

    Per block of B rows: d2 = |x_b|^2 + |x|^2 - 2 x_b x^T via a [B, D] x
    [D, U] matmul (clamped at 0 — the expanded form can go slightly
    negative in fp), self-distances masked to +inf by global row id, each
    row sorted and masked-prefix-reduced exactly like the small-U path.
    `lax.map` sequences the blocks, so peak memory is O(B*U + U*D), never
    O(U^2).  The expanded distance form differs from the direct (x-y)^2 sum
    at fp rounding level, so blocked vs small-U scores agree to rtol, not
    bitwise — the oracle-contract tests pin it.
    """
    u, d = flat.shape
    closest = jnp.maximum(u - num_byzantine - 2, 1)
    nb = -(-u // block_rows)
    pad = nb * block_rows - u
    fpad = jnp.pad(flat, ((0, pad), (0, 0)))
    sq = jnp.sum(jnp.square(flat), axis=1)                   # [U]
    sq_pad = jnp.pad(sq, (0, pad))
    blocks = fpad.reshape(nb, block_rows, d)
    sq_blocks = sq_pad.reshape(nb, block_rows)
    ids = jnp.arange(nb * block_rows).reshape(nb, block_rows)
    j = jnp.arange(u)

    def score_block(args):
        xb, sb, rb = args
        d2 = sb[:, None] + sq[None, :] - 2.0 * (xb @ flat.T)  # [B, U]
        d2 = jnp.maximum(d2, 0.0)
        d2 = jnp.where(rb[:, None] == j[None, :], jnp.inf, d2)
        srt = jnp.sort(d2, axis=1)
        return jnp.sum(jnp.where(j[None, :] < closest, srt, 0.0), axis=1)

    scores = jax.lax.map(score_block, (blocks, sq_blocks, ids))  # [nb, B]
    return scores.reshape(-1)[:u]


def flat_krum(flat: Array, num_byzantine, multi=1) -> Array:
    """(Multi-)Krum: average the `multi` lowest-scoring workers' gradients.
    num_byzantine and multi may be traced scalars (masked rank selection).
    Large worker populations take the blocked distance path (the [U, U]
    matrix never materializes at once)."""
    u = flat.shape[0]
    scores = (_krum_scores_blocked(flat, num_byzantine)
              if u >= KRUM_BLOCK_MIN_U
              else _krum_scores(flat, num_byzantine))
    ranked = flat[jnp.argsort(scores)]                 # [U, D], best first
    keep = jnp.arange(u) < multi
    sel = jnp.sum(jnp.where(keep[:, None], ranked, 0.0), axis=0)
    return sel / jnp.asarray(multi, flat.dtype)


def flat_geometric_median(flat: Array, iters: int = 8,
                          eps: float = 1e-8) -> Array:
    """Weiszfeld iterations for the geometric median (iters is static — a
    lax.scan length)."""

    def body(z, _):
        w = 1.0 / jnp.maximum(jnp.linalg.norm(flat - z, axis=1), eps)  # [U]
        z = jnp.sum(w[:, None] * flat, axis=0) / jnp.sum(w)
        return z, None

    z0 = jnp.mean(flat, axis=0)
    z, _ = jax.lax.scan(body, z0, None, length=iters)
    return z


# ------------------------------------- masked (partial-participation) kernels
#
# K-of-U client sampling (fl/sweep.py): non-participating workers never report
# a gradient, so every screening defense must run on the participating rows
# only.  Each masked kernel reduces to its unmasked twin at a full mask (the
# full-participation lanes of a mixed sweep): selects with an all-True mask
# are identity, counts equal the static U, and means are rescaled by
# exactly-1.0 (mean * (U/count)) instead of re-divided — a sum/traced-count
# spelling would round differently from jnp.mean under jit (XLA
# strength-reduces the divide-by-constant into a reciprocal multiply).
# Inside a whole fused sweep XLA may still order a masked reduction
# differently, so full lanes of a mixed grid are pinned to a few ulp, not
# bitwise (tests/test_scenario_axes.py).


def flat_masked_mean(flat: Array, mask: Array) -> Array:
    """Mean of the participating rows (== flat_mean at a full mask)."""
    u = flat.shape[0]
    scale = u / jnp.sum(mask.astype(flat.dtype))
    return jnp.mean(jnp.where(mask[:, None], flat, 0.0), axis=0) * scale


def flat_masked_median(flat: Array, mask: Array) -> Array:
    """Coordinate median over the participating rows: non-participants are
    +inf-padded so the sort pushes them past the end, and the two middle
    indices come from the traced participant count."""
    srt = sorted_columns(jnp.where(mask[:, None], flat, jnp.inf))
    cnt = jnp.sum(mask.astype(jnp.int32))
    return (srt[(cnt - 1) // 2] + srt[cnt // 2]) / 2


def flat_masked_trimmed_mean(flat: Array, trim, mask: Array) -> Array:
    """Trimmed mean over the participating rows: drop the `trim` largest and
    smallest PARTICIPATING values per coordinate, mean the rest."""
    u = flat.shape[0]
    srt = sorted_columns(jnp.where(mask[:, None], flat, jnp.inf))
    cnt = jnp.sum(mask.astype(jnp.int32))
    idx = jnp.arange(u)
    keep = (idx >= trim) & (idx < cnt - trim)
    kept = jnp.sum(jnp.where(keep[:, None], srt, 0.0), axis=0)
    return kept / (cnt - 2 * trim)


def _masked_krum_scores(flat: Array, num_byzantine, mask: Array) -> Array:
    """`_krum_scores` over the participating rows: distances to (or from) a
    non-participant are +inf, the closest-count comes from the participant
    count, and non-participant scores are +inf so ranking never picks them."""
    u = flat.shape[0]
    cnt = jnp.sum(mask.astype(jnp.int32))
    closest = jnp.maximum(cnt - num_byzantine - 2, 1)
    d2 = jnp.sum((flat[:, None, :] - flat[None, :, :]) ** 2, axis=-1)
    pair_ok = mask[:, None] & mask[None, :] & ~jnp.eye(u, dtype=bool)
    d2 = jnp.where(pair_ok, d2, jnp.inf)
    srt = jnp.sort(d2, axis=1)
    j = jnp.arange(u)
    scores = jnp.sum(jnp.where(j[None, :] < closest, srt, 0.0), axis=1)
    return jnp.where(mask, scores, jnp.inf)


def _masked_krum_scores_blocked(flat: Array, num_byzantine, mask: Array,
                                block_rows: int = KRUM_BLOCK_ROWS) -> Array:
    """`_krum_scores_blocked` with the participation mask applied per block
    (columns of non-participants +inf before the row sort, rows of
    non-participants +inf after)."""
    u, d = flat.shape
    cnt = jnp.sum(mask.astype(jnp.int32))
    closest = jnp.maximum(cnt - num_byzantine - 2, 1)
    nb = -(-u // block_rows)
    pad = nb * block_rows - u
    fpad = jnp.pad(flat, ((0, pad), (0, 0)))
    sq = jnp.sum(jnp.square(flat), axis=1)
    sq_pad = jnp.pad(sq, (0, pad))
    blocks = fpad.reshape(nb, block_rows, d)
    sq_blocks = sq_pad.reshape(nb, block_rows)
    ids = jnp.arange(nb * block_rows).reshape(nb, block_rows)
    j = jnp.arange(u)

    def score_block(args):
        xb, sb, rb = args
        d2 = sb[:, None] + sq[None, :] - 2.0 * (xb @ flat.T)
        d2 = jnp.maximum(d2, 0.0)
        d2 = jnp.where(rb[:, None] == j[None, :], jnp.inf, d2)
        d2 = jnp.where(mask[None, :], d2, jnp.inf)
        srt = jnp.sort(d2, axis=1)
        return jnp.sum(jnp.where(j[None, :] < closest, srt, 0.0), axis=1)

    scores = jax.lax.map(score_block, (blocks, sq_blocks, ids)).reshape(-1)[:u]
    return jnp.where(mask, scores, jnp.inf)


def flat_masked_krum(flat: Array, num_byzantine, multi, mask: Array) -> Array:
    """(Multi-)Krum over the participating rows (same large-U routing as
    `flat_krum`; non-participants score +inf, so `multi <= K` — enforced by
    the sweep spec validation — keeps them out of the averaged prefix)."""
    u = flat.shape[0]
    scores = (_masked_krum_scores_blocked(flat, num_byzantine, mask)
              if u >= KRUM_BLOCK_MIN_U
              else _masked_krum_scores(flat, num_byzantine, mask))
    ranked = flat[jnp.argsort(scores)]
    keep = jnp.arange(u) < multi
    sel = jnp.sum(jnp.where(keep[:, None], ranked, 0.0), axis=0)
    return sel / jnp.asarray(multi, flat.dtype)


def flat_masked_geometric_median(flat: Array, mask: Array, iters: int = 8,
                                 eps: float = 1e-8) -> Array:
    """Weiszfeld over the participating rows: non-participants get zero
    weight and the iteration starts from the participants' mean."""
    u = flat.shape[0]
    scale = u / jnp.sum(mask.astype(flat.dtype))

    def body(z, _):
        w = jnp.where(
            mask, 1.0 / jnp.maximum(jnp.linalg.norm(flat - z, axis=1), eps),
            0.0)
        z = jnp.sum(w[:, None] * flat, axis=0) / jnp.sum(w)
        return z, None

    z0 = jnp.mean(jnp.where(mask[:, None], flat, 0.0), axis=0) * scale
    z, _ = jax.lax.scan(body, z0, None, length=iters)
    return z


# ------------------------------------------------ branchless lane dispatch

# code -> flat kernel taking the uniform operand tuple (flat, trim, f, multi).
# Code 0 (analog FLOA) falls back to the mean: the sweep engine discards that
# branch's output for analog lanes (they take the OTA combine), but under a
# vmapped lax.switch every branch must still produce a [D] row.
_FLAT_KERNELS_BY_CODE: Dict[int, Callable] = {
    DEFENSE_CODES["floa"]: lambda op, it: flat_mean(op[0]),
    DEFENSE_CODES["mean"]: lambda op, it: flat_mean(op[0]),
    DEFENSE_CODES["median"]: lambda op, it: flat_median(op[0]),
    DEFENSE_CODES["trimmed_mean"]: lambda op, it: flat_trimmed_mean(op[0], op[1]),
    DEFENSE_CODES["krum"]: lambda op, it: flat_krum(op[0], op[2], op[3]),
    DEFENSE_CODES["multi_krum"]: lambda op, it: flat_krum(op[0], op[2], op[3]),
    DEFENSE_CODES["geometric_median"]:
        lambda op, it: flat_geometric_median(op[0], iters=it),
}

# Masked twins for K-of-U partial participation: uniform operand tuple
# (flat, trim, f, multi, mask).  The sweep engine selects this table at
# BUILD time only when the sweep contains participation lanes, so
# full-participation sweeps trace zero masking ops.
_MASKED_FLAT_KERNELS_BY_CODE: Dict[int, Callable] = {
    DEFENSE_CODES["floa"]: lambda op, it: flat_masked_mean(op[0], op[4]),
    DEFENSE_CODES["mean"]: lambda op, it: flat_masked_mean(op[0], op[4]),
    DEFENSE_CODES["median"]: lambda op, it: flat_masked_median(op[0], op[4]),
    DEFENSE_CODES["trimmed_mean"]:
        lambda op, it: flat_masked_trimmed_mean(op[0], op[1], op[4]),
    DEFENSE_CODES["krum"]:
        lambda op, it: flat_masked_krum(op[0], op[2], op[3], op[4]),
    DEFENSE_CODES["multi_krum"]:
        lambda op, it: flat_masked_krum(op[0], op[2], op[3], op[4]),
    DEFENSE_CODES["geometric_median"]:
        lambda op, it: flat_masked_geometric_median(op[0], op[4], iters=it),
}


def make_flat_defense_selector(codes: Optional[Sequence[int]] = None,
                               gm_iters: int = 8,
                               masked: bool = False) -> Callable:
    """Branchless defense dispatch for one lane: a `lax.switch` over the
    defense codes present in a sweep.

    Returns fn(code, flat, trim, num_byzantine, multi) -> [D], taking a
    trailing [U] bool participation-mask operand when masked=True.  Under
    `vmap` (code varying across lanes) the switch lowers to computing every
    listed branch and selecting per lane — which is why `codes` should be
    the codes a sweep actually contains (the default is all of
    DEFENSE_CODES): absent defenses then cost nothing.  Codes outside the
    list (e.g. analog lanes' 0 in a digital-only list) are remapped to the
    first branch; the caller overrides those lanes' output anyway.
    """
    if codes is None:
        codes = sorted(DEFENSE_CODES.values())
    codes = sorted({int(c) for c in codes})
    assert codes, "empty defense-code set"
    lookup = np.zeros(max(DEFENSE_CODES.values()) + 1, np.int32)
    for i, c in enumerate(codes):
        lookup[c] = i
    lookup_j = jnp.asarray(lookup)
    table = _MASKED_FLAT_KERNELS_BY_CODE if masked else _FLAT_KERNELS_BY_CODE
    branches = [functools.partial(table[c], it=gm_iters) for c in codes]

    if masked:
        def select(code, flat, trim, num_byzantine, multi, mask):
            return jax.lax.switch(lookup_j[code], branches,
                                  (flat, trim, num_byzantine, multi, mask))
    else:
        def select(code, flat, trim, num_byzantine, multi):
            return jax.lax.switch(lookup_j[code], branches,
                                  (flat, trim, num_byzantine, multi))

    return select


def make_group_defense_kernel(code: int, gm_iters: int = 8,
                              masked: bool = False) -> Callable:
    """Static single-family dispatch for a grouped lane partition
    (`scenario.build_lane_groups`): `code` is a concrete Python int, so the
    returned fn(flat [S_g, U, D], trim, f, multi each [S_g]) -> [S_g, D] is
    ONE family's kernel vmapped over its contiguous group — no `lax.switch`,
    no other family traced.  Per-lane math is identical to the switch
    selector's branch for `code` (same kernel-table entry), which is what
    makes grouped == switch dispatch exact.  masked=True appends a [S_g, U]
    bool participation-mask argument (same table as the masked selector)."""
    if masked:
        mfn = functools.partial(_MASKED_FLAT_KERNELS_BY_CODE[int(code)],
                                it=gm_iters)

        def apply_masked(flat, trim, num_byzantine, multi, mask):
            return jax.vmap(lambda f, t, nb, m, pk: mfn((f, t, nb, m, pk)))(
                flat, trim, num_byzantine, multi, mask)

        return apply_masked

    fn = functools.partial(_FLAT_KERNELS_BY_CODE[int(code)], it=gm_iters)

    def apply(flat, trim, num_byzantine, multi):
        return jax.vmap(lambda f, t, nb, m: fn((f, t, nb, m)))(
            flat, trim, num_byzantine, multi)

    return apply


# ----------------------------------------------------------- pytree wrappers


def coordinate_median(grads_u):
    flat, unravel = _flatten_u(grads_u)
    return unravel(flat_median(flat))


def trimmed_mean(grads_u, trim: int = 1):
    """Remove the `trim` largest and smallest per coordinate, then mean."""
    flat, unravel = _flatten_u(grads_u)
    return unravel(flat_trimmed_mean(flat, trim))


def krum(grads_u, num_byzantine: int, multi: int = 1):
    """(Multi-)Krum: score_i = sum of the U-f-2 smallest sq-distances to others;
    average the `multi` lowest-scoring workers' gradients."""
    flat, unravel = _flatten_u(grads_u)
    return unravel(flat_krum(flat, num_byzantine, multi))


def geometric_median(grads_u, iters: int = 8, eps: float = 1e-8):
    """Weiszfeld iterations for the geometric median."""
    flat, unravel = _flatten_u(grads_u)
    return unravel(flat_geometric_median(flat, iters=iters, eps=eps))


DEFENSES: Dict[str, Callable] = {
    "median": coordinate_median,
    "trimmed_mean": trimmed_mean,
    "krum": krum,
    "geometric_median": geometric_median,
    "mean": lambda g: jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), g),
}


def digital_aggregate(grads_u, defense: str = "mean", **kw):
    """Gather-based digital aggregation with a named defense."""
    fn = DEFENSES[defense]
    return fn(grads_u, **kw) if kw else fn(grads_u)
