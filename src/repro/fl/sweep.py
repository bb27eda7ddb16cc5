"""Compiled multi-scenario sweep engine: S scenarios x R rounds, one XLA program.

The paper's experimental section (Figs. 1-4) is a grid of scenarios — attack
type x attacker count x power policy x seed — that the looped `FLTrainer.run`
simulates one round-dispatch at a time.  This engine removes both axes of
Python overhead:

  rounds     -> a `jax.lax.scan` body (no per-round dispatch or host sync);
  scenarios  -> a vmapped stacked-`ScenarioParams` axis (one trace, S lanes),
                built by `SweepSpec` from ordinary frozen `FLOAConfig`s.

The lane axis also carries a **defense code** (core.scenario.DEFENSE_CODES):
code 0 lanes take the analog FLOA combine, any other code applies a digital
screening defense (median / trimmed-mean / (multi-)Krum / geometric median)
to the same [S, U, D] per-worker gradient slab — so the full
policy x defense x attack x attacker-count showdown grid is ONE compiled
program, and pure-FLOA sweeps trace no defense kernels at all.  Dispatch is
**grouped** by default: defense codes are concrete config, so the engine
statically partitions the lanes by code (`scenario.build_lane_groups`),
runs each family's kernel once over its contiguous sub-slab, and scatters
results back to lane order — a mixed grid pays only for the families it
contains.  `grouped_dispatch=False` keeps the PR-3 per-lane vmapped
`lax.switch` (which computes every family present for every lane) as the
equivalence reference.  Digital lanes
model Byzantine workers as sign-flipped reported gradients (FLTrainer
mode="digital" semantics) and ignore the channel; their per-worker slab is
the gathered all-gather payload the paper's analog scheme avoids.

The warm path operates on **flat state end-to-end**: parameters are flattened
once to a [S, D] matrix before the scan and stay flat across all rounds.  The
pytree boundary is crossed only inside the loss/grad closure (via a cached
row-unflatten built from one `jax.eval_shape` of the init) and once at the end
of the run — per-worker gradients come off the grad transpose already as one
[S, U, D] block, so the per-round flatten/concat and per-leaf unflatten/update
of the tree-state engine disappear.  The OTA superposition +
de-standardization bias + receiver noise + PS update fuse into one
`batched_floa_step` call (fused batched Pallas kernel on TPU, einsum oracle
elsewhere).  `flat_state=False` keeps the PR-1 tree-state path as the
equivalence reference.

The lane axis is embarrassingly parallel, so it shards: pass `mesh=` (a 1-D
("data",) mesh, e.g. `launch.mesh.make_sweep_mesh()`) and the flat-state scan
is `shard_map`ped over the devices — S is padded to a multiple of the device
count with ghost lanes (replicas of the last scenario) that are dropped from
the results; every real lane's trajectory is unchanged.

The round axis splits too: `chunk_rounds=C` turns the one R-round scan into a
**scan of chunks** — an outer (uncompiled) Python loop over ceil(R/C) chunks
whose inner C-round scan body is the untouched monolithic body, with the
(state, keys, absolute-round-offset) carry threaded through the chunk
boundaries.  Trajectories are unchanged (bit-identical under
`strict_numerics`): the chunk boundary exists for the *input pipeline*, not
the math.  `async_staging=True` double-buffers it — while chunk k executes,
chunk k+1's batch block is sliced host-side (`data.iter_chunk_blocks`, numpy
views) and transferred with an async `jax.device_put`
(`launch.mesh.stage_batch_block`, pre-sharded replicated under a mesh), so
the device never idles waiting on host->device input transfers and the full
[R, ...] batch stack never has to live in device memory.

    spec   = SweepSpec.build([(name, floa_cfg, alpha, seed), ...])
    engine = SweepEngine(loss_fn, spec, eval_fn=...)
    result = engine.run(params0, batches)     # batches: [R, ...] leaves
    result.loss            # [S, R]
    result.metrics["acc"]  # [S, R]

All scenarios share the model init, the per-round batches (the paper's
figures reuse one dataset/sampler across setups), U, and D; everything else —
policy, attack, attacker count/channel, SNR, learning rate, PRNG seed —
varies per scenario.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import channel as CH
from repro.core import defenses as DEF
from repro.core import scenario as SC
from repro.core import standardize as S
from repro.core.aggregation import (
    FLOAConfig,
    batched_floa_combine,
    batched_floa_step,
    flatten_worker_grads,
    per_worker_grads,
)
from repro.core.attacks import DIRECTIONAL_ATTACKS, AttackType
from repro.core.power_control import Policy
from repro.core.scenario import DefenseSpec
from repro.checkpoint import ckpt as CKPT
from repro.data.pipeline import iter_chunk_blocks
from repro.fl.plan import ExecutionPlan
from repro.fl.trainer import RoundLog
from repro.launch.distributed import fetch as _fetch
from repro.launch.mesh import lane_sharding, put_with_sharding, \
    replicated_sharding, stage_batch_block, sweep_state_sharding

Array = jax.Array

# Resume-checkpoint manifest schema version (the `extra` dict written by
# `_save_checkpoint`); bumped when the carry layout changes so a resume
# against a checkpoint from an incompatible engine fails loudly.
_RESUME_VERSION = 1

# Sentinel distinguishing "caller passed this legacy kwarg" from "left at
# default": only explicitly-passed legacy knobs trigger the deprecation
# warning and participate in building the implicit ExecutionPlan.
_UNSET = object()

# Per-round RNG schedule: every lane splits its round subkey into 3 slots
# (0 = channel gains, 1 = receiver noise, 2 = jamming) — UNCHANGED since
# PR 1, so pre-existing scenario codes keep a bitwise-identical key stream.
# The adaptive-adversary axis draws from `fold_in(subkey, const)` side
# channels instead of widening the split:
_FOLD_COLLUDE = 3   # colluding cohort's shared direction
_FOLD_MARKOV = 4    # Gauss-Markov fading innovation
_FOLD_PART = 5      # K-of-U participation mask
_FOLD_H_INIT = 7    # folded on the lane BASE key: stationary h_0 state

# Layer scopes of the compiled round.  They are HLO op_name metadata only
# (the compiled program is the same instruction for instruction), and a
# profiler trace carries them as each device op's `tf_op` path, so a layer's
# device time is the time of the ops whose path names its scope.  Every
# round builder uses the same five names.
SCOPE_GRADS = "sweep_grads"       # per-worker gradients -> the [S, U, D] slab
SCOPE_COMBINE = "sweep_combine"   # stats, channel/Markov/participation draws,
#                                   coefficients, noise, OTA combine, jamming,
#                                   cohort direction, analog lanes' PS update
SCOPE_SCREEN = "sweep_screen"     # digital flip, defense kernels or the
#                                   lax.switch selector, screened PS update
SCOPE_LOSS = "sweep_loss"         # post-update loss forward, aggregate norm
SCOPE_EVAL = "sweep_eval"         # eval_fn on its schedule
LAYER_SCOPES = (SCOPE_GRADS, SCOPE_COMBINE, SCOPE_SCREEN, SCOPE_LOSS,
                SCOPE_EVAL)

# jax.monitoring scalar recorded once per `SweepEngine.run`: the bytes of
# the device arrays staged from host arrays in that run, over all chunks.
H2D_BYTES = "/repro/sweep/h2d_bytes"


def _layer(name: str):
    """The named scope of one layer of the round (see SCOPE_*)."""
    return jax.named_scope(name)


# A host span (TraceMe) on the profiler's clock.  With no profiler running
# it costs one check; its keyword arguments are recorded only when one is.
_span = jax.profiler.TraceAnnotation


def _named(fn: Callable, name: str) -> Callable:
    """fn under a stable name: jit names the program after it
    (`jit_sweep_run`), so a trace tells the sweep's ops from helper jits'.
    The signature (and so the program's parameter names) stays fn's."""
    @functools.wraps(fn)
    def named(*args):
        return fn(*args)

    named.__name__ = named.__qualname__ = name
    return named


@dataclasses.dataclass(frozen=True)
class ScenarioCase:
    """One lane of the sweep: a frozen FLOAConfig plus its lr and PRNG seed.

    defense selects the lane's aggregation rule: the default analog FLOA
    combine ("floa"), or a digital screening defense (median / trimmed-mean /
    Krum / ... — see core.scenario.DEFENSE_CODES) applied to the gathered
    [U, D] gradient slab, with digital attackers modelled as sign-flipped
    reported gradients (the FLTrainer mode="digital" semantics).

    participants selects K-of-U per-round client sampling: each round the
    lane draws K participants from its own key stream (non-participants
    transmit nothing; digital defenses screen the K participating rows
    only).  None (default) and participants=U are full participation:
    unless another lane samples K < U, no masking op is traced and the two
    are bitwise equal (tests/test_scenario_axes.py).
    """

    name: str
    floa: FLOAConfig
    alpha: float
    seed: int = 0
    defense: DefenseSpec = dataclasses.field(default_factory=DefenseSpec)
    participants: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """An ordered set of scenarios destined for one compiled sweep."""

    cases: Tuple[ScenarioCase, ...]

    @classmethod
    def build(cls, cases: Sequence) -> "SweepSpec":
        """Accepts ScenarioCase instances or (name, floa, alpha[, seed]) tuples."""
        with _span("sweep.spec"):
            out = []
            for c in cases:
                if not isinstance(c, ScenarioCase):
                    c = ScenarioCase(*c)
                out.append(c)
            return cls(cases=tuple(out))

    def __post_init__(self):
        assert self.cases, "empty sweep"
        u = self.cases[0].floa.num_workers
        for c in self.cases:
            c.floa.validate()
            assert c.floa.num_workers == u, "sweep scenarios must share U"
            c.defense.validate(u)
            if c.participants is not None:
                k = c.participants
                if not 1 <= k <= u:
                    raise ValueError(
                        f"lane {c.name!r}: participants={k} invalid for "
                        f"U={u}: need 1 <= K <= U")
                # Digital screening bounds must hold for the K PARTICIPATING
                # rows, not just U (DefenseSpec.validate's bound): the masked
                # kernels screen K rows per round.
                d = c.defense
                if d.name == "trimmed_mean" and not 2 * d.trim < k:
                    raise ValueError(
                        f"lane {c.name!r}: trimmed_mean trim={d.trim} "
                        f"invalid for K={k} participants: need 2*trim < K")
                if d.name in ("krum", "multi_krum"):
                    if d.num_byzantine > k - 3:
                        raise ValueError(
                            f"lane {c.name!r}: krum num_byzantine="
                            f"{d.num_byzantine} invalid for K={k} "
                            f"participants: need f <= K - 3")
                    if d.multi > k:
                        raise ValueError(
                            f"lane {c.name!r}: krum multi={d.multi} invalid "
                            f"for K={k} participants: need multi <= K")
        gm_iters = {c.defense.gm_iters for c in self.cases
                    if c.defense.name == "geometric_median"}
        if len(gm_iters) > 1:  # ValueError like every other defense bound:
            # a bare assert vanishes under -O and a wrong Weiszfeld depth
            # would run silently
            raise ValueError(
                "geometric_median lanes must share gm_iters (it is a static "
                f"scan length, one per compiled sweep); got {sorted(gm_iters)}")

    def __len__(self) -> int:
        return len(self.cases)

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(c.name for c in self.cases)

    @property
    def num_workers(self) -> int:
        return self.cases[0].floa.num_workers

    def stacked_params(self) -> SC.ScenarioParams:
        """Frozen dataclass configs -> traceable struct-of-arrays, [S, ...]."""
        return SC.stack(tuple(
            SC.from_floa(c.floa, c.alpha, c.defense,
                         participants=c.participants)
            for c in self.cases))

    def keys(self) -> Array:
        return jnp.stack([jax.random.PRNGKey(c.seed) for c in self.cases])

    # Static trace decisions: skip the [S, D] RNG draws entirely when no
    # scenario can consume them (EF-only sweeps, noiseless ablations).
    @property
    def any_noise(self) -> bool:
        return any(c.floa.channel.noise_std > 0.0
                   and c.floa.power.policy != Policy.EF for c in self.cases)

    @property
    def any_jamming(self) -> bool:
        return any(c.floa.attack.attack == AttackType.GAUSSIAN
                   and c.floa.attack.num_attackers > 0
                   and c.floa.power.policy != Policy.EF for c in self.cases)

    # Defense-code lane axis (also static trace decisions): a sweep with no
    # digital lanes skips the screening kernels entirely, and a mixed sweep
    # builds its lax.switch over exactly the defense codes present — absent
    # defenses cost nothing under the vmapped select.
    @property
    def any_digital(self) -> bool:
        return any(c.defense.is_digital for c in self.cases)

    @property
    def all_digital(self) -> bool:
        return all(c.defense.is_digital for c in self.cases)

    @property
    def digital_codes(self) -> Tuple[int, ...]:
        return tuple(sorted({c.defense.code for c in self.cases
                             if c.defense.is_digital}))

    @property
    def lane_codes(self) -> Tuple[int, ...]:
        """Per-lane defense codes in lane order — concrete config, which is
        what makes the grouped dispatch a static (build-time) partition."""
        return tuple(c.defense.code for c in self.cases)

    # analog_noise / analog_jamming restrict the any_* trace decisions to the
    # lanes that actually consume the draws: the grouped engine's analog
    # group.  (A digital lane's channel config is dead weight — under the
    # switch dispatch its noise row multiplies into a discarded combine, and
    # an all-zero noise_std row is bitwise inert anyway.)
    @property
    def analog_noise(self) -> bool:
        return any(c.floa.channel.noise_std > 0.0
                   and c.floa.power.policy != Policy.EF
                   and not c.defense.is_digital for c in self.cases)

    @property
    def analog_jamming(self) -> bool:
        return any(c.floa.attack.attack == AttackType.GAUSSIAN
                   and c.floa.attack.num_attackers > 0
                   and c.floa.power.policy != Policy.EF
                   and not c.defense.is_digital for c in self.cases)

    @property
    def gm_iters(self) -> int:
        its = {c.defense.gm_iters for c in self.cases
               if c.defense.name == "geometric_median"}
        return its.pop() if its else 8

    # Adaptive-adversary axis (PR 8) — three more static trace gates.  Each
    # is False for every pre-existing scenario code, so sweeps without the
    # new axes trace the exact program (and key stream) they always did.
    @property
    def any_markov(self) -> bool:
        """Gauss-Markov fading consumers: rho > 0 on an analog, non-EF lane
        (digital lanes ignore the channel; EF ignores |h|).  Gates the
        [S, U, 2] complex-gain scan carry."""
        return any(c.floa.channel.markov_rho > 0.0
                   and c.floa.power.policy != Policy.EF
                   and not c.defense.is_digital for c in self.cases)

    @property
    def any_partial(self) -> bool:
        """K-of-U participation: any lane sampling K < U clients.  A lane
        with participants=U is full participation and does not count, so a
        sweep whose lanes are all None or U traces the unmasked program and
        is bitwise equal to participants=None."""
        u = self.num_workers
        return any(c.participants is not None and c.participants < u
                   for c in self.cases)

    @property
    def any_directional(self) -> bool:
        """COLLUDING/OMNISCIENT cohorts with someone in them, on an analog
        non-EF lane: gates the post-combine rank-1 direction injection."""
        return any(c.floa.attack.attack in DIRECTIONAL_ATTACKS
                   and c.floa.attack.num_attackers > 0
                   and c.floa.power.policy != Policy.EF
                   and not c.defense.is_digital for c in self.cases)


@dataclasses.dataclass
class SweepResult:
    """Per-scenario, per-round trajectories ([S, R] arrays, host-side)."""

    names: Tuple[str, ...]
    params: object                  # final params, leaves [S, ...]
    loss: np.ndarray                # [S, R]
    grad_norm: np.ndarray           # [S, R]
    metrics: Dict[str, np.ndarray]  # each [S, R]

    def index(self, name: str) -> int:
        return self.names.index(name)

    def save(self, path: str) -> str:
        """Serialize to <path>.npz + <path>.meta.json (the
        `repro.checkpoint.write_tree` format, atomic): every params leaf,
        the [S, R] loss/grad_norm trajectories, and each metrics entry as
        exact arrays, with the scenario names in the manifest's `extra` —
        so a resumed or remote sweep can ship its results whole.  Schema
        documented in docs/benchmarks.md.  Returns the payload path."""
        tree = {"params": self.params, "loss": self.loss,
                "grad_norm": self.grad_norm, "metrics": dict(self.metrics)}
        return CKPT.write_tree(path, tree, extra={
            "kind": "SweepResult", "version": 1, "names": list(self.names)})

    @classmethod
    def load(cls, path: str) -> "SweepResult":
        """Inverse of `save`: byte-exact arrays, names, metrics.  The
        params container structure is rebuilt from the recorded tree paths
        (dicts and lists; tuples come back as lists)."""
        tree, meta = CKPT.read_tree(path)
        if meta.get("extra", {}).get("kind") != "SweepResult":
            raise ValueError(
                f"{path!r} is not a saved SweepResult "
                f"(manifest extra.kind={meta.get('extra', {}).get('kind')!r})")
        return cls(names=tuple(meta["extra"]["names"]),
                   params=tree["params"], loss=tree["loss"],
                   grad_norm=tree["grad_norm"],
                   metrics=dict(tree.get("metrics", {})))

    def logs(self, name_or_idx, eval_every: int = 1) -> List[RoundLog]:
        """RoundLog list for one scenario, sampled on the same schedule as
        `FLTrainer.run(eval_every=...)` — drop-in for the figure CSV writers.
        Use the engine's own eval_every here: off-schedule rounds carry NaN
        accuracy (the eval was skipped inside the scan)."""
        i = (name_or_idx if isinstance(name_or_idx, int)
             else self.index(name_or_idx))
        rounds = self.loss.shape[1]
        acc = self.metrics.get("accuracy")
        out = []
        for t in range(rounds):
            if eval_every and (t % eval_every == 0 or t == rounds - 1):
                out.append(RoundLog(
                    step=t, loss=float(self.loss[i, t]),
                    accuracy=(float(acc[i, t]) if acc is not None
                              else float("nan")),
                    grad_norm=float(self.grad_norm[i, t])))
        return out


def _digital_flip(flat: Array, sp: SC.ScenarioParams) -> Array:
    """Digital attackers report -g (the FLTrainer mode="digital" threat
    model — there is no channel to cheat on): sign-flip Byzantine rows of
    the [S, U, D] slab.  Shared by the switch and grouped dispatch paths so
    their per-lane math is identical."""
    sign = jnp.where((sp.attack != 0)[:, None] & sp.byz_mask,
                     jnp.float32(-1.0), jnp.float32(1.0))
    return flat * sign[:, :, None]


def stack_params(params, num: int):
    """Broadcast one init pytree to a stacked [S, ...] scenario axis."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (num,) + x.shape), params)


def make_row_unflatten(template):
    """Cached [D]-row -> params-pytree mapper, from one `jax.eval_shape`.

    template: a single (unstacked) params pytree or matching ShapeDtypeStruct
    tree.  Returns (unflatten_row, sizes) where sizes are the per-leaf entry
    counts in flatten order — the same order `flatten_worker_grads` uses, so
    flatten(unflatten(w)) == w.
    """
    shapes = jax.eval_shape(lambda p: p, template)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    sizes = tuple(math.prod(l.shape) for l in leaves)

    def unflatten_row(w):
        out, off = [], 0
        for l, n in zip(leaves, sizes):
            out.append(w[off:off + n].reshape(l.shape).astype(l.dtype))
            off += n
        return jax.tree_util.tree_unflatten(treedef, out)

    return unflatten_row, sizes


class _WorkerShards:
    """Worker-axis sharding arithmetic for the flat-state scan body.

    Built once per engine (U and the shard count are static); every method
    below runs INSIDE the shard_mapped scan, on one device's slice of the
    ("workers",) mesh axis.  U is ghost-padded up to u_pad = shards * u_loc:
    ghost workers replicate worker U-1's batch rows (finite gradients, no
    NaN poisoning) and carry zero combine coefficients, so they contribute
    exactly nothing to the psum and their stats are sliced away after the
    all-gather.

    RNG discipline: channel gains / coefficients / noise are always drawn at
    the FULL U on every shard (ScenarioParams is replicated), so the key
    consumption schedule — and hence every draw — is identical to the
    unsharded engine's.  Only the gradient slab and its weighted reduction
    are actually distributed.
    """

    def __init__(self, u: int, shards: int):
        self.u = u
        self.shards = shards
        self.u_loc = -(-u // shards)          # ceil: last shard may be ghosts
        self.u_pad = self.u_loc * shards

    def local_batch(self, batch):
        """Gather this shard's workers' rows of the per-round batch:
        [U*b, ...] leaves -> [u_loc*b, ...].  Global worker indices are
        clipped to U-1, so ghost workers recompute worker U-1's gradient
        (discarded — their coefficient column is zeroed in `local_coeff`)."""
        b = jax.tree_util.tree_leaves(batch)[0].shape[0] // self.u
        widx = jax.lax.axis_index("workers")
        gi = jnp.clip(widx * self.u_loc + jnp.arange(self.u_loc), 0, self.u - 1)
        rows = (gi[:, None] * b + jnp.arange(b)[None, :]).reshape(-1)
        return jax.tree_util.tree_map(lambda x: x[rows], batch)

    def gather_slab(self, x: Array) -> Array:
        """[S, u_loc, D] local slab -> [S, U, D] full slab (all-gather over
        "workers"; ghost rows sliced off).  The digital screening defenses
        consume this — they are order statistics over the worker axis, so
        they need the gathered slab the analog scheme avoids."""
        full = jax.lax.all_gather(x, "workers", axis=1, tiled=True)
        return full[:, :self.u]

    def gather_stats(self, gbar_i: Array, eps2_i: Array):
        """Per-worker scalar stats [S, u_loc] -> full [S, U].  All-gathering
        the SCALARS (not the slab) keeps the handshake cheap, and the global
        mean is then reduced from the identical [S, U] vector the unsharded
        engine reduces — same values, same order, bitwise-equal stats."""
        g = jax.lax.all_gather(gbar_i, "workers", axis=1, tiled=True)
        e = jax.lax.all_gather(eps2_i, "workers", axis=1, tiled=True)
        return g[:, :self.u], e[:, :self.u]

    def local_coeff(self, coeff: Array) -> Array:
        """Full [S, U] combine coefficients -> this shard's [S, u_loc] slice,
        ghost columns zero-padded (u_pad = shards * u_loc, so the dynamic
        slice is always in bounds and never clamps across shard boundaries)."""
        pad = self.u_pad - self.u
        if pad:
            coeff = jnp.pad(coeff, ((0, 0), (0, pad)))
        widx = jax.lax.axis_index("workers")
        return jax.lax.dynamic_slice_in_dim(
            coeff, widx * self.u_loc, self.u_loc, axis=1)

    def psum_combine(self, coeff, flat_loc, noise_row, bias_row, eps):
        """The OTA superposition as a psum over worker shards: each shard
        contributes the weighted sum of its own workers' gradients, the
        all-reduce models the multiple-access channel's addition, and the
        (replicated) de-standardization bias + receiver noise land once
        after the reduction — matching `batched_floa_combine`'s reference
        einsum with the U axis distributed."""
        partial = jnp.einsum("su,sud->sd", self.local_coeff(coeff), flat_loc)
        total = jax.lax.psum(partial, "workers")
        return total + bias_row[:, None] + eps[:, None] * noise_row


class _ModelShards:
    """Flat-parameter (D) axis sharding arithmetic for the flat-state scan.

    Built once per compiled program (D comes off the params template); every
    method below runs INSIDE the shard_mapped scan, on one device's column
    block of the ("model",) mesh axis.  D is zero-padded once, pre-jit, to
    d_pad = shards * d_loc with d_loc a multiple of the Pallas TILE_D — the
    "model" split is always even and every shard's column block stays
    kernel-tile aligned.  Ghost columns carry zeros for the whole run: the
    state pads with zeros, the pad region is invisible to the loss (the row
    unflatten reads exactly D entries, so its gradient there is
    structurally zero), the stats' partial sums see exact 0.0
    contributions, and the scan body re-masks the aggregate each round (the
    de-standardization bias is a per-lane scalar broadcast that would
    otherwise smear onto ghost columns).

    RNG discipline: [D]-shaped draws (receiver noise, jamming, the
    colluding cohort's direction) always happen at the FULL real D on every
    shard and are then pad+sliced to the local block — the key consumption
    schedule, and every drawn value, is identical to the unsharded
    engine's (mirroring _WorkerShards' full-U draw rule).
    """

    def __init__(self, d: int, shards: int, tile_d: Optional[int] = None):
        if tile_d is None:
            from repro.kernels.floa_aggregate import TILE_D as tile_d
        self.d = d
        self.shards = shards
        chunk = shards * tile_d
        self.d_pad = -(-d // chunk) * chunk
        self.d_loc = self.d_pad // shards

    def pad_cols(self, x: Array) -> Array:
        """Zero-pad the last (D) axis up to d_pad.  Host- and trace-safe."""
        pad = self.d_pad - x.shape[-1]
        if pad == 0:
            return x
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def local_cols(self, x: Array) -> Array:
        """[..., D or d_pad] full columns -> this shard's [..., d_loc]
        block (zero-padding the real-D tail first, so the last shard's
        ghost columns are exact zeros)."""
        if x.shape[-1] != self.d_pad:
            x = self.pad_cols(x)
        midx = jax.lax.axis_index("model")
        return jax.lax.dynamic_slice_in_dim(
            x, midx * self.d_loc, self.d_loc, axis=x.ndim - 1)

    def gather_cols(self, x: Array) -> Array:
        """[..., d_loc] local block -> [..., D] full REAL columns
        (all-gather over "model"; ghost columns sliced off — they sit at
        the tail of the concatenated blocks, positions D..d_pad-1)."""
        full = jax.lax.all_gather(x, "model", axis=x.ndim - 1, tiled=True)
        return full[..., :self.d]

    def col_mask(self) -> Array:
        """[d_loc] bool: True on this shard's REAL columns.  where(mask,
        x, 0) is a bitwise identity on real columns, so re-masking the
        aggregate never perturbs them."""
        midx = jax.lax.axis_index("model")
        return midx * self.d_loc + jnp.arange(self.d_loc) < self.d


class SweepEngine:
    """Builds (and caches) the jitted scan-over-rounds x vmap-over-scenarios
    program for one (loss_fn, spec, eval_fn) triple.  Reuse the instance to
    amortize compilation across repeated runs (benchmarks, seeds-resampling).

    Execution strategy lives in an `ExecutionPlan` (fl/plan.py) — the
    primary signature is::

        engine = SweepEngine(loss_fn, spec, eval_fn=...,
                             plan=ExecutionPlan(mesh=..., chunk_rounds=...))

    Every plan knob changes HOW the sweep executes, never WHAT it computes;
    each one's equivalence contract (what stays identical, and to what
    tolerance) is stated below and pinned by the test suite.  The plan's
    cross-knob invariants are validated at `ExecutionPlan` construction.
    The pre-plan per-knob constructor kwargs (`flat_state=`, `mesh=`, ...)
    still work: they build the equivalent plan internally (bitwise-equal
    execution, pinned by tests/test_execution_plan.py) and emit a
    DeprecationWarning.  Passing both a plan and legacy kwargs is an error.

    eval_fn / eval_every: run eval_fn only on rounds t with
    t % eval_every == 0 plus the final round (the FLTrainer.run schedule);
    other rounds carry NaN in the metrics arrays.  eval_every <= 0 means
    final round only.  Evaluation happens inside the compiled scan (behind a
    lax.cond), so a sparse schedule skips the eval compute entirely.  The
    schedule is anchored to the ABSOLUTE round index — chunking (below) does
    not move it.

    flat_state=True (default) runs the flat-state warm path: params live as
    one [S, D] f32 matrix for the whole scan and the combine + PS update fuse
    into `batched_floa_step`.  flat_state=False keeps the PR-1 tree-state
    path (per-round flatten/concat + per-leaf update, verbatim by default)
    as the equivalence reference and benchmark baseline.  Contract: the
    paths agree to fp rounding (rtol ~1e-5); constructing BOTH engines with
    strict_numerics=True makes them bit-identical for f32 models.  (The flat
    state is f32; non-f32 leaves are round-tripped through f32 each round,
    matching the flatten that the tree path applies to the gradients.)

    strict_numerics=True pins the standardization stats' fp reduction tree
    (leaf-segmented sums off the materialized [S, U, D] slab, behind an
    optimization barrier) so that every execution strategy — tree vs flat
    state, grouped vs switch dispatch, chunked vs monolithic, sharded vs
    not — replays the same trajectory BIT-FOR-BIT, at the cost of one extra
    pass over the slab per round.  Off (default), XLA may fuse each
    strategy's stats reduction differently and the strategies agree to fp
    rounding only.

    mesh: optional sweep mesh (see `launch.mesh.make_sweep_mesh`) — "data"
    shards the lane axis, "workers" the worker axis, "model" the flat-
    parameter (D) axis; any subset composes, up to the 3-D
    ("data", "workers", "model") mesh.  The flat-state scan is shard_mapped
    over the mesh; with a "data" axis, S is padded up to a multiple of the
    lane-shard count with ghost lanes (replicas of the last scenario) that
    are dropped from the returned SweepResult.  Requires flat_state=True.
    Contract: every real lane's trajectory matches the unsharded engine
    (rtol 1e-6; bitwise in practice and under strict_numerics).

    worker_shards=W > 1 (derived from the mesh's "workers" axis) shards the
    [S, U, D] gradient slab's WORKER axis: each shard computes gradients for
    its own ceil(U/W) workers from its slice of the batch (ghost workers
    replicate worker U-1 and are coefficient-masked to zero), the
    standardization handshake all-gathers per-worker SCALAR stats (so the
    global mean reduces the identical [S, U] vector the unsharded engine
    reduces — bitwise-equal stats), and the OTA combine becomes a
    `lax.psum` of per-shard partial superpositions over the "workers" axis.
    Digital screening lanes all-gather their group's sub-slab first (order
    statistics need the full worker axis).  RNG draws (channel gains,
    coefficients, noise) happen at full U on every shard, so the key
    schedule is the unsharded engine's exactly.  Contract: worker-sharded ==
    unsharded at rtol ~1e-6 per round for any U (including U % W != 0) —
    the psum reduces partial superpositions in mesh order, so multi-round
    float32 trajectories may drift a few ulp past that; under
    strict_numerics the engine all-gathers the full slab up front and
    replays the unsharded reduction order verbatim — bitwise equality, at
    the cost of materializing [S, U, D] per device.

    model_shards=M > 1 (derived from the mesh's "model" axis) shards the
    flat [S, D] state's and the [S, U, D] slab's PARAMETER axis: D is
    zero-padded once, pre-jit, to a multiple of M * TILE_D (ghost columns
    stay exactly zero — see `_ModelShards`), per-worker gradients come off
    all-gathered full-D rows (the grad trace is the unsharded engine's),
    the standardization stats reduce per-shard partial sums with two scalar
    psums per worker (`core.standardize.flat_partial_stats` documents the
    numerical contract), every [D]-shaped RNG draw happens at the full real
    D on every shard (identical key schedule), column-wise screening
    defenses (mean / median / trimmed-mean) run shard-local over their
    column block, row-geometry defenses (Krum family, geometric median)
    all-gather full rows first, and the final unflatten slices the real
    columns back out.  Composes with "data" and "workers" into up-to-3-D
    meshes.  Contract: model-sharded == unsharded at rtol ~1e-6 per round
    (the stats' partial-sum tree reassociates f32 addition); under
    strict_numerics the engine gathers full rows, replays the unsharded
    math verbatim, and re-slices only the carry — bitwise equality.

    grouped_dispatch=True (default) partitions the lanes of a defense-
    carrying sweep by defense code at BUILD time (codes are concrete config):
    lanes are gathered into per-family contiguous groups
    (`scenario.build_lane_groups`), each group's kernel runs once over its
    [S_g, U, D] sub-slab — the analog group keeps the fused
    `batched_floa_step` route, digital groups run exactly their own family —
    and results scatter back to lane order host-side.  A mixed grid thus pays
    only for the families it contains, where the per-lane `lax.switch`
    (grouped_dispatch=False, the PR-3 reference path) computes EVERY family
    present for EVERY lane under vmap.  Under a mesh each group is ghost-
    padded to a multiple of the device count so every shard traces the same
    static group layout.  Pure-FLOA sweeps are untouched by the flag.
    Contract: lane trajectories match the switch path (rtol 1e-6; bitwise
    under strict_numerics) — the per-lane math and key-split schedule are
    shared, only which lanes trace which kernels changes.

    chunk_rounds: None (default) compiles ONE scan over all R rounds.  An
    int C >= 1 switches to scan-of-chunks execution: an outer Python loop
    dispatches ceil(R/C) inner scans of (up to) C rounds each, threading the
    (state, keys, absolute-round-offset) carry through the chunk boundaries
    — RNG key splitting, the eval schedule, metric layout, grouped-dispatch
    lane permutation, and sharded ghost padding are all chunk-invariant.
    Contract: chunked == monolithic at rtol 1e-6 (bitwise under
    strict_numerics) for any C, including R % C != 0 (the last chunk is
    short; it compiles once more for the remainder shape).  The chunk
    boundary exists to bound device batch memory ([C, ...] blocks instead of
    [R, ...]) and to give the input pipeline a place to overlap:

    async_staging=True (requires chunk_rounds) double-buffers the
    host->device batch staging: while chunk k executes, chunk k+1's block is
    sliced host-side (numpy views) and transferred with an async
    `jax.device_put` (`launch.mesh.stage_batch_block`, landing pre-sharded
    replicated under a mesh), so the device never idles on input transfers.
    Contract: a pure scheduling change — results are bit-identical to
    async_staging=False; wins show up on data-bound configs (large batch
    blocks relative to round compute).

    checkpoint_dir (requires chunk_rounds) makes the chunked execution
    preemption-safe: after every checkpoint_every_chunks-th chunk boundary
    (never the final one) the full resume carry — execution-order state
    (including the Markov `h` tuple element when present), the key
    schedule, the absolute round offset, and the host-side
    loss/grad-norm/metric blocks accumulated so far — is written with
    `repro.checkpoint.save_pytree` (atomic: the meta manifest's rename
    commits).  `run(..., resume=True)` restores the latest committed
    snapshot, validates its manifest against this run (rounds, chunking,
    lane names, eval schedule), and dispatches only the remaining chunks.
    Contract: resumed == uninterrupted BITWISE — the restored carry is
    byte-exact and the re-dispatched chunk program is the identical jitted
    computation, so no fp tolerance is needed (pinned across flat/grouped/
    Markov grids and across a SIGKILLed process in
    tests/test_sweep_resume.py).
    """

    def __init__(self, loss_fn: Callable, spec: SweepSpec,
                 eval_fn: Optional[Callable] = None, eval_every: int = 1,
                 plan: Optional[ExecutionPlan] = None,
                 flat_state=_UNSET, mesh=_UNSET, strict_numerics=_UNSET,
                 grouped_dispatch=_UNSET, chunk_rounds=_UNSET,
                 async_staging=_UNSET):
        """See the class docstring for each plan knob's equivalence contract.

        plan: the execution strategy (fl.plan.ExecutionPlan).  The remaining
        kwargs are the deprecated pre-plan spelling: any that are passed
        explicitly build the equivalent plan (DeprecationWarning); mixing
        them with plan= raises.
        """
        with _span("sweep.init"):
            legacy = {k: v for k, v in dict(
                flat_state=flat_state, mesh=mesh, strict_numerics=strict_numerics,
                grouped_dispatch=grouped_dispatch, chunk_rounds=chunk_rounds,
                async_staging=async_staging).items() if v is not _UNSET}
            if legacy:
                if plan is not None:
                    raise ValueError(
                        f"pass the execution strategy as plan=ExecutionPlan(...) "
                        f"OR as the legacy per-knob kwargs, not both (got plan "
                        f"and {sorted(legacy)})")
                warnings.warn(
                    "SweepEngine's per-knob execution kwargs (flat_state, mesh, "
                    "strict_numerics, grouped_dispatch, chunk_rounds, "
                    "async_staging) are deprecated; pass "
                    "plan=ExecutionPlan(...) instead",
                    DeprecationWarning, stacklevel=2)
                plan = ExecutionPlan(**legacy)
            elif plan is None:
                plan = ExecutionPlan()
            self.plan = plan
            self.loss_fn = loss_fn
            self.spec = spec
            self.eval_fn = eval_fn
            self.eval_every = eval_every
            # Legacy attribute surface: downstream code (tests, benchmarks)
            # reads the knobs off the engine; keep them as plain mirrors of the
            # plan.
            self.flat_state = plan.flat_state
            self.mesh = plan.mesh
            self.strict_numerics = plan.strict_numerics
            self.grouped_dispatch = plan.grouped_dispatch
            self.chunk_rounds = plan.chunk_rounds
            self.async_staging = plan.async_staging
            self.checkpoint_dir = plan.checkpoint_dir
            self.checkpoint_every_chunks = plan.checkpoint_every_chunks
            self._num = len(spec)
            self._u = spec.num_workers
            self._sp = spec.stacked_params()
            shards = plan.data_shards
            self._ws = (_WorkerShards(self._u, plan.worker_shards)
                        if plan.worker_sharded else None)
            # Model-axis sharding arithmetic is built lazily in _build: the
            # flat parameter count D only arrives with the params template.
            self._ms = None
            # Grouped dispatch only matters when a screening defense shares the
            # grid with other families; pure-FLOA sweeps keep the untouched
            # (unpermuted) fused path regardless of the flag.
            self._groups = (SC.build_lane_groups(spec.lane_codes, shards)
                            if plan.grouped_dispatch and spec.any_digital
                            else None)
            if self._groups is not None:
                self._pad = self._groups.exec_lanes - self._num
                if self._groups.num_ghosts > self._num:
                    # Per-group padding to the device count blew the executed
                    # lane axis up past 2x: every ghost lane runs (discarded)
                    # grads/loss/eval each round, so grouped dispatch can LOSE
                    # to the switch path here — say so instead of silently
                    # inverting the default's advantage.
                    warnings.warn(
                        f"grouped dispatch executes {self._groups.exec_lanes} "
                        f"lanes for {self._num} scenarios ({self._groups.num_ghosts}"
                        f" ghosts: {len(self._groups.codes)} defense-code groups "
                        f"each padded to a multiple of {shards} devices); with "
                        f"groups this small relative to the mesh, "
                        f"grouped_dispatch=False may be faster")
                self._sp_run = SC.permute_lanes(self._sp, self._groups.perm)
            else:
                self._pad = -self._num % shards
                self._sp_run = SC.pad_lanes(self._sp, self._num + self._pad)
            # The compiled program is built lazily on the first run: the flat
            # path needs the params template (leaf shapes/dtypes) to cache its
            # row unflatten, and that only arrives with params0.
            self._run_jit = None
            self._chunk_jit = None
            self._finalize_jit = None
            self._template = None
            self._runs = 0          # the run index each sweep.run span carries
            self._h2d_bytes = 0     # this run's H2D_BYTES so far

    # ------------------------------------------------------------ builders

    def _make_digital_select(self):
        """Defense-code lane axis: [S, U, D] slab -> per-lane aggregate select.

        Returns apply(gagg_floa, flat, sp[, part]) -> [S, D]: digital
        attackers' rows are sign-flipped (the FLTrainer mode="digital"
        semantics — a digital Byzantine worker reports -g, it has no channel
        to cheat on), the lane's screening defense runs on the flipped slab
        via a vmapped `lax.switch` over the codes present in the spec, and
        analog lanes (code 0) keep their OTA combine output.  Both state
        paths share this helper so strict_numerics stays bitwise across
        them.  When the spec has participation lanes the selector switches
        over the MASKED kernel table and `part` ([S, U] bool) excludes
        non-participating rows from every screen.
        """
        masked = self.spec.any_partial
        selector = DEF.make_flat_defense_selector(
            self.spec.digital_codes, gm_iters=self.spec.gm_iters,
            masked=masked)

        def apply(gagg_floa, flat, sp: SC.ScenarioParams, part=None):
            flipped = _digital_flip(flat, sp)
            if masked:
                dig = jax.vmap(selector)(sp.defense, flipped, sp.def_trim,
                                         sp.def_f, sp.def_multi, part)
            else:
                dig = jax.vmap(selector)(sp.defense, flipped, sp.def_trim,
                                         sp.def_f, sp.def_multi)
            if gagg_floa is None:  # all-digital sweep: no analog leg at all
                return dig
            return jnp.where((sp.defense == 0)[:, None], gagg_floa, dig)

        return apply

    # ----- grouped dispatch (static lane partition by defense code) -----

    def _digital_group_kernels(self) -> Dict[int, Callable]:
        """code -> single-family [S_g, U, D] kernel, for each digital group
        in the partition (codes are concrete build-time config).  With
        participation lanes in the spec the kernels take the masked form
        (trailing [S_g, U] bool participation argument)."""
        return {code: DEF.make_group_defense_kernel(
                    code, gm_iters=self.spec.gm_iters,
                    masked=self.spec.any_partial)
                for code, _, _ in self._groups.local_slices
                if code != SC._FLOA_CODE}

    # ----- adaptive-adversary axis helpers (PR 8) -----

    def _make_part_draw(self):
        """Per-round K-of-U participation masks, full lane axis: [S, U] bool
        from each lane's fold_in(subkey, _FOLD_PART) side channel — the
        3-way round split is untouched, so non-participation draws are
        unchanged."""
        u = self._u

        def draw(sub_s, sp: SC.ScenarioParams):
            return jax.vmap(lambda k, pk: SC.participation_mask(
                jax.random.fold_in(k, _FOLD_PART), pk, u))(sub_s, sp.part_k)

        return draw

    def _make_markov_update(self):
        """One Gauss-Markov fading step over the full lane axis.

        (h [S, U, 2], sub_s, sp) -> (h_new, h_abs [S, U]).  The legacy
        i.i.d. draw off key slot 0 happens for EVERY lane exactly as before
        (so slots 1/2 — noise/jam — see an identical key stream), and
        rho = 0 lanes keep that draw via the per-lane where: their |h| is
        BITWISE the block-i.i.d. engine's.  rho > 0 lanes take |h| off the
        evolving complex state instead, with innovations from the
        fold_in(subkey, _FOLD_MARKOV) side channel.
        """
        def update(h, sub_s, sp: SC.ScenarioParams):
            ks = jax.vmap(lambda k: jax.random.split(k, 3))(sub_s)
            h_iid = jax.vmap(SC.sample_gains)(ks[:, 0], sp)
            w_in = jax.vmap(lambda k, sg: CH.complex_gain_init(
                jax.random.fold_in(k, _FOLD_MARKOV), sg))(sub_s, sp.sigma)
            h_new = CH.gauss_markov_step(h, w_in, sp.chan_rho[:, None, None])
            h_abs = jnp.where((sp.chan_rho > 0.0)[:, None],
                              CH.complex_gain_abs(h_new), h_iid)
            return h_new, h_abs

        return update

    def _h0_init(self, keys, sp: SC.ScenarioParams):
        """Stationary complex-gain init [S, U, 2] from each lane's BASE key
        (fold_in const _FOLD_H_INIT — the per-round split schedule never
        sees it), so every marginal is Rayleigh(sigma) from round 0."""
        return jax.vmap(lambda k, sg: CH.complex_gain_init(
            jax.random.fold_in(k, _FOLD_H_INIT), sg))(keys, sp.sigma)

    def _make_analog_step(self, ws: Optional[_WorkerShards] = None,
                          grouped: bool = False,
                          ms: Optional[_ModelShards] = None):
        """The analog leg of one round — ONE definition shared by all four
        builders (tree/flat state x grouped/switch dispatch), which is what
        keeps their per-lane math (and the equivalence contracts) aligned.

        step(wg | None, fg, sub_g, spg, gbar_i, eps2_i, part=None,
             h_abs=None) -> (w_new | None, gagg):
        standardization stats + channel draw + power/attack coefficients +
        receiver noise + OTA combine (+ jamming + adaptive rank-1 cohort
        direction) on a [S_g, U, D] (sub-)slab.  With wg given and neither
        jamming nor a directional attack in the spec, the combine and PS
        update stay fused (`batched_floa_step`).  grouped=True narrows the
        noise/jam trace gates to the analog group's lanes (analog_noise /
        analog_jamming).

        part: optional [S_g, U] participation masks — stats then average the
        K participating workers only (`masked_global_stats`, bitwise equal
        to the plain mean at a full mask) and non-participants drop out of
        the coefficients.  h_abs: optional pre-drawn |h| (the Gauss-Markov
        carry path); None draws the legacy block-i.i.d. gains off key
        slot 0.

        With ws (worker sharding, non-strict), fg is the LOCAL
        [S_g, u_loc, D] slice, the draws still happen at full U (replicated
        — identical key schedule), and the combine is `ws.psum_combine`.

        With ms (model sharding, non-strict), fg's LAST axis is the local
        [.., d_loc] column block; every [D]-shaped draw still happens at
        the full real D (identical key schedule) and is pad+sliced local,
        the combine runs on local columns, and the aggregate (and the
        fused route's w_new) is re-masked so ghost columns stay exactly
        zero — the de-standardization bias is a per-lane scalar broadcast
        that would otherwise land on them.
        """
        any_noise = self.spec.analog_noise if grouped else self.spec.any_noise
        any_jam = (self.spec.analog_jamming if grouped
                   else self.spec.any_jamming)
        any_dir = self.spec.any_directional

        def step(wg, fg, sub_g, spg, gbar_i, eps2_i, part=None, h_abs=None):
            n_g = fg.shape[0]
            # [D]-shaped draws happen at the full real D even when fg's
            # columns are a local block (ms) — identical key schedule.
            dim = ms.d if ms is not None else fg.shape[-1]
            if part is None:
                gbar, eps2 = jax.vmap(S.global_stats)(gbar_i, eps2_i)
            else:
                gbar, eps2 = jax.vmap(S.masked_global_stats)(
                    gbar_i, eps2_i, part)
            eps = jnp.sqrt(eps2)
            ks = jax.vmap(lambda k: jax.random.split(k, 3))(sub_g)  # [Sg,3,2]
            if h_abs is None:
                h_abs = jax.vmap(SC.sample_gains)(ks[:, 0], spg)
            args = (h_abs, spg, gbar, eps2)
            if part is not None:
                args = args + (part,)
            coeff, bias_w, jam_std, noise_std, dir_w = jax.vmap(
                SC.scenario_coefficients)(*args)
            if any_noise:
                z = jax.vmap(
                    lambda k: jax.random.normal(k, (dim,), jnp.float32)
                )(ks[:, 1])
                noise_row = noise_std[:, None] * z
                if ms is not None:
                    noise_row = ms.local_cols(noise_row)
            else:
                noise_row = jnp.zeros((n_g, fg.shape[-1]), jnp.float32)
            bias_row = bias_w * gbar
            if ws is not None:
                gagg = ws.psum_combine(coeff, fg, noise_row, bias_row, eps)
            else:
                if wg is not None and not (any_jam or any_dir):
                    w_new, gagg = batched_floa_step(
                        wg, spg.alpha, coeff, fg, noise_row, bias_row, eps)
                    if ms is not None:
                        mask = ms.col_mask()
                        w_new = jnp.where(mask, w_new, 0.0)
                        gagg = jnp.where(mask, gagg, 0.0)
                    return w_new, gagg
                gagg = batched_floa_combine(
                    coeff, fg, noise_row, bias_row, eps)
            if ms is not None:
                # The bias is a per-lane scalar broadcast: re-zero the
                # ghost columns (bitwise identity on real ones).  Every
                # later additive term (jam / direction) is already zero
                # there, so one mask suffices.
                gagg = jnp.where(ms.col_mask(), gagg, 0.0)
            if any_jam:
                n2 = jax.vmap(
                    lambda k: jax.random.normal(k, (dim,), jnp.float32)
                )(ks[:, 2])
                jam_row = jam_std[:, None] * n2
                if ms is not None:
                    jam_row = ms.local_cols(jam_row)
                gagg = gagg + jam_row
            if any_dir:
                # The cohort's shared rank-1 payload, injected after the OTA
                # combine: COLLUDING transmits a cohort-common unit-RMS
                # random direction (fold_in side channel), OMNISCIENT the
                # round's honest (participating) mean gradient; dir_w
                # carries the |h|-weighted received amplitude and is 0.0 for
                # every other attack code.
                d = jax.vmap(lambda k: jax.random.normal(
                    jax.random.fold_in(k, _FOLD_COLLUDE), (dim,),
                    jnp.float32))(sub_g)
                rms = jnp.sqrt(jnp.mean(jnp.square(d), axis=-1,
                                        keepdims=True))
                d = d / jnp.maximum(rms, 1e-20)
                if ms is not None:
                    # Unit-RMS normalization happened at the full real D
                    # (bitwise the unsharded direction); only then slice.
                    d = ms.local_cols(d)
                hmaskf = (~spg.byz_mask).astype(jnp.float32)
                if part is not None:
                    hmaskf = hmaskf * part.astype(jnp.float32)
                cnt = jnp.maximum(jnp.sum(hmaskf, axis=-1), 1.0)
                if ws is not None:
                    hpart = jnp.einsum("su,sud->sd",
                                       ws.local_coeff(hmaskf), fg)
                    hsum = jax.lax.psum(hpart, "workers")
                else:
                    hsum = jnp.einsum("su,sud->sd", hmaskf, fg)
                hm = hsum / cnt[:, None]
                dir_row = jnp.where(
                    (spg.attack == SC._COLLUDING)[:, None], d, hm)
                gagg = gagg + dir_w[:, None] * dir_row
            w_new = None if wg is None else wg - spg.alpha[:, None] * gagg
            return w_new, gagg

        return step

    def _scan_driver(self, one_round, eval_lane, finalize=None,
                     eval_prep=None):
        """Shared scan-over-rounds driver for both state representations.

        Key splitting, the FLTrainer.run eval schedule, and the
        (state, keys, t) carry are identical for the tree- and flat-state
        paths; only the per-round step (`one_round`), the per-lane eval view
        (`eval_lane`, None to skip eval; `eval_prep`, an optional state ->
        eval-rows mapping applied BEFORE the per-lane vmap — the
        model-sharded path gathers full rows there, keeping collectives out
        of the eval cond), and the final state -> stacked params mapping
        (`finalize`) differ.

        Returns (run, scan_chunk, finalize):

          run(state, keys, batches, sp)  — the monolithic program: one scan
              over all R rounds, returning the raw final state (finalize is
              composed OUTSIDE — by `_build`, after any shard_map — so the
              state -> params mapping never has to trace under the mesh).
          scan_chunk(state, keys, t0, rounds_total, batches, sp) — one chunk
              of the scan-of-chunks execution: the SAME scan body over a
              [C, ...] batch block starting at absolute round t0 of
              rounds_total, returning the raw (state, keys) carry for the
              next chunk.  t0/rounds_total are traced int32 scalars, so
              every full-size chunk shares one compile.
          finalize — the final state -> stacked-params mapping (None for the
              tree path, whose state already is the params pytree); applied
              once after the last chunk (or after the monolithic run).

        The monolithic run is scan_chunk at (t0=0, rounds_total=R), so the
        two execution modes share the per-round trace by construction — the
        chunked==monolithic equivalence contract reduces to lax.scan's own
        carry semantics.
        """
        eval_every = self.eval_every

        def eval_maybe(state, t, rounds):
            """eval_lane on the FLTrainer.run schedule; NaN off-schedule.
            The lax.cond skips the eval compute entirely on off-schedule
            rounds.  Metrics are cast to f32 so the NaN sentinel is
            representable (an integer metric would silently read as a
            plausible value).  eval_prep runs OUTSIDE the cond: its
            collectives (the model-sharded full-row gather) must execute
            unconditionally so every mesh shard agrees on the program."""
            if eval_lane is None:
                return {}
            with _layer(SCOPE_EVAL):
                rows = state if eval_prep is None else eval_prep(state)

                def as_f32(s_):
                    return jax.tree_util.tree_map(
                        lambda x: x.astype(jnp.float32),
                        jax.vmap(eval_lane)(s_))

                shapes = jax.eval_shape(as_f32, rows)
                blank = jax.tree_util.tree_map(
                    lambda s: jnp.full(s.shape, jnp.nan, s.dtype), shapes)
                due = (t == rounds - 1)
                if eval_every > 0:
                    due = due | (t % eval_every == 0)
                return jax.lax.cond(due, as_f32, lambda _: blank, rows)

        def scan_chunk(state, keys, t0, rounds_total, batches, sp):
            def body(carry, batch):
                state, keys, t = carry
                split = jax.vmap(jax.random.split)(keys)    # [S, 2, 2]
                keys, subs = split[:, 0], split[:, 1]
                state, loss, gn = one_round(state, batch, subs, sp)
                metrics = eval_maybe(state, t, rounds_total)
                return (state, keys, t + 1), (loss, gn, metrics)

            (state, keys, _), (loss, gn, metrics) = jax.lax.scan(
                body, (state, keys, t0), batches)
            return state, keys, loss, gn, metrics

        def run(state, keys, batches, sp):
            rounds = jax.tree_util.tree_leaves(batches)[0].shape[0]
            state, _, loss, gn, metrics = scan_chunk(
                state, keys, jnp.int32(0), jnp.int32(rounds), batches, sp)
            return state, loss, gn, metrics

        return run, scan_chunk, finalize

    def _flat_epilogue(self, unflatten_row, ms: Optional[_ModelShards]):
        """(eval_prep, eval_lane, finalize) for the flat-state builders.

        Without model sharding these are the historical mappings verbatim
        (eval_prep None).  With ms, eval gathers full real-D rows before
        the per-lane vmap (`eval_prep` — the h tuple element, when present,
        is dropped there, which eval never consumed anyway), and finalize —
        which `_build` composes OUTSIDE the shard_map, on the global
        [S, d_pad] state — slices the real columns before unflattening.
        """
        eval_fn = self.eval_fn
        any_markov = self.spec.any_markov
        if ms is not None:
            d = ms.d
            if any_markov:
                eval_prep = lambda st: ms.gather_cols(st[0])
                finalize = lambda st: jax.vmap(unflatten_row)(st[0][:, :d])
            else:
                eval_prep = ms.gather_cols
                finalize = lambda st: jax.vmap(unflatten_row)(st[:, :d])
            eval_lane = (None if eval_fn is None
                         else lambda wr: eval_fn(unflatten_row(wr)))
        elif any_markov:
            eval_prep = None
            eval_lane = (None if eval_fn is None
                         else lambda st: eval_fn(unflatten_row(st[0])))
            finalize = lambda st: jax.vmap(unflatten_row)(st[0])
        else:
            eval_prep = None
            eval_lane = (None if eval_fn is None
                         else lambda wr: eval_fn(unflatten_row(wr)))
            # The only unflatten outside the loss closure: once, at the end.
            finalize = jax.vmap(unflatten_row)
        return eval_prep, eval_lane, finalize

    def _make_run_grouped(self, sizes):
        """Tree-state path with grouped defense dispatch: the per-round
        structure of `_make_run`, but the [S, U, D] slab is processed as
        static per-family groups (lanes pre-gathered into LaneGroups
        execution order) — the analog group's combine and each digital
        family's kernel trace once over their own contiguous sub-slab, and
        the per-lane aggregates concatenate back in group order.  No
        `lax.switch`, no family traced for lanes that don't run it."""
        loss_fn, eval_fn = self.loss_fn, self.eval_fn
        u = self._u
        strict = self.strict_numerics
        local_slices = self._groups.local_slices
        analog_step = self._make_analog_step(grouped=True)
        kernels = self._digital_group_kernels()
        any_markov = self.spec.any_markov
        any_partial = self.spec.any_partial
        markov_update = self._make_markov_update() if any_markov else None
        part_draw = self._make_part_draw() if any_partial else None

        def one_round(state, batch, sub_s, sp: SC.ScenarioParams):
            params_s = state[0] if any_markov else state
            with _layer(SCOPE_GRADS):
                grads = jax.vmap(
                    lambda p: per_worker_grads(loss_fn, p, batch, u)[0]
                )(params_s)
                flat, unflatten = flatten_worker_grads(grads, batch_dims=2)
                if strict:
                    flat = jax.lax.optimization_barrier(flat)
            num = flat.shape[0]
            with _layer(SCOPE_COMBINE):
                if any_markov:
                    h_new, h_abs_all = markov_update(state[1], sub_s, sp)
                else:
                    h_new, h_abs_all = None, None
                part_all = part_draw(sub_s, sp) if any_partial else None
            parts = []
            for code, start, end in local_slices:
                analog = code == SC._FLOA_CODE
                with _layer(SCOPE_COMBINE if analog else SCOPE_SCREEN):
                    sl = slice(start, end)
                    fg = flat[sl]
                    spg = jax.tree_util.tree_map(lambda x: x[sl], sp)
                    part_g = None if part_all is None else part_all[sl]
                    if analog:
                        if strict:
                            gbar_i, eps2_i = jax.vmap(
                                lambda g: S.flat_scalar_stats(g, sizes))(fg)
                        else:
                            grads_g = jax.tree_util.tree_map(
                                lambda x: x[sl], grads)
                            gbar_i, eps2_i = jax.vmap(
                                S.per_worker_scalar_stats)(grads_g)
                        _, gagg_g = analog_step(
                            None, fg, sub_s[sl], spg, gbar_i, eps2_i,
                            part=part_g,
                            h_abs=(None if h_abs_all is None
                                   else h_abs_all[sl]))
                    else:
                        flipped = _digital_flip(fg, spg)
                        if any_partial:
                            gagg_g = kernels[code](flipped, spg.def_trim,
                                                   spg.def_f, spg.def_multi,
                                                   part_g)
                        else:
                            gagg_g = kernels[code](flipped, spg.def_trim,
                                                   spg.def_f, spg.def_multi)
                parts.append(gagg_g)
            # The concatenation and the one PS update of every group's
            # lanes stay unscoped: they belong to no single layer.
            gagg_flat = jnp.concatenate(parts, axis=0)

            gagg = unflatten(gagg_flat)
            new_params = jax.tree_util.tree_map(
                lambda p, g: p - (sp.alpha.reshape((num,) + (1,) * (p.ndim - 1))
                                  * g).astype(p.dtype),
                params_s, gagg)
            with _layer(SCOPE_LOSS):
                gn = jnp.sqrt(jnp.sum(jnp.square(gagg_flat), axis=-1))
                loss = jax.vmap(lambda p: loss_fn(p, batch))(new_params)
            new_state = (new_params, h_new) if any_markov else new_params
            return new_state, loss, gn

        if any_markov:
            eval_lane = (None if eval_fn is None
                         else lambda st: eval_fn(st[0]))
            return self._scan_driver(one_round, eval_lane,
                                     finalize=lambda st: st[0])
        return self._scan_driver(one_round, eval_fn)

    def _make_run_flat_grouped(self, unflatten_row, sizes):
        """Flat-state warm path with grouped defense dispatch.

        The carry stays one [S, D] matrix; per round, each group's lanes
        take exactly their family's compute on a contiguous sub-slab of the
        [S, U, D] gradient block — the analog group keeps the fused
        `batched_floa_step`, digital groups run their kernel and the plain
        PS update — and the per-group (w_new, gagg) slices concatenate back
        in the (static) group order.  Under a mesh the group layout is
        shard-uniform (`build_lane_groups(shards=...)`), so the same static
        slicing serves every device of the shard_mapped scan.
        """
        loss_fn, eval_fn = self.loss_fn, self.eval_fn
        u = self._u
        strict = self.strict_numerics
        local_slices = self._groups.local_slices
        has_analog = any(c == SC._FLOA_CODE for c, _, _ in local_slices)
        # Worker sharding: strict mode all-gathers the full slab up front
        # and replays the unsharded reduction order verbatim (bitwise
        # contract); the default keeps the slab local and distributes the
        # combine as a psum.  Model sharding follows the same rule over the
        # column axis: strict gathers full rows and re-slices only the
        # carry; the default runs the combine / stats / column-wise screens
        # on each shard's local column block.
        ws = self._ws
        ws_run = None if strict else ws
        ms = self._ms
        ms_run = None if strict else ms
        analog_step = self._make_analog_step(ws_run, grouped=True, ms=ms_run)
        kernels = self._digital_group_kernels()
        any_markov = self.spec.any_markov
        any_partial = self.spec.any_partial
        markov_update = self._make_markov_update() if any_markov else None
        part_draw = self._make_part_draw() if any_partial else None

        def flat_loss(w_row, batch):
            return loss_fn(unflatten_row(w_row), batch)

        def group_step(code, sl, w, grads, sub_s, sp, part_all, h_abs_all):
            """One group's (w_new, gagg) on its lane slice `sl`: the analog
            leg (fused PS update), or the family's screen and its update."""
            wg, fg = w[sl], grads[sl]
            spg = jax.tree_util.tree_map(lambda x: x[sl], sp)
            part_g = None if part_all is None else part_all[sl]
            if code == SC._FLOA_CODE:
                if strict:
                    gbar_i, eps2_i = jax.vmap(
                        lambda g: S.flat_scalar_stats(g, sizes))(fg)
                elif ms_run is not None:
                    # Shard-local partial sums -> two scalar psums per
                    # worker over "model" (ghost columns contribute exactly
                    # 0.0); the shared epilogue recovers the full-row
                    # stats.  See standardize.flat_partial_stats for the fp
                    # contract (rtol vs the single-sum path).
                    s1, s2 = S.flat_partial_stats(fg)
                    s1 = jax.lax.psum(s1, "model")
                    s2 = jax.lax.psum(s2, "model")
                    gbar_i, eps2_i = S.stats_from_partials(s1, s2, ms.d)
                    if ws_run is not None:
                        gbar_i, eps2_i = ws.gather_stats(gbar_i, eps2_i)
                else:
                    gbar_i, eps2_i = jax.vmap(
                        lambda g: S.flat_scalar_stats(g))(fg)
                    if ws_run is not None:
                        gbar_i, eps2_i = ws.gather_stats(gbar_i, eps2_i)
                return analog_step(
                    wg, fg, sub_s[sl], spg, gbar_i, eps2_i, part=part_g,
                    h_abs=None if h_abs_all is None else h_abs_all[sl])
            fg_full = ws.gather_slab(fg) if ws_run is not None else fg
            # Column-wise screens (mean/median/trimmed-mean) are
            # per-coordinate over the worker axis, so they run on the local
            # column block as-is; row-geometry screens (Krum family,
            # geometric median) score whole rows by pairwise distance and
            # need the full columns gathered.
            row_geo = ms_run is not None and code not in DEF.COLUMNWISE_CODES
            if row_geo:
                fg_full = ms.gather_cols(fg_full)
            flipped = _digital_flip(fg_full, spg)
            if any_partial:
                gagg_g = kernels[code](flipped, spg.def_trim, spg.def_f,
                                       spg.def_multi, part_g)
            else:
                gagg_g = kernels[code](flipped, spg.def_trim, spg.def_f,
                                       spg.def_multi)
            if row_geo:
                gagg_g = ms.local_cols(gagg_g)
            elif ms_run is not None:
                # Column-wise outputs on all-zero ghost columns are zero in
                # exact arithmetic; the mask makes the invariant
                # unconditional (bitwise identity on real columns).
                gagg_g = jnp.where(ms.col_mask(), gagg_g, 0.0)
            return wg - spg.alpha[:, None] * gagg_g, gagg_g

        def one_round(state, batch, sub_s, sp: SC.ScenarioParams):
            w = state[0] if any_markov else state
            with _layer(SCOPE_GRADS):
                if ms is not None:
                    # Gradients always come off the FULL real-D rows: the
                    # gather reconstructs exactly the unsharded row values,
                    # so the per-worker grad trace is the unsharded
                    # engine's.  Strict mode then keeps everything
                    # full-width (re-slicing only the carry at the end —
                    # the bitwise contract); the default re-slices the slab
                    # to this shard's columns.
                    w = ms.gather_cols(w)
                if ws is None:
                    grads = jax.vmap(
                        lambda wr: per_worker_grads(flat_loss, wr, batch, u)[0]
                    )(w)  # [S, U, D]
                else:
                    lb = ws.local_batch(batch)
                    grads = jax.vmap(
                        lambda wr: per_worker_grads(flat_loss, wr, lb,
                                                    ws.u_loc)[0]
                    )(w)  # [S, u_loc, D]
                    if strict:
                        grads = ws.gather_slab(grads)
                if ms_run is not None:
                    grads = ms.local_cols(grads)
                    w = state[0] if any_markov else state  # back to local cols
                if strict and has_analog:
                    grads = jax.lax.optimization_barrier(grads)
            with _layer(SCOPE_COMBINE):
                if any_markov:
                    h_new, h_abs_all = markov_update(state[1], sub_s, sp)
                else:
                    h_new, h_abs_all = None, None
                part_all = part_draw(sub_s, sp) if any_partial else None
            w_parts, g_parts = [], []
            for code, start, end in local_slices:
                analog = code == SC._FLOA_CODE
                with _layer(SCOPE_COMBINE if analog else SCOPE_SCREEN):
                    w_new_g, gagg_g = group_step(
                        code, slice(start, end), w, grads, sub_s, sp,
                        part_all, h_abs_all)
                w_parts.append(w_new_g)
                g_parts.append(gagg_g)
            # The group concatenation stays unscoped: no single layer's.
            w_new = jnp.concatenate(w_parts, axis=0)
            gagg = jnp.concatenate(g_parts, axis=0)
            with _layer(SCOPE_LOSS):
                if ms_run is not None:
                    gn = jnp.sqrt(jax.lax.psum(
                        jnp.sum(jnp.square(gagg), axis=-1), "model"))
                    loss = jax.vmap(lambda wr: flat_loss(wr, batch))(
                        ms.gather_cols(w_new))
                else:
                    gn = jnp.sqrt(jnp.sum(jnp.square(gagg), axis=-1))
                    loss = jax.vmap(lambda wr: flat_loss(wr, batch))(w_new)
            if ms is not None and strict:
                w_new = ms.local_cols(w_new)
            new_state = (w_new, h_new) if any_markov else w_new
            return new_state, loss, gn

        eval_prep, eval_lane, finalize = self._flat_epilogue(
            unflatten_row, ms)
        return self._scan_driver(one_round, eval_lane, finalize=finalize,
                                 eval_prep=eval_prep)

    def _make_run(self, sizes):
        """PR-1 tree-state path: params stay a pytree; every round pays the
        [S, U, D] flatten/concat and a per-leaf unflatten + update.

        By default this is the PR-1 engine verbatim (pytree stats, then
        flatten) — the honest benchmark baseline.  strict_numerics swaps the
        stats for the barrier + leaf-segmented reduction off the flattened
        slab, pinning the fp reduction tree both engines use so the
        flat-state path can match it bitwise."""
        loss_fn, eval_fn = self.loss_fn, self.eval_fn
        u = self._u
        strict = self.strict_numerics
        all_digital = self.spec.all_digital
        digital_select = (self._make_digital_select()
                          if self.spec.any_digital else None)
        analog_step = self._make_analog_step()
        any_markov = self.spec.any_markov
        any_partial = self.spec.any_partial
        markov_update = self._make_markov_update() if any_markov else None
        part_draw = self._make_part_draw() if any_partial else None

        def one_round(state, batch, sub_s, sp: SC.ScenarioParams):
            params_s = state[0] if any_markov else state
            # 1. per-worker local SGD gradients, per scenario: leaves [S, U, ...]
            with _layer(SCOPE_GRADS):
                grads = jax.vmap(
                    lambda p: per_worker_grads(loss_fn, p, batch, u)[0]
                )(params_s)
            with _layer(SCOPE_COMBINE):
                if any_markov:
                    h_new, h_abs = markov_update(state[1], sub_s, sp)
                else:
                    h_new, h_abs = None, None
                part = part_draw(sub_s, sp) if any_partial else None

            if all_digital:
                # No analog leg to trace (mirrors the flat-state path, so
                # strict_numerics stays bitwise across representations).
                with _layer(SCOPE_GRADS):
                    flat, unflatten = flatten_worker_grads(grads,
                                                           batch_dims=2)
                num = flat.shape[0]
                with _layer(SCOPE_SCREEN):
                    gagg_flat = digital_select(None, flat, sp, part)
            else:
                # 2. scalar-stat standardization handshake.
                if strict:
                    # Barrier first: stats reduce from the materialized slab
                    # (needed by the combine anyway), bit-matching the strict
                    # flat-state path.
                    with _layer(SCOPE_GRADS):
                        flat, unflatten = flatten_worker_grads(
                            grads, batch_dims=2)
                        flat = jax.lax.optimization_barrier(flat)
                    with _layer(SCOPE_COMBINE):
                        gbar_i, eps2_i = jax.vmap(
                            lambda g: S.flat_scalar_stats(g, sizes))(flat)
                else:
                    with _layer(SCOPE_COMBINE):
                        gbar_i, eps2_i = jax.vmap(
                            S.per_worker_scalar_stats)(grads)
                    with _layer(SCOPE_GRADS):
                        flat, unflatten = flatten_worker_grads(
                            grads, batch_dims=2)
                num = flat.shape[0]
                # 3+4. channel draw + coefficients + OTA combine (+ jam +
                # directional cohort), the shared analog leg; wg=None keeps
                # the two-step route the tree update needs.
                with _layer(SCOPE_COMBINE):
                    _, gagg_flat = analog_step(None, flat, sub_s, sp,
                                               gbar_i, eps2_i,
                                               part=part, h_abs=h_abs)
                if digital_select is not None:
                    # Defense lanes override the analog combine with their
                    # screening defense on the same (already materialized) slab.
                    with _layer(SCOPE_SCREEN):
                        gagg_flat = digital_select(gagg_flat, flat, sp, part)

            # 5. PS update w <- w - alpha * gagg (per-scenario alpha), in
            # the layer whose aggregate it applies.
            with _layer(SCOPE_SCREEN if digital_select is not None
                        else SCOPE_COMBINE):
                gagg = unflatten(gagg_flat)
                new_params = jax.tree_util.tree_map(
                    lambda p, g: p - (sp.alpha.reshape(
                        (num,) + (1,) * (p.ndim - 1)) * g).astype(p.dtype),
                    params_s, gagg)

            with _layer(SCOPE_LOSS):
                gn = jnp.sqrt(jnp.sum(jnp.square(gagg_flat), axis=-1))
                loss = jax.vmap(lambda p: loss_fn(p, batch))(new_params)
            new_state = (new_params, h_new) if any_markov else new_params
            return new_state, loss, gn

        if any_markov:
            eval_lane = (None if eval_fn is None
                         else lambda st: eval_fn(st[0]))
            return self._scan_driver(one_round, eval_lane,
                                     finalize=lambda st: st[0])
        return self._scan_driver(one_round, eval_fn)

    def _make_run_flat(self, unflatten_row, sizes):
        """Flat-state warm path: the carry is one [S, D] f32 matrix.

        The pytree boundary lives inside `flat_loss` only — its grad
        transpose assembles the per-worker gradients straight into the
        [S, U, D] block the combine consumes, and `batched_floa_step` fuses
        the PS update into the same pass, so no per-round concat, unflatten,
        or per-leaf update survives in the compiled scan body.
        """
        loss_fn, eval_fn = self.loss_fn, self.eval_fn
        u = self._u
        strict = self.strict_numerics
        any_jam = self.spec.any_jamming
        any_dir = self.spec.any_directional
        all_digital = self.spec.all_digital
        digital_select = (self._make_digital_select()
                          if self.spec.any_digital else None)
        # Worker sharding: strict mode (and the all-digital short-circuit,
        # whose defenses are order statistics over the full worker axis)
        # all-gathers the slab right after the local gradient pass and then
        # runs the unsharded math verbatim; the default keeps the slab local
        # — scalar stats all-gather, the OTA combine psums.  Model sharding
        # follows the same split over the column axis (the all-digital and
        # mixed-select legs keep full columns for the lax.switch selector —
        # it may contain row-geometry screens — and re-slice its output).
        ws = self._ws
        ws_run = None if strict else ws
        ms = self._ms
        ms_run = None if strict else ms
        analog_step = self._make_analog_step(ws_run, ms=ms_run)
        # Jamming and the directional cohort land AFTER the combine (neither
        # fuses into `batched_floa_step`), and defense lanes select their
        # screening aggregate before the update — those sweeps take the
        # two-step route; pure-FLOA sweeps keep the fused combine + update.
        fused = not (any_jam or any_dir or digital_select is not None
                     or ws_run is not None)
        any_markov = self.spec.any_markov
        any_partial = self.spec.any_partial
        markov_update = self._make_markov_update() if any_markov else None
        part_draw = self._make_part_draw() if any_partial else None

        def flat_loss(w_row, batch):
            return loss_fn(unflatten_row(w_row), batch)

        def one_round(state, batch, sub_s, sp: SC.ScenarioParams):
            w_loc = state[0] if any_markov else state
            # Under model sharding gradients always come off the FULL
            # real-D rows — the gather reconstructs exactly the unsharded
            # row values, so the per-worker grad trace is the unsharded
            # engine's.  `w` below is the width the round's update math
            # runs at: full columns in strict mode (re-slicing only the
            # carry — the bitwise contract), local columns otherwise.
            with _layer(SCOPE_GRADS):
                w_full = ms.gather_cols(w_loc) if ms is not None else w_loc
                # 1. per-worker gradients, already flat: [S, U, D] (the
                # local [S, u_loc, D] slice under worker sharding).
                if ws is None:
                    grads = jax.vmap(
                        lambda wr: per_worker_grads(flat_loss, wr, batch, u)[0]
                    )(w_full)
                else:
                    lb = ws.local_batch(batch)
                    grads = jax.vmap(
                        lambda wr: per_worker_grads(flat_loss, wr, lb,
                                                    ws.u_loc)[0]
                    )(w_full)
                    if strict or all_digital:
                        grads = ws.gather_slab(grads)
            w = w_loc if ms_run is not None else w_full
            with _layer(SCOPE_COMBINE):
                if any_markov:
                    h_new, h_abs = markov_update(state[1], sub_s, sp)
                else:
                    h_new, h_abs = None, None
                part = part_draw(sub_s, sp) if any_partial else None

            def outputs(w_new, gagg):
                """gn / loss / carry epilogue, shared by every leg.  With
                local columns the squared norm psums over "model" and the
                loss reads gathered rows; strict model sharding computed
                full-width and re-slices only the carry."""
                with _layer(SCOPE_LOSS):
                    if ms_run is not None:
                        gn = jnp.sqrt(jax.lax.psum(
                            jnp.sum(jnp.square(gagg), axis=-1), "model"))
                        loss = jax.vmap(lambda wr: flat_loss(wr, batch))(
                            ms.gather_cols(w_new))
                    else:
                        gn = jnp.sqrt(jnp.sum(jnp.square(gagg), axis=-1))
                        loss = jax.vmap(lambda wr: flat_loss(wr, batch))(
                            w_new)
                if ms is not None and strict:
                    w_new = ms.local_cols(w_new)
                new_state = (w_new, h_new) if any_markov else w_new
                return new_state, loss, gn

            # All-digital sweeps skip the analog leg entirely (stats,
            # channel draw, coefficients, combine — their outputs would be
            # discarded by the defense select anyway, and XLA cannot DCE
            # through the per-lane jnp.where).  The selector always sees
            # full columns (grads were never column-sliced on this leg);
            # its output re-slices local, ghost columns exact zeros.
            if all_digital:
                with _layer(SCOPE_SCREEN):
                    gagg = digital_select(None, grads, sp, part)
                    if ms_run is not None:
                        gagg = ms.local_cols(gagg)
                    w_new = w - sp.alpha[:, None] * gagg
                return outputs(w_new, gagg)

            if ms_run is not None:
                with _layer(SCOPE_GRADS):
                    grads = ms.local_cols(grads)

            # 2. standardization handshake.  strict_numerics pins the fp
            # reduction tree to the tree-state path's (materialization
            # barrier + leaf-segmented sums) so the two engines agree
            # bitwise; the default lets XLA fuse the whole-row reduction
            # into the gradient producer — one less pass over the slab, at
            # the price of ulp-level stat differences.
            if strict:
                with _layer(SCOPE_GRADS):
                    grads = jax.lax.optimization_barrier(grads)
            with _layer(SCOPE_COMBINE):
                if strict:
                    gbar_i, eps2_i = jax.vmap(
                        lambda g: S.flat_scalar_stats(g, sizes))(grads)
                elif ms_run is not None:
                    # Shard-local partial sums -> two scalar psums per
                    # worker over "model" (ghost columns contribute exactly
                    # 0.0); see standardize.flat_partial_stats for the fp
                    # contract.
                    s1, s2 = S.flat_partial_stats(grads)
                    s1 = jax.lax.psum(s1, "model")
                    s2 = jax.lax.psum(s2, "model")
                    gbar_i, eps2_i = S.stats_from_partials(s1, s2, ms.d)
                    if ws_run is not None:
                        gbar_i, eps2_i = ws.gather_stats(gbar_i, eps2_i)
                else:
                    gbar_i, eps2_i = jax.vmap(
                        lambda g: S.flat_scalar_stats(g))(grads)
                    if ws_run is not None:
                        # Local per-worker scalars -> full [S, U]: the
                        # global mean then reduces the same vector the
                        # unsharded engine reduces (bitwise-equal stats).
                        gbar_i, eps2_i = ws.gather_stats(gbar_i, eps2_i)

                # 3+4(+5). the shared analog leg: channel draw +
                # coefficients + OTA combine (+ jam + directional cohort);
                # with wg given it fuses the PS update too.
                w_new, gagg = analog_step(w if fused else None, grads, sub_s,
                                          sp, gbar_i, eps2_i,
                                          part=part, h_abs=h_abs)
                if not fused and digital_select is None:
                    w_new = w - sp.alpha[:, None] * gagg
            if not fused and digital_select is not None:
                with _layer(SCOPE_SCREEN):
                    slab = (ws.gather_slab(grads) if ws_run is not None
                            else grads)
                    if ms_run is not None:
                        # The switch selector may contain row-geometry
                        # screens: feed it full columns, slice its output,
                        # and merge with the (local) analog aggregate —
                        # replicating the selector's own defense==0 merge.
                        slab = ms.gather_cols(slab)
                        dig = ms.local_cols(
                            digital_select(None, slab, sp, part))
                        gagg = jnp.where((sp.defense == 0)[:, None],
                                         gagg, dig)
                    else:
                        gagg = digital_select(gagg, slab, sp, part)
                    # The select merges every lane's aggregate: the one
                    # PS update follows it.
                    w_new = w - sp.alpha[:, None] * gagg

            return outputs(w_new, gagg)

        eval_prep, eval_lane, finalize = self._flat_epilogue(
            unflatten_row, ms)
        return self._scan_driver(one_round, eval_lane, finalize=finalize,
                                 eval_prep=eval_prep)

    def _build(self, template):
        """Compile-cache the run programs (lazy: needs the params template).

        Both execution modes are wrapped here — the monolithic all-R scan
        (`_run_jit`) and the per-chunk scan (`_chunk_jit`, plus the one-shot
        `_finalize_jit` applied after the last chunk) — but jit compiles on
        first call, so an engine only ever pays for the mode it runs."""
        self._template = template
        unflatten_row, sizes = make_row_unflatten(template)
        # Model-shard arithmetic needs D (= sum of the template leaf
        # sizes), so it is born here rather than in __init__.
        self._ms = (_ModelShards(sum(sizes), self.plan.model_shards)
                    if self.plan.model_sharded else None)
        if self.flat_state:
            run, chunk, final = (
                self._make_run_flat_grouped(unflatten_row, sizes)
                if self._groups is not None
                else self._make_run_flat(unflatten_row, sizes))
        else:
            run, chunk, final = (
                self._make_run_grouped(sizes)
                if self._groups is not None else self._make_run(sizes))
        if self.mesh is not None:
            # Prefix specs: lane axis 0 on state/keys/ScenarioParams, lane
            # axis 1 on the [R, S]-stacked scan outputs, batches replicated.
            # A mesh without a "data" axis (pure worker sharding) keeps
            # every operand replicated over the mesh — only the scan body's
            # own all_gather/psum collectives distribute work.  With a
            # "model" axis the flat [S, D(+pad)] state additionally splits
            # its column axis (the Markov `h` tuple element stays
            # lane-only: its worker axis is never column-sharded); loss /
            # grad-norm / metrics come out replicated over "model" — every
            # shard computes them from psummed or gathered full rows.
            has_data = "data" in self.mesh.axis_names
            lane = P("data") if has_data else P()
            lane_t = P(None, "data") if has_data else P()
            rep = P()
            if "model" in self.mesh.axis_names:
                w_spec = P("data" if has_data else None, "model")
                state_spec = ((w_spec, lane) if self.spec.any_markov
                              else w_spec)
            else:
                state_spec = lane
            run = jax.shard_map(
                run, mesh=self.mesh,
                in_specs=(state_spec, lane, rep, lane),
                out_specs=(state_spec, lane_t, lane_t, lane_t),
                check_vma=False)
            # The chunk program additionally threads the raw (state, keys)
            # carry out (lane-sharded) and takes the replicated scalar
            # t0 / rounds_total pair; finalize runs OUTSIDE the shard_map
            # (vmap over lanes, sharding propagates through jit).
            chunk = jax.shard_map(
                chunk, mesh=self.mesh,
                in_specs=(state_spec, lane, rep, rep, rep, lane),
                out_specs=(state_spec, lane, lane_t, lane_t, lane_t),
                check_vma=False)
        if final is None:
            self._run_jit = jax.jit(_named(run, "sweep_run"))
        else:
            # finalize composes OUTSIDE any shard_map but INSIDE the same
            # jit — it is pure layout (slice/reshape/astype), so the
            # monolithic program's results are unchanged, and under a
            # "model" mesh it sees the global [S, d_pad] state to slice.
            def sweep_run(state, keys, batches, sp, _run=run, _final=final):
                st, loss, gn, metrics = _run(state, keys, batches, sp)
                return _final(st), loss, gn, metrics

            self._run_jit = jax.jit(sweep_run)
        self._chunk_jit = jax.jit(_named(chunk, "sweep_chunk"))
        self._finalize_jit = (None if final is None
                              else jax.jit(_named(final, "sweep_finalize")))

    # ----------------------------------------------------- chunked execution

    def _resume_extra(self, rounds: int) -> dict:
        """The validation fingerprint a resume checkpoint carries: every
        quantity the restored carry is only valid for verbatim."""
        return {"resume_version": _RESUME_VERSION,
                "rounds_total": int(rounds),
                "chunk_rounds": int(self.chunk_rounds),
                "exec_lanes": int(self._num + self._pad),
                "eval_every": int(self.eval_every),
                "model_shards": int(self.plan.model_shards),
                "names": list(self.spec.names)}

    def _save_checkpoint(self, t_next, rounds, state, keys,
                         losses, gns, metric_blocks) -> None:
        """Snapshot the full resume carry at a chunk boundary: execution-
        order (permuted/padded) state — the Markov `h` tuple element rides
        along as an ordinary pytree leaf — the key schedule, and the
        host-side trajectory blocks accumulated so far.  Step index =
        rounds completed.  Multi-process: the fetch edge is a COLLECTIVE
        (process_allgather for lane-sharded arrays on a process-spanning
        mesh), so EVERY process builds the host-side tree — only the
        filesystem write is process 0's."""
        tree = {
            "carry": {
                "state": jax.tree_util.tree_map(_fetch, state),
                "keys": _fetch(keys),
            },
            "blocks": {
                "loss": np.concatenate([_fetch(x) for x in losses]),
                "grad_norm": np.concatenate([_fetch(x) for x in gns]),
                "metrics": {
                    k: np.concatenate([_fetch(m[k]) for m in metric_blocks])
                    for k in (metric_blocks[0] if metric_blocks else {})},
            },
        }
        if jax.process_index() != 0:
            return
        extra = self._resume_extra(rounds)
        extra["t_next"] = int(t_next)
        CKPT.save_pytree(self.checkpoint_dir, int(t_next), tree, extra=extra)

    def _restore_checkpoint(self, rounds, state, keys):
        """Load the latest committed resume checkpoint, validate its
        manifest against this engine/run, and refit the saved carry onto
        the freshly-built (state, keys) structures.  Returns
        (t_start, state, keys, losses, gns, metric_blocks) — t_start = 0
        with the fresh carry when no checkpoint exists yet (so
        `resume=True` is safe on the very first launch)."""
        step = CKPT.latest_step(self.checkpoint_dir)
        if jax.process_count() > 1:
            # Only process 0 writes, so its directory view is the
            # authoritative one: broadcast its latest committed step and
            # resume every process from that SAME boundary.  Without this
            # a mid-write race (or a non-shared filesystem) would leave
            # ranks at different t_start, dispatching different numbers
            # of chunk programs and hanging on mismatched collectives.
            from jax.experimental import multihost_utils
            step = int(multihost_utils.broadcast_one_to_all(
                np.int64(-1 if step is None else step)))
            step = None if step < 0 else step
        if step is None:
            return 0, state, keys, [], [], []
        try:
            saved, meta = CKPT.restore_pytree(self.checkpoint_dir, step)
        except FileNotFoundError as e:
            raise FileNotFoundError(
                f"process {jax.process_index()} cannot read resume "
                f"checkpoint step {step} from {self.checkpoint_dir!r}: "
                f"multi-process resume requires checkpoint_dir on a "
                f"filesystem shared by every process (process 0 writes, "
                f"the rest read)") from e
        ex = meta.get("extra", {})
        want = self._resume_extra(rounds)
        got = {k: ex.get(k) for k in want}
        if got != want:
            mismatch = sorted(k for k in want if got[k] != want[k])
            raise ValueError(
                f"resume checkpoint step {step} in "
                f"{self.checkpoint_dir!r} was written by an incompatible "
                f"run: manifest keys {mismatch} differ (checkpoint "
                f"{ {k: got[k] for k in mismatch} } vs engine "
                f"{ {k: want[k] for k in mismatch} })")
        t_start = int(ex["t_next"])
        # Refit the path-rebuilt carry onto this run's exact container
        # structure (tuples — the Markov (w, h) carry — come back from the
        # manifest as lists; leaves are byte-exact, so the refit is purely
        # structural and the resumed trajectory stays bitwise).
        def refit(template, rebuilt):
            return jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(template),
                jax.tree_util.tree_leaves(rebuilt))

        # The restored carry is host data staged onto the device, like the
        # batches: it counts toward H2D_BYTES.
        self._h2d_bytes += sum(np.asarray(x).nbytes for x in
                               jax.tree_util.tree_leaves(saved["carry"]))
        state = refit(state, saved["carry"]["state"])
        keys = jnp.asarray(saved["carry"]["keys"])
        if self.mesh is not None:
            lane = lane_sharding(self.mesh)
            if self._ms is not None:
                # Same model-aware placement as run(): the saved carry was
                # fetched at the padded width, so it re-lands column-sharded.
                wsh = sweep_state_sharding(self.mesh)
                if self.spec.any_markov:
                    state = (put_with_sharding(state[0], wsh),
                             put_with_sharding(state[1], lane))
                else:
                    state = put_with_sharding(state, wsh)
            else:
                state = jax.tree_util.tree_map(
                    lambda x: put_with_sharding(x, lane), state)
            keys = put_with_sharding(keys, lane)
        blocks = saved["blocks"]
        return (t_start, state, keys, [blocks["loss"]],
                [blocks["grad_norm"]], [blocks.get("metrics", {})])

    def _run_chunked(self, state, keys, batches, sp, resume: bool = False):
        """Outer loop of the scan-of-chunks execution: dispatch the compiled
        C-round chunk program once per [C, ...] block, thread the
        (state, keys, absolute-round-offset) carry through the boundaries,
        finalize once after the last chunk.

        With async_staging the next block is sliced + `device_put` right
        after the current chunk is dispatched (both are async), so block
        k+1's host->device transfer overlaps chunk k's device compute;
        without it each block is staged synchronously just before its own
        chunk.  Staging order is the ONLY difference between the modes — the
        dispatched programs and operands are identical, so their results
        are bit-identical.

        checkpoint_dir (plan) snapshots the resume carry after every
        checkpoint_every_chunks-th chunk boundary (never after the final
        chunk — the run is about to return); resume=True restores the
        latest snapshot and dispatches only the remaining chunks.  A
        resumed run replays the exact jitted chunk program on a byte-exact
        carry from an on-schedule boundary, so it is bit-identical to the
        uninterrupted run (pinned in tests/test_sweep_resume.py).
        """
        rounds = jax.tree_util.tree_leaves(batches)[0].shape[0]
        if rounds == 0:
            # Zero chunks would leave nothing to concatenate; the monolithic
            # program handles the degenerate stack (lax.scan over length-0
            # xs yields empty [0, S] outputs), keeping chunked == monolithic
            # for every input.
            with _span("sweep.dispatch"):
                return self._run_jit(state, keys, batches, sp)
        t_start = 0
        losses, gns, metric_blocks = [], [], []
        if resume:
            with _span("sweep.checkpoint"):
                t_start, state, keys, losses, gns, metric_blocks = \
                    self._restore_checkpoint(rounds, state, keys)
        rounds_total = jnp.int32(rounds)
        # Checkpoints land only on chunk boundaries, so t_start is a
        # multiple of chunk_rounds and the remaining blocks slice exactly
        # like the uninterrupted run's (numpy views, nothing copied).
        remaining = jax.tree_util.tree_map(lambda x: x[t_start:], batches)
        blocks = iter_chunk_blocks(remaining, self.chunk_rounds)

        def stage():
            with _span("sweep.stage"):
                blk = next(blocks, None)
                return (None if blk is None else self._staged(
                    blk, stage_batch_block(blk, mesh=self.mesh)))

        nxt = stage() if self.async_staging else None
        every = self.checkpoint_every_chunks
        for i, t0 in enumerate(range(t_start, rounds, self.chunk_rounds)):
            block = nxt if self.async_staging else stage()
            with _span("sweep.dispatch"):
                state, keys, loss, gn, metrics = self._chunk_jit(
                    state, keys, jnp.int32(t0), rounds_total, block, sp)
            if self.async_staging:
                nxt = stage()   # overlaps the in-flight chunk dispatched above
            losses.append(loss)
            gns.append(gn)
            metric_blocks.append(metrics)
            t_next = min(t0 + self.chunk_rounds, rounds)
            if (self.checkpoint_dir is not None and t_next < rounds
                    and (i + 1) % every == 0):
                with _span("sweep.checkpoint"):
                    self._save_checkpoint(t_next, rounds, state, keys,
                                          losses, gns, metric_blocks)

        if self._finalize_jit is None:
            params = state
        else:
            with _span("sweep.dispatch"):
                params = self._finalize_jit(state)
        # Host-side concat along the round axis: per-chunk outputs are
        # [C, S_exec]; the caller's scatter-back/ghost-drop sees the same
        # [R, S_exec] layout the monolithic scan produces.
        with _span("sweep.fetch"):
            loss = np.concatenate([_fetch(x) for x in losses])
            gn = np.concatenate([_fetch(x) for x in gns])
            metrics = {
                k: np.concatenate([_fetch(m[k]) for m in metric_blocks])
                for k in (metric_blocks[0] if metric_blocks else {})}
        return params, loss, gn, metrics

    # ----------------------------------------------------------------- run

    def run(self, params0, batches, keys: Optional[Array] = None,
            params_stacked: bool = False, resume: bool = False
            ) -> SweepResult:
        """params0: single init pytree, broadcast to all lanes (or pass
        params_stacked=True for leaves already carrying a leading S axis).
        batches: pytree of [R, ...] arrays shared by every scenario.

        resume=True (requires plan.checkpoint_dir) restores the latest
        committed chunk-boundary checkpoint and runs only the remaining
        chunks; the result is bit-identical to the uninterrupted run.  With
        no checkpoint on disk yet it is a fresh run, so a preemptible loop
        can pass resume=True unconditionally.  params0/batches/keys must be
        the original run's (the manifest pins rounds, chunking, lane names,
        and the eval schedule, and raises on mismatch — but the carry can
        only be bitwise-valid for the original inputs)."""
        if resume and self.checkpoint_dir is None:
            raise ValueError(
                "resume=True needs a checkpoint to restore: construct the "
                "engine with plan=ExecutionPlan(checkpoint_dir=..., "
                "chunk_rounds=...)")
        index, self._runs = self._runs, self._runs + 1
        rounds = jax.tree_util.tree_leaves(batches)[0].shape[0]
        self._h2d_bytes = 0
        # One host span per phase, nested in sweep.run: stage the host
        # inputs, prepare the execution-order carry (and build the programs
        # on the first run), stage it onto the mesh, dispatch, fetch.
        with _span("sweep.run", run=index, lanes=self._num, rounds=rounds):
            with _span("sweep.stage"):
                params0 = self._staged(
                    params0, jax.tree_util.tree_map(jnp.asarray, params0))
                if keys is not None:
                    keys = self._staged(keys, jnp.asarray(keys))
                if self.chunk_rounds is None:
                    batches = self._staged(
                        batches, jax.tree_util.tree_map(jnp.asarray, batches))
                else:
                    # Chunked execution stages [C, ...] blocks per chunk; the
                    # full [R, ...] stack stays host-side (numpy views slice
                    # for free and the device never holds more than ~two
                    # blocks).
                    batches = jax.tree_util.tree_map(np.asarray, batches)

            with _span("sweep.prepare"):
                if not params_stacked:
                    params0 = stack_params(params0, self._num)
                if keys is None:
                    keys = self.spec.keys()
                template = jax.eval_shape(
                    lambda p: jax.tree_util.tree_map(lambda x: x[0], p),
                    params0)
                if self._run_jit is None or template != self._template:
                    self._build(template)

                num, total = self._num, self._num + self._pad
                if self.flat_state:
                    state, _ = flatten_worker_grads(params0, batch_dims=1)
                    if self._ms is not None:
                        # Model sharding: zero-pad D to shards * d_loc ONCE,
                        # pre-jit; ghost columns stay exactly zero for the
                        # whole run (the scan body re-masks every aggregate).
                        # pad_cols acts on the last axis so it commutes with
                        # the lane permute/pad below (axis 0).
                        state = self._ms.pad_cols(state)
                else:
                    state = params0
                if self.spec.any_markov:
                    # Gauss-Markov fading: the scan carry grows a [S, U, 2]
                    # complex gain state (stationary init off each lane's base
                    # key).  Tuples thread through permute/pad/device_put/shard
                    # specs unchanged — all pytree-prefix operations.
                    state = (state, self._h0_init(keys, self._sp))
                if self._groups is not None:
                    # Grouped dispatch: gather lanes (and their per-group
                    # ghosts) into LaneGroups execution order; results
                    # un-permute below.
                    state = SC.permute_lanes(state, self._groups.perm)
                    keys = SC.permute_lanes(keys, self._groups.perm)
                else:
                    if self.flat_state:
                        state = SC.pad_lanes(state, total)
                    keys = SC.pad_lanes(keys, total)
                sp = self._sp_run

            if self.mesh is not None:
                with _span("sweep.stage"):
                    lane = lane_sharding(self.mesh)
                    rep = replicated_sharding(self.mesh)
                    if self._ms is not None:
                        # The flat [S, d_pad] state splits its column axis over
                        # "model"; the Markov h tuple element (no D axis) stays
                        # lane-sharded like every other operand.
                        wsh = sweep_state_sharding(self.mesh)
                        if self.spec.any_markov:
                            state = (put_with_sharding(state[0], wsh),
                                     put_with_sharding(state[1], lane))
                        else:
                            state = put_with_sharding(state, wsh)
                    else:
                        state = jax.tree_util.tree_map(
                            lambda x: put_with_sharding(x, lane), state)
                    keys = put_with_sharding(keys, lane)
                    sp = jax.tree_util.tree_map(
                        lambda x: put_with_sharding(x, lane), sp)
                    if self.chunk_rounds is None:
                        batches = jax.tree_util.tree_map(
                            lambda x: put_with_sharding(x, rep), batches)

            if self.chunk_rounds is None:
                with _span("sweep.dispatch"):
                    params, loss, gn, metrics = self._run_jit(
                        state, keys, batches, sp)
            else:
                params, loss, gn, metrics = self._run_chunked(
                    state, keys, batches, sp, resume=resume)

            with _span("sweep.fetch"):
                if jax.process_count() > 1:
                    # Multi-process fetch edge: the jitted outputs are sharded
                    # over a process-spanning mesh; all-gather them host-side
                    # so every process returns the identical full SweepResult.
                    params = jax.tree_util.tree_map(_fetch, params)
                    loss, gn = _fetch(loss), _fetch(gn)
                    metrics = {k: _fetch(v) for k, v in metrics.items()}

                if self._groups is not None:
                    # Scatter back to lane order: pick each source lane's
                    # execution row (ghosts are exact replicas; the first
                    # occurrence serves).
                    inv = np.asarray(self._groups.inverse)
                    inv_j = jnp.asarray(inv)

                    def lanes(x):  # scan gives [R, S_exec]
                        return np.asarray(x).T[inv]

                    params_out = jax.tree_util.tree_map(lambda x: x[inv_j],
                                                        params)
                else:
                    def lanes(x):  # scan gives [R, S(+ghosts)]: drop ghosts
                        return np.asarray(x).T[:num]

                    params_out = jax.tree_util.tree_map(lambda x: x[:num],
                                                        params)

                result = SweepResult(
                    names=self.spec.names,
                    params=params_out,
                    loss=lanes(loss),
                    grad_norm=lanes(gn),
                    metrics={k: lanes(v) for k, v in metrics.items()},
                )
        jax.monitoring.record_scalar(H2D_BYTES, float(self._h2d_bytes))
        return result

    def _staged(self, host, dev):
        """dev, the device copy of the pytree `host`; the bytes of the
        leaves that came from host (not already device) arrays count
        toward this run's H2D_BYTES."""
        for x, y in zip(jax.tree_util.tree_leaves(host),
                        jax.tree_util.tree_leaves(dev)):
            if not isinstance(x, jax.Array):
                self._h2d_bytes += y.nbytes
        return dev


def run_sweep(loss_fn: Callable, params0, batches, spec: SweepSpec,
              eval_fn: Optional[Callable] = None,
              eval_every: int = 1,
              plan: Optional[ExecutionPlan] = None, *,
              resume: bool = False,
              flat_state=_UNSET,
              mesh=_UNSET,
              chunk_rounds=_UNSET,
              async_staging=_UNSET) -> SweepResult:
    """One-shot convenience wrapper around SweepEngine (see the SweepEngine
    class docstring for each plan knob's equivalence contract)::

        run_sweep(loss_fn, params0, batches, spec,
                  plan=ExecutionPlan(mesh=..., chunk_rounds=...))

    plan= is the execution-strategy signature.  The loose per-knob kwargs
    (flat_state / mesh / chunk_rounds / async_staging) are the deprecated
    pre-plan spelling: any passed explicitly build the equivalent plan
    (bitwise-equal execution, pinned by tests/test_execution_plan.py) and
    emit a DeprecationWarning; mixing them with plan= raises.  Everything
    past plan is keyword-only, so a stray positional argument raises
    instead of silently binding to resume.  resume= forwards to
    `SweepEngine.run` (preemption-safe continuation off
    plan.checkpoint_dir)."""
    legacy = {k: v for k, v in dict(
        flat_state=flat_state, mesh=mesh, chunk_rounds=chunk_rounds,
        async_staging=async_staging).items() if v is not _UNSET}
    if legacy:
        if plan is not None:
            raise ValueError(
                f"pass the execution strategy as plan=ExecutionPlan(...) OR "
                f"as the legacy per-knob kwargs, not both (got plan and "
                f"{sorted(legacy)})")
        warnings.warn(
            "run_sweep's loose execution kwargs (flat_state, mesh, "
            "chunk_rounds, async_staging) are deprecated; pass "
            "plan=ExecutionPlan(...) instead",
            DeprecationWarning, stacklevel=2)
        plan = ExecutionPlan(**legacy)
    elif plan is None:
        plan = ExecutionPlan()
    return SweepEngine(loss_fn, spec, eval_fn=eval_fn,
                       eval_every=eval_every,
                       plan=plan).run(params0, batches, resume=resume)
