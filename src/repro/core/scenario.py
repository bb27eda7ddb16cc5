"""Traceable scenario parameters: `FLOAConfig` as a struct-of-arrays pytree.

`FLOAConfig` is a frozen dataclass whose policy/attack fields steer Python
branches at trace time — perfect for one jit per scenario, useless for a
`vmap` over a *stacked* scenario axis (the paper's Figs. 1-4 are exactly such
grids: attack type x attacker count x power policy x seed).  This module is
the bridge:

  ScenarioParams      every FLOAConfig field that varies per scenario, as
                      arrays (enums -> int32 codes, masks/sigmas -> vectors),
                      so a whole sweep stacks into one [S, ...] pytree.
  from_floa           FLOAConfig (+ per-scenario alpha) -> ScenarioParams.
  scenario_coefficients
                      branchless re-derivation of channel.py / power_control.py
                      / attacks.py for ONE scenario — policy and attack
                      selection via jnp.where on the code arrays, so the same
                      function vmaps cleanly over the stacked axis.

The branchless path must agree with the branching modules exactly; the
per-combination equivalence test in tests/test_sweep.py is the contract.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import attacks as A
from repro.core.channel import rayleigh_gains
from repro.core.power_control import Policy, ci_b0_arrays, max_amplitude_arrays

Array = jax.Array

POLICY_CODES = {
    Policy.CI: 0,
    Policy.BEV: 1,
    Policy.EF: 2,
    Policy.TRUNCATED_CI: 3,
}
ATTACK_CODES = {
    A.AttackType.NONE: 0,
    A.AttackType.STRONGEST: 1,
    A.AttackType.SIGN_FLIP_PROTOCOL_POWER: 2,
    A.AttackType.GAUSSIAN: 3,
    A.AttackType.COLLUDING: 4,
    A.AttackType.OMNISCIENT: 5,
}
_CI, _BEV, _EF, _TCI = 0, 1, 2, 3
_NONE, _STRONGEST, _SIGN_FLIP, _GAUSSIAN = 0, 1, 2, 3
_COLLUDING, _OMNISCIENT = 4, 5

# Defense-code lane axis: 0 selects the analog FLOA combine (the paper's
# scheme); every other code selects a digital screening defense applied to
# the gathered [U, D] per-worker gradient slab (core/defenses.py).  "krum"
# and "multi_krum" share a kernel (multi=1 vs multi=m) but keep distinct
# codes so sweep tables name the defense family they ran.
DEFENSE_CODES = {
    "floa": 0,
    "mean": 1,
    "median": 2,
    "trimmed_mean": 3,
    "krum": 4,
    "multi_krum": 5,
    "geometric_median": 6,
}
_FLOA_CODE = 0


@dataclasses.dataclass(frozen=True)
class DefenseSpec:
    """Per-lane aggregation rule: analog FLOA (name="floa") or a digital
    screening defense with its hyper-parameters.

    This is the validation layer for the defense kernels: trim / Krum bounds
    are checked HERE, on concrete Python ints, because `assert`s on traced
    values silently vanish under jit (and a bare `assert 2 * trim < u` says
    nothing useful about a negative trim anyway).

    gm_iters is a static Weiszfeld iteration count (a lax.scan length), so it
    cannot vary across the lanes of one compiled sweep — SweepSpec enforces
    that all geometric-median lanes agree.
    """

    name: str = "floa"
    trim: int = 1           # trimmed_mean: drop `trim` largest+smallest/coord
    num_byzantine: int = 0  # krum / multi_krum: assumed attacker count f
    multi: int = 1          # multi_krum: average the m best-scored workers
    gm_iters: int = 8       # geometric_median: Weiszfeld iterations

    @property
    def code(self) -> int:
        return DEFENSE_CODES[self.name]

    @property
    def is_digital(self) -> bool:
        return self.name != "floa"

    def validate(self, num_workers: int) -> "DefenseSpec":
        if self.name not in DEFENSE_CODES:
            raise ValueError(
                f"unknown defense {self.name!r}; one of {sorted(DEFENSE_CODES)}")
        u = num_workers
        if self.name == "trimmed_mean" and not 0 <= 2 * self.trim < u:
            raise ValueError(
                f"trimmed_mean trim={self.trim} invalid for U={u}: "
                f"need 0 <= 2*trim < U")
        if self.name in ("krum", "multi_krum"):
            if not 0 <= self.num_byzantine < u:
                raise ValueError(
                    f"krum num_byzantine={self.num_byzantine} invalid for "
                    f"U={u}: need 0 <= f < U")
            if not 1 <= self.multi <= u:
                raise ValueError(
                    f"krum multi={self.multi} invalid for U={u}: "
                    f"need 1 <= multi <= U")
        if self.name == "geometric_median" and self.gm_iters < 1:
            raise ValueError(f"geometric_median gm_iters={self.gm_iters} < 1")
        return self

    _KWARGS_BY_DEFENSE = {
        "trimmed_mean": frozenset({"trim"}),
        "krum": frozenset({"num_byzantine", "multi"}),
        "multi_krum": frozenset({"num_byzantine", "multi"}),
        "geometric_median": frozenset({"iters", "gm_iters"}),
    }

    @classmethod
    def from_kwargs(cls, name: str, **kw) -> "DefenseSpec":
        """Build from `FLTrainer`-style (defense, **defense_kwargs).

        Kwargs irrelevant to `name` are rejected, matching the pytree path
        (where e.g. coordinate_median(trim=...) is a TypeError) — silently
        dropping them would run a different defense than the caller asked
        for.
        """
        extra = set(kw) - cls._KWARGS_BY_DEFENSE.get(name, frozenset())
        if extra:
            raise ValueError(
                f"defense {name!r} does not accept kwargs {sorted(extra)}")
        fields = dict(trim=kw.get("trim", 1),
                      num_byzantine=kw.get("num_byzantine", 0),
                      multi=kw.get("multi", 1),
                      gm_iters=kw.get("iters", kw.get("gm_iters", 8)))
        if name == "krum" and fields["multi"] > 1:
            name = "multi_krum"
        return cls(name=name, **fields)


class ScenarioParams(NamedTuple):
    """One scenario's FLOA knobs as arrays (NamedTuple == pytree, so a list of
    these stacks with a single tree_map into the [S, ...] sweep axis)."""

    policy: Array      # int32 [] — POLICY_CODES
    attack: Array      # int32 [] — ATTACK_CODES
    byz_mask: Array    # bool  [U]
    sigma: Array       # f32   [U] Rayleigh scales
    p_max: Array       # f32   [U] per-worker max power
    dim: Array         # f32   []  power-accounting gradient dim D (eq. 4)
    noise_std: Array   # f32   []  receiver AWGN std (0 under EF)
    alpha: Array       # f32   []  raw learning rate (eq. 8)
    defense: Array     # int32 [] — DEFENSE_CODES (0 = analog FLOA combine)
    def_trim: Array    # int32 []  trimmed_mean trim count
    def_f: Array       # int32 []  (multi-)Krum assumed attacker count f
    def_multi: Array   # int32 []  multi-Krum average count m
    # Adaptive-adversary axis (PR 8); the numpy-scalar defaults keep older
    # direct constructions (tests, notebooks) valid and inert.  numpy (not
    # jnp) scalars: a jnp default would run a device computation at class
    # definition, and `jax.distributed.initialize` refuses to bootstrap
    # once any computation has executed — importing repro must stay free of
    # device work for the multi-host entry points to exist at all.
    chan_rho: Array = np.float32(0.0)    # f32 [] Gauss-Markov fading rho
    part_k: Array = np.int32(1 << 30)    # int32 [] K-of-U participation count
    #                                      (>= U means full participation)

    @property
    def num_workers(self) -> int:
        return self.byz_mask.shape[-1]


def from_floa(cfg, alpha: float,
              defense: Optional[DefenseSpec] = None,
              participants: Optional[int] = None) -> ScenarioParams:
    """FLOAConfig (frozen dataclass) -> traceable ScenarioParams.

    EF scenarios get noise_std forced to 0 here (the dataclass path simply
    never reaches the noise branch under EF; the branchless path always adds
    the noise term, so the std itself must be zero).

    defense: optional DefenseSpec; omitted means the analog FLOA combine.
    Digital lanes keep the full channel/power params (their branchless floa
    half still traces) but the lane's update consumes the screening defense
    output instead.

    participants: optional K for K-of-U per-round client sampling (the sweep
    engine draws the round's K participants from the lane key); None means
    full participation, and so is K = U (the sweep engine traces no masking
    op unless some lane samples K < U).
    """
    cfg.validate()
    u = cfg.num_workers
    defense = (defense or DefenseSpec()).validate(u)
    if participants is not None and not 1 <= participants <= u:
        raise ValueError(
            f"participants={participants} invalid for U={u}: need 1 <= K <= U")
    mask = (jnp.asarray(cfg.attack.byzantine_mask, dtype=bool)
            if cfg.attack.byzantine_mask else jnp.zeros((u,), dtype=bool))
    is_ef = cfg.power.policy == Policy.EF
    return ScenarioParams(
        policy=jnp.int32(POLICY_CODES[cfg.power.policy]),
        attack=jnp.int32(ATTACK_CODES[cfg.attack.attack]),
        byz_mask=mask,
        sigma=cfg.channel.sigmas(),
        p_max=cfg.power.p_maxes(),
        dim=jnp.float32(cfg.power.dim),
        noise_std=jnp.float32(0.0 if is_ef else cfg.channel.noise_std),
        alpha=jnp.float32(alpha),
        defense=jnp.int32(defense.code),
        def_trim=jnp.int32(defense.trim),
        def_f=jnp.int32(defense.num_byzantine),
        def_multi=jnp.int32(defense.multi),
        chan_rho=jnp.float32(cfg.channel.markov_rho),
        part_k=jnp.int32(u if participants is None else participants),
    )


def stack(params: Tuple[ScenarioParams, ...]) -> ScenarioParams:
    """[ScenarioParams] * S -> ScenarioParams with a leading S axis on every
    leaf.  All scenarios must share U (shapes must match to stack)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params)


@dataclasses.dataclass(frozen=True)
class LaneGroups:
    """Static partition of a sweep's lane axis by defense code.

    Defense codes are concrete config (DefenseSpec / ScenarioParams.defense is
    filled from Python ints), so the partition is known at ENGINE BUILD time —
    the grouped dispatch in fl/sweep.py uses it to run each defense family's
    kernel once over a contiguous sub-slab instead of paying every family for
    every lane under a vmapped `lax.switch`.

    The execution order is shard-uniform: each group is ghost-padded to a
    multiple of `shards` (replicating its LAST member, the same trick as
    `pad_lanes`) and laid out device-major, so after a shard_map over
    ("data",) every device's local lane block has the IDENTICAL static group
    layout `local_slices` — grouped dispatch then works inside the one shared
    trace with purely static slicing.  shards=1 is the unsharded engine.

      codes         group defense codes, ascending (one entry per group)
      perm          [S_exec] execution row -> source lane index (ghost rows
                    repeat their group's last real lane)
      inverse       [S] source lane -> an execution row carrying its
                    trajectory (ghosts are replicas, any occurrence is valid)
      local_slices  ((code, start, end), ...) group boundaries in LOCAL
                    (per-shard) lane coordinates
      shards        device count the layout was built for
    """

    codes: Tuple[int, ...]
    perm: Tuple[int, ...]
    inverse: Tuple[int, ...]
    local_slices: Tuple[Tuple[int, int, int], ...]
    shards: int

    @property
    def exec_lanes(self) -> int:
        return len(self.perm)

    @property
    def lanes_per_shard(self) -> int:
        return len(self.perm) // self.shards

    @property
    def num_ghosts(self) -> int:
        return len(self.perm) - len(self.inverse)


def build_lane_groups(codes, shards: int = 1) -> LaneGroups:
    """Lane defense codes (concrete ints, lane order) -> LaneGroups.

    Within a group the original lane order is preserved (stable partition);
    groups are ordered by ascending code so the analog FLOA group (code 0),
    when present, is always the first slice.
    """
    codes = [int(c) for c in codes]
    assert codes, "empty lane-code list"
    assert shards >= 1, shards
    group_codes = sorted(set(codes))
    padded = {}
    for c in group_codes:
        members = [i for i, ci in enumerate(codes) if ci == c]
        members += [members[-1]] * (-len(members) % shards)
        padded[c] = members
    per_shard = {c: len(padded[c]) // shards for c in group_codes}
    perm = []
    for d in range(shards):
        for c in group_codes:
            k = per_shard[c]
            perm.extend(padded[c][d * k:(d + 1) * k])
    first_row = {}
    for row, lane in enumerate(perm):
        first_row.setdefault(lane, row)
    local_slices, off = [], 0
    for c in group_codes:
        local_slices.append((c, off, off + per_shard[c]))
        off += per_shard[c]
    return LaneGroups(
        codes=tuple(group_codes), perm=tuple(perm),
        inverse=tuple(first_row[i] for i in range(len(codes))),
        local_slices=tuple(local_slices), shards=shards)


def permute_lanes(sp, perm):
    """Gather a lane-stacked pytree (ScenarioParams, key array, flat [S, D]
    state, ...) into LaneGroups execution order.  `perm` may repeat source
    lanes (per-group ghost padding), so this subsumes `pad_lanes` for the
    grouped engine: ghosts replicate a real lane of the SAME defense family
    and run a real, discarded scenario."""
    idx = jnp.asarray(perm, dtype=jnp.int32)
    return jax.tree_util.tree_map(lambda x: x[idx], sp)


def pad_lanes(sp, total: int):
    """Pad a lane-stacked pytree (ScenarioParams, key array, flat [S, D]
    state, ...) to `total` lanes by replicating the last real lane.  The
    single definition of ghost-lane padding for mesh sharding: every leaf
    keeps valid values, so the padded lanes run real — discarded —
    scenarios instead of NaNs poisoning collective-free lane math."""
    s = jax.tree_util.tree_leaves(sp)[0].shape[0]
    assert total >= s, (total, s)
    if total == s:
        return sp
    return jax.tree_util.tree_map(
        lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[-1:], (total - s,) + x.shape[1:])]), sp)


def sample_gains(key: Array, sp: ScenarioParams) -> Array:
    """|h_{i,t}| ~ Rayleigh(sp.sigma), [U] — channel.sample_channel_gains
    with the scales coming from the traceable params (both share
    channel.rayleigh_gains, so the draws are identical per key).  Under EF
    the dataclass path forces h == 1; scenario_coefficients handles that
    branchlessly, so the raw draw here is simply ignored for EF scenarios."""
    return rayleigh_gains(key, sp.sigma)


def participation_mask(key: Array, part_k: Array, num_workers: int) -> Array:
    """K-of-U per-round client sampling: the K workers with the smallest
    uniform scores participate (rank-of-rank top-K, so exactly K of U and
    every subset is equally likely).  part_k may be traced; part_k >= U is
    an all-True mask (full participation)."""
    scores = jax.random.uniform(key, (num_workers,))
    rank = jnp.argsort(jnp.argsort(scores))
    return rank < part_k


def scenario_coefficients(
    h_abs: Array, sp: ScenarioParams, gbar: Array, eps2: Array,
    part: Optional[Array] = None,
) -> Tuple[Array, Array, Array, Array, Array]:
    """Branchless eq. (7) coefficient derivation for one scenario.

    Returns (s, bias_w, jam_std, noise_std, dir_w):
      s [U]       signed per-worker payload coefficients (attacks.py semantics)
      bias_w []   de-standardization bias weight (x gbar x 1)
      jam_std []  GAUSSIAN jamming noise std (0 unless that attack is active)
      noise_std []  effective receiver AWGN std (0 under EF)
      dir_w []    received weight of the COLLUDING/OMNISCIENT cohort's shared
                  rank-1 direction (0 for every other attack; the caller owns
                  the direction row itself — see fl/sweep.py)

    part: optional [U] bool participation mask (`participation_mask`); None
    is full participation with zero masking ops traced, and an all-True mask
    is bitwise-identical to None (the K=U contract).

    Every policy/attack formula is computed, then selected with jnp.where on
    the int32 codes — so the whole thing vmaps over a stacked scenario axis.
    The selected values are the *same expressions* the branching modules
    compute, so per-scenario outputs match attacks.signed_coefficients /
    power_control.transmit_amplitudes bit-for-bit.
    """
    u = sp.byz_mask.shape[-1]
    dim = sp.dim   # power-accounting D from the config, NOT the model's size
    is_ef = sp.policy == _EF
    mask = sp.byz_mask
    # Non-participants transmit nothing: they drop out of the payload, the
    # bias/jamming/directional cohort sums, and the EF mean share.
    eff_mask = mask if part is None else (mask & part)
    eps = jnp.sqrt(eps2)

    # --- power_control.transmit_amplitudes, all policies at once (the
    # formulas live in power_control/attacks as array helpers so the
    # branching and branchless paths cannot drift apart).
    b0 = ci_b0_arrays(sp.p_max, sp.sigma, dim)
    ci_amp = b0 / h_abs
    bev_amp = max_amplitude_arrays(sp.p_max, dim)
    amp = jnp.where(sp.policy == _CI, ci_amp,
                    jnp.where(sp.policy == _TCI,
                              jnp.minimum(ci_amp, bev_amp), bev_amp))
    if part is None:
        ef_share = 1.0 / u
    else:
        # (1/u) * (u/K): == 1.0/u bitwise at the full mask (the scale is
        # exactly 1.0), the 1/K mean share otherwise.
        ef_share = (1.0 / u) * (u / jnp.sum(part.astype(jnp.float32)))
    honest_s = jnp.where(is_ef, ef_share, amp * h_abs)

    # --- attacks.signed_coefficients (+ the EF early-return's sign flip).
    phat = A.strongest_attack_amplitude(sp.p_max, dim, gbar, eps2)
    strongest_s = -eps * phat * h_abs
    attacker_s = jnp.where(sp.attack == _STRONGEST, strongest_s,
                           jnp.where(sp.attack == _SIGN_FLIP, -honest_s, 0.0))
    # EF models any active attacker as a sign-flipped mean share (-1/U).
    attacker_s = jnp.where(is_ef, -honest_s, attacker_s)
    active = sp.attack != _NONE
    s = jnp.where(active & mask, attacker_s, honest_s)
    if part is not None:
        s = jnp.where(part, s, 0.0)

    # PS de-standardizes assuming protocol power for every worker; attackers
    # that never standardized (STRONGEST/GAUSSIAN/COLLUDING/OMNISCIENT)
    # leave the bias behind.
    has_bias = active & (~is_ef) & ((sp.attack == _STRONGEST)
                                    | (sp.attack == _GAUSSIAN)
                                    | (sp.attack == _COLLUDING)
                                    | (sp.attack == _OMNISCIENT))
    bias_w = jnp.where(has_bias,
                       jnp.sum(jnp.where(eff_mask, honest_s, 0.0)), 0.0)

    # --- attacks.gaussian_jam_std.
    jam = A.jam_std_arrays(h_abs, sp.p_max, dim, eff_mask, eps2)
    jam_std = jnp.where(active & (~is_ef) & (sp.attack == _GAUSSIAN), jam, 0.0)

    # --- adaptive rank-1 attacks: the cohort's shared-direction weight
    # (attacks.colluding_dir_weight / omniscient_dir_weight; unused outputs
    # are dead code XLA drops when no directional lane is present).
    collude_w = A.colluding_dir_weight(h_abs, sp.p_max, dim, eff_mask, eps2)
    omni_w = A.omniscient_dir_weight(h_abs, sp.p_max, dim, eff_mask,
                                     gbar, eps2)
    directional = active & (~is_ef)
    dir_w = jnp.where(directional & (sp.attack == _COLLUDING), collude_w,
                      jnp.where(directional & (sp.attack == _OMNISCIENT),
                                omni_w, 0.0))

    noise_std = jnp.where(is_ef, 0.0, sp.noise_std)
    return s, bias_w, jam_std, noise_std, dir_w
