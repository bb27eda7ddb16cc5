"""Adaptive-adversary scenario axes: degenerate and composition contracts.

PR 8 adds four branchless lane axes to the compiled sweep — COLLUDING /
OMNISCIENT directional attacks, Gauss-Markov fading (chan_rho), and K-of-U
per-round participation — all inside the ONE jitted scan.  These tests pin
the contracts that make the axes safe to mix into existing grids:

* markov rho=0 lanes are BITWISE identical to the i.i.d. channel draw, even
  when they share a sweep with rho>0 lanes (the legacy key stream is
  untouched: slot 0 still draws the i.i.d. gains, the Markov innovation
  comes from fold_in side-channels);
* participants=U is BITWISE identical to participants=None (both trace the
  unmasked program), and full lanes that share a grid with a K<U lane run
  the masked machinery at an all-True mask within a few ulp of it;
* a cohort-of-1 OMNISCIENT attacker on identical worker shards reproduces
  the STRONGEST attack (eq. 18) to float tolerance — the honest mean IS the
  negated common gradient;
* with every axis active the engine's own equivalence matrix still holds
  bitwise under strict_numerics: flat == tree state, grouped == switch
  dispatch, chunked == monolithic, sharded == unsharded (8 fake devices via
  the CI sweep-sharded job; single-device mesh runs everywhere).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_threefry_partitionable", True)

from repro.core.attacks import AttackType
from repro.core.channel import ChannelConfig
from repro.core.power_control import Policy
from repro.core.scenario import DefenseSpec
from repro.fl import ExecutionPlan, ScenarioCase, SweepEngine, SweepSpec
from repro.launch.mesh import make_sweep_mesh
from sweep_testlib import U, floa as _floa, grid_cases, tiny_problem

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(see the CI sweep-sharded job)")


def _with_rho(cfg, rho):
    """FLOAConfig with the channel's markov_rho replaced."""
    return dataclasses.replace(
        cfg, channel=dataclasses.replace(cfg.channel, markov_rho=rho))


def _axes_grid(dim):
    """Mixed grid exercising every new axis at once: legacy lanes, a Markov
    lane, colluding/omniscient lanes, partial participation (analog and
    digital), and their compositions."""
    return [
        ScenarioCase("legacy-bev", _floa(dim, Policy.BEV, 2), 0.05, seed=300),
        ScenarioCase("legacy-ci", _floa(dim, Policy.CI, 1), 0.05, seed=301),
        ScenarioCase("markov", _with_rho(_floa(dim, Policy.BEV, 1), 0.9),
                     0.05, seed=302),
        ScenarioCase("collude",
                     _floa(dim, Policy.CI, 2, attack=AttackType.COLLUDING),
                     0.05, seed=303),
        ScenarioCase("omni",
                     _floa(dim, Policy.BEV, 1, attack=AttackType.OMNISCIENT),
                     0.05, seed=304),
        ScenarioCase("part3", _floa(dim, Policy.BEV, 1), 0.05, seed=305,
                     participants=3),
        ScenarioCase("markov+collude+part",
                     _with_rho(_floa(dim, Policy.CI, 2,
                                     attack=AttackType.COLLUDING), 0.5),
                     0.05, seed=306, participants=3),
        ScenarioCase("median-part", _floa(dim, Policy.EF, 1, 0.0), 0.05,
                     seed=307, defense=DefenseSpec(name="median"),
                     participants=3),
        ScenarioCase("trimmed-part", _floa(dim, Policy.EF, 2, 0.0), 0.05,
                     seed=308, defense=DefenseSpec(name="trimmed_mean",
                                                   trim=1),
                     participants=3),
    ]


def _assert_bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a.loss), np.asarray(b.loss))
    np.testing.assert_array_equal(np.asarray(a.grad_norm),
                                  np.asarray(b.grad_norm))
    for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                      jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _assert_close(a, b):
    np.testing.assert_allclose(a.loss, b.loss, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(a.grad_norm, b.grad_norm, rtol=1e-6,
                               atol=1e-7)
    for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                      jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------- markov

def test_markov_rho0_lanes_bitwise_equal_iid():
    """Legacy lanes inside a sweep that ALSO carries a rho>0 Markov lane are
    bitwise unchanged: activating the fading carry must not perturb anyone
    else's key stream or arithmetic."""
    loss, params, dim, batches = tiny_problem()
    legacy = grid_cases(dim, 2)
    ref = SweepEngine(loss, SweepSpec.build(legacy)).run(params, batches)
    mixed = legacy + [ScenarioCase(
        "markov", _with_rho(_floa(dim, Policy.BEV, 1), 0.9), 0.05, seed=999)]
    got = SweepEngine(loss, SweepSpec.build(mixed)).run(params, batches)
    np.testing.assert_array_equal(np.asarray(got.loss[:2]),
                                  np.asarray(ref.loss))
    np.testing.assert_array_equal(np.asarray(got.grad_norm[:2]),
                                  np.asarray(ref.grad_norm))
    assert np.all(np.isfinite(np.asarray(got.loss[2])))


def test_markov_rho0_lane_bitwise_equal_explicit():
    """A lane explicitly constructed with markov_rho=0.0 == the same lane
    without the field — rho=0 degenerates to the i.i.d. draw bitwise."""
    loss, params, dim, batches = tiny_problem()
    base = grid_cases(dim, 3)
    zeroed = [dataclasses.replace(c, floa=_with_rho(c.floa, 0.0))
              for c in base]
    a = SweepEngine(loss, SweepSpec.build(base)).run(params, batches)
    b = SweepEngine(loss, SweepSpec.build(zeroed)).run(params, batches)
    _assert_bitwise(a, b)


def test_markov_lane_differs_from_iid():
    """rho=0.9 genuinely changes the channel realization (same seed)."""
    loss, params, dim, batches = tiny_problem()
    iid = ScenarioCase("l", _floa(dim, Policy.BEV, 1), 0.05, seed=42)
    mk = ScenarioCase("l", _with_rho(_floa(dim, Policy.BEV, 1), 0.9),
                      0.05, seed=42)
    a = SweepEngine(loss, SweepSpec.build([iid])).run(params, batches)
    b = SweepEngine(loss, SweepSpec.build([mk])).run(params, batches)
    assert not np.allclose(a.loss, b.loss)
    assert np.all(np.isfinite(np.asarray(b.loss)))


def test_markov_rho_validation():
    with pytest.raises(ValueError):
        ChannelConfig(num_workers=U, sigma=1.0, markov_rho=1.0)
    with pytest.raises(ValueError):
        ChannelConfig(num_workers=U, sigma=1.0, markov_rho=-0.1)


# ---------------------------------------------------------- participation

def test_participants_full_u_bitwise_equal_none():
    """participants=U is full participation: with no K<U lane in the sweep
    it traces the unmasked program, so trajectories agree exactly.

    Next to a K<U lane, full lanes run the masked stats/combine/defense
    machinery at an all-True mask.  Each masked kernel equals its unmasked
    spelling bitwise on its own, but XLA may fuse and order the masked
    reductions of the whole sweep differently (attacked analog lanes moved
    by 1 ulp under one XLA CPU build), so those lanes are pinned to a few
    f32 ulp per round, not bitwise."""
    loss, params, dim, batches = tiny_problem()
    base = grid_cases(dim, 4) + [
        ScenarioCase("med", _floa(dim, Policy.EF, 1, 0.0), 0.05, seed=50,
                     defense=DefenseSpec(name="median")),
        ScenarioCase("krum", _floa(dim, Policy.EF, 1, 0.0), 0.05, seed=51,
                     defense=DefenseSpec(name="krum", num_byzantine=1)),
    ]
    full = [dataclasses.replace(c, participants=U) for c in base]
    a = SweepEngine(loss, SweepSpec.build(base)).run(params, batches)
    b = SweepEngine(loss, SweepSpec.build(full)).run(params, batches)
    _assert_bitwise(a, b)

    partial = ScenarioCase("part", _floa(dim, Policy.BEV, 1), 0.05, seed=52,
                           participants=U - 1)
    m = SweepEngine(loss, SweepSpec.build(base + [partial])).run(
        params, batches)
    rounds = np.asarray(a.loss).shape[1]
    rtol = 4 * rounds * np.finfo(np.float32).eps
    np.testing.assert_allclose(np.asarray(m.loss)[:len(base)],
                               np.asarray(a.loss), rtol=rtol, atol=0)
    np.testing.assert_allclose(np.asarray(m.grad_norm)[:len(base)],
                               np.asarray(a.grad_norm), rtol=rtol, atol=0)


def test_partial_lanes_run_and_differ():
    """K<U participation changes the trajectory and stays finite."""
    loss, params, dim, batches = tiny_problem()
    c_full = ScenarioCase("f", _floa(dim, Policy.BEV, 1), 0.05, seed=60)
    c_part = dataclasses.replace(c_full, participants=2)
    a = SweepEngine(loss, SweepSpec.build([c_full])).run(params, batches)
    b = SweepEngine(loss, SweepSpec.build([c_part])).run(params, batches)
    assert not np.allclose(a.loss, b.loss)
    assert np.all(np.isfinite(np.asarray(b.loss)))


def test_participants_validation():
    loss, params, dim, _ = tiny_problem()
    bad = ScenarioCase("b", _floa(dim, Policy.BEV, 1), 0.05, seed=1,
                       participants=U + 1)
    with pytest.raises(ValueError, match="participants"):
        SweepSpec.build([bad])
    with pytest.raises(ValueError, match="participants"):
        SweepSpec.build([dataclasses.replace(bad, participants=0)])
    # Defense arity must fit the PARTICIPATING cohort, not U.
    trm = ScenarioCase("t", _floa(dim, Policy.EF, 1, 0.0), 0.05, seed=2,
                       defense=DefenseSpec(name="trimmed_mean", trim=1),
                       participants=2)
    with pytest.raises(ValueError, match="trim"):
        SweepSpec.build([trm])
    kr = ScenarioCase("k", _floa(dim, Policy.EF, 1, 0.0), 0.05, seed=3,
                      defense=DefenseSpec(name="krum", num_byzantine=1),
                      participants=3)
    with pytest.raises(ValueError, match="participants"):
        SweepSpec.build([kr])


# ------------------------------------------------------------ directional

def test_cohort_of_one_omniscient_matches_strongest():
    """On identical worker shards with a noiseless channel, the honest mean
    equals the common gradient, so a single OMNISCIENT attacker's transmit
    vector coincides with the eq. 18 STRONGEST attack.  Only the addition
    order differs (post-combine injection vs in-superposition), so the match
    is allclose, not bitwise."""
    loss, params, dim, batches = tiny_problem()
    tiled = {k: np.tile(v[:, :v.shape[1] // U], (1, U, 1))
             for k, v in batches.items()}
    st = ScenarioCase("s", _floa(dim, Policy.CI, 1, noise=0.0), 0.05, seed=70)
    om = ScenarioCase("o", _floa(dim, Policy.CI, 1, noise=0.0,
                                 attack=AttackType.OMNISCIENT), 0.05, seed=70)
    res = SweepEngine(loss, SweepSpec.build([st, om])).run(params, tiled)
    np.testing.assert_allclose(np.asarray(res.loss[0]),
                               np.asarray(res.loss[1]), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(res.grad_norm[0]),
                               np.asarray(res.grad_norm[1]), rtol=2e-5)


def test_directional_lanes_finite_and_distinct():
    """Colluding and omniscient lanes run inside a mixed grid, stay finite,
    and produce trajectories distinct from STRONGEST and from each other."""
    loss, params, dim, batches = tiny_problem()
    mk = lambda n, atk, s: ScenarioCase(
        n, _floa(dim, Policy.BEV, 2, attack=atk), 0.05, seed=s)
    res = SweepEngine(loss, SweepSpec.build([
        mk("st", AttackType.STRONGEST, 80),
        mk("co", AttackType.COLLUDING, 80),
        mk("om", AttackType.OMNISCIENT, 80),
    ])).run(params, batches)
    assert np.all(np.isfinite(np.asarray(res.loss)))
    assert not np.allclose(res.loss[0], res.loss[1])
    assert not np.allclose(res.loss[0], res.loss[2])
    assert not np.allclose(res.loss[1], res.loss[2])


def test_directional_attacks_leave_legacy_lanes_bitwise():
    """Adding a colluding lane to a sweep leaves the other lanes' key streams
    and arithmetic untouched (the direction draw is a fold_in side-channel)."""
    loss, params, dim, batches = tiny_problem()
    legacy = grid_cases(dim, 2)
    ref = SweepEngine(loss, SweepSpec.build(legacy)).run(params, batches)
    mixed = legacy + [ScenarioCase(
        "co", _floa(dim, Policy.CI, 2, attack=AttackType.COLLUDING),
        0.05, seed=888)]
    got = SweepEngine(loss, SweepSpec.build(mixed)).run(params, batches)
    np.testing.assert_array_equal(np.asarray(got.loss[:2]),
                                  np.asarray(ref.loss))


# ---------------------------------------------------- engine equivalences

def test_all_axes_strict_flat_equals_tree():
    loss, params, dim, batches = tiny_problem()
    spec = SweepSpec.build(_axes_grid(dim))
    flat = SweepEngine(loss, spec,
                       plan=ExecutionPlan(strict_numerics=True)).run(
        params, batches)
    tree = SweepEngine(loss, spec,
                       plan=ExecutionPlan(flat_state=False,
                                          strict_numerics=True)).run(
        params, batches)
    _assert_bitwise(flat, tree)


def test_all_axes_strict_grouped_equals_switch():
    loss, params, dim, batches = tiny_problem()
    spec = SweepSpec.build(_axes_grid(dim))
    grouped = SweepEngine(loss, spec,
                          plan=ExecutionPlan(strict_numerics=True)).run(
        params, batches)
    switch = SweepEngine(loss, spec,
                         plan=ExecutionPlan(grouped_dispatch=False,
                                            strict_numerics=True)).run(
        params, batches)
    _assert_bitwise(grouped, switch)


def test_all_axes_strict_chunked_equals_monolithic():
    loss, params, dim, batches = tiny_problem()
    spec = SweepSpec.build(_axes_grid(dim))
    mono = SweepEngine(loss, spec,
                       plan=ExecutionPlan(strict_numerics=True)).run(
        params, batches)
    ch = SweepEngine(loss, spec,
                     plan=ExecutionPlan(strict_numerics=True,
                                        chunk_rounds=3)).run(params, batches)
    _assert_bitwise(mono, ch)


def test_all_axes_single_device_mesh_matches_unsharded():
    """Degenerate 1-device shard_map over the tuple (flat, h) Markov carry —
    runs everywhere (tier-1)."""
    loss, params, dim, batches = tiny_problem()
    spec = SweepSpec.build(_axes_grid(dim))
    un = SweepEngine(loss, spec).run(params, batches)
    sh = SweepEngine(loss, spec,
                     plan=ExecutionPlan(mesh=make_sweep_mesh(1))).run(
        params, batches)
    _assert_close(sh, un)


@needs_8_devices
def test_all_axes_sharded_matches_unsharded():
    """8 fake devices: the mixed-axes grid (9 lanes, ghost-padded) matches
    the unsharded engine — the Markov h carry and participation masks shard
    with the lane axis."""
    loss, params, dim, batches = tiny_problem()
    spec = SweepSpec.build(_axes_grid(dim))
    un = SweepEngine(loss, spec).run(params, batches)
    sh = SweepEngine(loss, spec,
                     plan=ExecutionPlan(mesh=make_sweep_mesh(8))).run(
        params, batches)
    _assert_close(sh, un)


@needs_8_devices
def test_all_axes_sharded_strict_bitwise():
    loss, params, dim, batches = tiny_problem()
    spec = SweepSpec.build(_axes_grid(dim))
    un = SweepEngine(loss, spec,
                     plan=ExecutionPlan(strict_numerics=True)).run(
        params, batches)
    sh = SweepEngine(loss, spec,
                     plan=ExecutionPlan(mesh=make_sweep_mesh(8),
                                        strict_numerics=True)).run(
        params, batches)
    _assert_bitwise(sh, un)
