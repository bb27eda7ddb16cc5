"""Sweep-engine contracts: batched kernel vs einsum oracle, branchless
scenario coefficients vs the branching dataclass modules, scan-trainer vs
looped FLTrainer bit-for-bit, and vmapped grids vs sequential runs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_threefry_partitionable", True)

from repro.core import attacks as A
from repro.core import scenario as SC
from repro.core.aggregation import FLOAConfig, batched_floa_combine
from repro.core.attacks import AttackConfig, AttackType, first_n_mask
from repro.core.channel import ChannelConfig, sample_channel_gains
from repro.core.power_control import Policy, PowerConfig, transmit_amplitudes
from repro.data import FederatedSampler
from repro.fl import (ExecutionPlan, FLTrainer, ScenarioCase, SweepEngine,
                      SweepSpec)
from repro.kernels import ops
from strategies import regression_batches, toy_shards

U = 4


# ---------------------------------------------------------------- kernels


@pytest.mark.parametrize("s,u,d", [(1, 4, 512), (3, 10, 2048), (5, 16, 5000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_floa_aggregate_batched_sweep(s, u, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(s * u * d), 5)
    coeffs = jax.random.normal(ks[0], (s, u))
    grads = jax.random.normal(ks[1], (s, u, d)).astype(dtype)
    noise = jax.random.normal(ks[2], (s, d)).astype(dtype)
    bias = jax.random.normal(ks[3], (s,))
    eps = jax.random.normal(ks[4], (s,))
    got = ops.floa_aggregate_batched(coeffs, grads, noise, bias, eps,
                                     interpret=True)
    want = ops.floa_aggregate_batched_ref(coeffs, grads, noise, bias, eps)
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_batched_ref_matches_per_scenario_unbatched():
    s, u, d = 3, 10, 1000
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    coeffs = jax.random.normal(ks[0], (s, u))
    grads = jax.random.normal(ks[1], (s, u, d))
    noise = jax.random.normal(ks[2], (s, d))
    bias = jax.random.normal(ks[3], (s,))
    eps = jax.random.normal(ks[4], (s,))
    want = jnp.stack([
        ops.floa_aggregate_ref(coeffs[i], grads[i], noise[i], bias[i], eps[i])
        for i in range(s)])
    got = ops.floa_aggregate_batched_ref(coeffs, grads, noise, bias, eps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batched_floa_combine_kernel_route_matches_ref():
    """aggregation.py's router: kernel (interpret) and einsum paths agree."""
    s, u, d = 2, 6, 4096
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    args = (jax.random.normal(ks[0], (s, u)),
            jax.random.normal(ks[1], (s, u, d)),
            jax.random.normal(ks[2], (s, d)),
            jax.random.normal(ks[3], (s,)),
            jax.random.normal(ks[4], (s,)))
    via_kernel = batched_floa_combine(*args, use_kernel=True, interpret=True)
    via_ref = batched_floa_combine(*args, use_kernel=False)
    np.testing.assert_allclose(np.asarray(via_kernel), np.asarray(via_ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------- branchless scenario params


def _floa(policy, attack, n_atk, sigma=(1.0, 0.5, 2.0, 1.5), noise=0.3):
    return FLOAConfig(
        channel=ChannelConfig(num_workers=U, sigma=sigma, noise_std=noise),
        power=PowerConfig(num_workers=U, dim=1000, p_max=1.0, policy=policy),
        attack=AttackConfig(attack=attack,
                            byzantine_mask=first_n_mask(U, n_atk)),
    )


@pytest.mark.parametrize("policy", [Policy.CI, Policy.BEV,
                                    Policy.TRUNCATED_CI, Policy.EF])
@pytest.mark.parametrize("attack,n_atk", [
    (AttackType.NONE, 0),
    (AttackType.STRONGEST, 2),
    (AttackType.SIGN_FLIP_PROTOCOL_POWER, 2),
    (AttackType.GAUSSIAN, 2),
])
def test_scenario_coefficients_match_dataclass(policy, attack, n_atk):
    """The branchless rewrite agrees with channel/power_control/attacks for
    every policy x attack combination (including the EF early-return)."""
    cfg = _floa(policy, attack, n_atk)
    sp = SC.from_floa(cfg, alpha=0.1)
    key = jax.random.PRNGKey(3)
    h = sample_channel_gains(key, cfg.channel)
    np.testing.assert_array_equal(np.asarray(SC.sample_gains(key, sp)),
                                  np.asarray(h))
    gbar, eps2 = jnp.float32(0.02), jnp.float32(1.7)
    assert float(sp.dim) == cfg.power.dim  # power-accounting D, not model size
    s, bias_w, jam_std, noise_std, dir_w = SC.scenario_coefficients(
        h, sp, gbar, eps2)
    assert float(dir_w) == 0.0  # no directional attack in this grid

    if policy == Policy.EF:
        sign = (jnp.where(cfg.attack.mask(), -1.0, 1.0)
                if attack != AttackType.NONE else jnp.ones((U,)))
        np.testing.assert_allclose(np.asarray(s), np.asarray(sign / U))
        assert float(bias_w) == 0.0 and float(jam_std) == 0.0
        assert float(noise_std) == 0.0
        return

    want_s, want_bias = A.signed_coefficients(
        h, cfg.power, cfg.channel, cfg.attack, gbar, eps2)
    want_jam = A.gaussian_jam_std(h, cfg.power, cfg.attack, eps2)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(bias_w), float(want_bias),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(jam_std), float(want_jam),
                               rtol=1e-6, atol=1e-7)
    assert float(noise_std) == np.float32(cfg.channel.noise_std)
    # honest rows equal the power-control amplitudes exactly
    honest = ~np.asarray(cfg.attack.mask())
    want_honest = np.asarray(
        transmit_amplitudes(h, cfg.power, cfg.channel) * h)
    np.testing.assert_allclose(np.asarray(s)[honest], want_honest[honest],
                               rtol=1e-6)


def test_scenario_stack_vmaps():
    """Stacked params + vmapped coefficients == per-scenario calls."""
    cfgs = [_floa(Policy.CI, AttackType.NONE, 0),
            _floa(Policy.BEV, AttackType.STRONGEST, 2),
            _floa(Policy.EF, AttackType.STRONGEST, 1),
            _floa(Policy.BEV, AttackType.GAUSSIAN, 3)]
    sps = [SC.from_floa(c, alpha=0.1) for c in cfgs]
    stacked = SC.stack(tuple(sps))
    h = jax.vmap(SC.sample_gains)(
        jax.random.split(jax.random.PRNGKey(0), len(cfgs)), stacked)
    gbar = jnp.arange(1.0, len(cfgs) + 1.0) * 0.01
    eps2 = jnp.arange(1.0, len(cfgs) + 1.0)
    out = jax.vmap(SC.scenario_coefficients)(h, stacked, gbar, eps2)
    for i, sp in enumerate(sps):
        want = SC.scenario_coefficients(h[i], sp, gbar[i], eps2[i])
        for got_leaf, want_leaf in zip(out, want):
            np.testing.assert_array_equal(np.asarray(got_leaf[i]),
                                          np.asarray(want_leaf))


# ----------------------------------------------------- engine equivalence


def _tiny_problem(rounds=6, batch=8, d_in=6, d_h=5):
    def loss(params, b):
        pred = jax.nn.relu(b["x"] @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - b["y"]) ** 2)
    k = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(k, (d_in, d_h)),
              "w2": jax.random.normal(k, (d_h, 1))}
    dim = sum(p.size for p in jax.tree_util.tree_leaves(params))
    batches = regression_batches(0, rounds, U * batch, d_in)
    return loss, params, dim, batches


def _tiny_floa(dim, policy=Policy.BEV, n_atk=1, noise=0.05,
               attack=AttackType.STRONGEST):
    return FLOAConfig(
        channel=ChannelConfig(num_workers=U, sigma=1.0,
                              noise_std=0.0 if policy == Policy.EF else noise),
        power=PowerConfig(num_workers=U, dim=dim, p_max=1.0, policy=policy),
        attack=AttackConfig(attack=attack if n_atk else AttackType.NONE,
                            byzantine_mask=first_n_mask(U, n_atk)),
    )


class _Replay:
    """Sampler stand-in that replays a pre-stacked batch dict round by round."""

    def __init__(self, batches):
        self.batches, self.t = batches, 0

    def next_round(self):
        out = {k: v[self.t] for k, v in self.batches.items()}
        self.t += 1
        return out


def test_run_scan_matches_loop_bitwise():
    """FLTrainer.run_scan must replay FLTrainer.run exactly: same keys, same
    batches -> bit-identical params and losses (noise and channel included)."""
    loss, params, dim, batches = _tiny_problem(rounds=7)
    tr = FLTrainer(loss_fn=loss, floa=_tiny_floa(dim), alpha=0.05)
    rounds = batches["x"].shape[0]
    p_loop, logs_loop = tr.run(dict(params), _Replay(batches), rounds,
                               jax.random.PRNGKey(3), eval_every=1)
    p_scan, logs_scan = tr.run_scan(dict(params), batches,
                                    jax.random.PRNGKey(3), eval_every=1)
    for k in params:
        np.testing.assert_array_equal(np.asarray(p_loop[k]),
                                      np.asarray(p_scan[k]))
    assert [l.loss for l in logs_loop] == [l.loss for l in logs_scan]
    assert [l.grad_norm for l in logs_loop] == [l.grad_norm for l in logs_scan]


def test_run_scan_matches_loop_digital_mode():
    loss, params, dim, batches = _tiny_problem(rounds=5)
    tr = FLTrainer(loss_fn=loss, floa=_tiny_floa(dim, policy=Policy.EF),
                   alpha=0.05, mode="digital", defense="median")
    rounds = batches["x"].shape[0]
    p_loop, _ = tr.run(dict(params), _Replay(batches), rounds,
                       jax.random.PRNGKey(2), eval_every=1)
    p_scan, _ = tr.run_scan(dict(params), batches, jax.random.PRNGKey(2),
                            eval_every=1)
    for k in params:
        np.testing.assert_array_equal(np.asarray(p_loop[k]),
                                      np.asarray(p_scan[k]))


def test_vmapped_grid_matches_singles():
    """A 2x2 (policy x attackers) vmapped grid reproduces each scenario's
    single-lane sequential run (tight tolerance: the S=4 and S=1 programs may
    schedule reductions differently, but the math is lane-independent)."""
    loss, params, dim, batches = _tiny_problem(rounds=5)
    cases = [ScenarioCase("ci0", _tiny_floa(dim, Policy.CI, 0), 0.05, seed=1),
             ScenarioCase("ci2", _tiny_floa(dim, Policy.CI, 2), 0.05, seed=2),
             ScenarioCase("bev0", _tiny_floa(dim, Policy.BEV, 0), 0.05, seed=3),
             ScenarioCase("bev2", _tiny_floa(dim, Policy.BEV, 2), 0.05, seed=4)]
    grid = SweepEngine(loss, SweepSpec.build(cases)).run(params, batches)
    for i, case in enumerate(cases):
        single = SweepEngine(loss, SweepSpec.build([case])).run(params, batches)
        np.testing.assert_allclose(grid.loss[i], single.loss[0],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(grid.grad_norm[i], single.grad_norm[0],
                                   rtol=1e-5, atol=1e-6)
        for gleaf, sleaf in zip(jax.tree_util.tree_leaves(grid.params),
                                jax.tree_util.tree_leaves(single.params)):
            np.testing.assert_allclose(np.asarray(gleaf[i]),
                                       np.asarray(sleaf[0]),
                                       rtol=1e-5, atol=1e-6)


def test_sweep_matches_looped_trainer():
    """One sweep lane == the looped FLTrainer on the same config and key
    (noiseless so the per-leaf vs flattened noise layouts cannot differ).

    The two programs compute the same math but XLA fuses them differently,
    so their f32 reductions (batch mean, worker stats, the combine) may sum
    in another order and each round can move the loss by an ulp or two that
    later rounds carry on.  The bound is 4 ulp per round; the worst lane
    measured 9 ulp after 6 rounds."""
    loss, params, dim, batches = _tiny_problem(rounds=6)
    rounds = batches["x"].shape[0]
    rtol = 4 * rounds * np.finfo(np.float32).eps
    for policy, n_atk in [(Policy.BEV, 1), (Policy.CI, 0), (Policy.EF, 2)]:
        floa = _tiny_floa(dim, policy, n_atk, noise=0.0)
        tr = FLTrainer(loss_fn=loss, floa=floa, alpha=0.05)
        _, logs = tr.run(dict(params), _Replay(batches), rounds,
                         jax.random.PRNGKey(9), eval_every=1)
        res = SweepEngine(loss, SweepSpec.build(
            [ScenarioCase("x", floa, 0.05, seed=9)])).run(params, batches)
        np.testing.assert_allclose(
            np.asarray([l.loss for l in logs]), res.loss[0],
            rtol=rtol, atol=0)


def test_sweep_honors_power_accounting_dim():
    """power.dim is the power-accounting D of eq. (4) and may differ from the
    model's true parameter count; the sweep lane must use the config value
    (as FLTrainer does), not the flattened gradient size."""
    loss, params, dim, batches = _tiny_problem(rounds=4)
    floa = _tiny_floa(dim * 7, Policy.BEV, 1, noise=0.0)  # deliberate mismatch
    tr = FLTrainer(loss_fn=loss, floa=floa, alpha=0.05)
    _, logs = tr.run(dict(params), _Replay(batches), 4, jax.random.PRNGKey(9),
                     eval_every=1)
    res = SweepEngine(loss, SweepSpec.build(
        [ScenarioCase("x", floa, 0.05, seed=9)])).run(params, batches)
    np.testing.assert_allclose(np.asarray([l.loss for l in logs]),
                               res.loss[0], rtol=1e-6, atol=1e-7)


def _grid_cases(dim):
    """Policy x attack grid covering every branchless code path (noise,
    jamming, EF early-return, truncated-CI) for the engine-equivalence tests."""
    return [
        ScenarioCase("ci0", _tiny_floa(dim, Policy.CI, 0), 0.05, seed=1),
        ScenarioCase("bev2", _tiny_floa(dim, Policy.BEV, 2), 0.05, seed=2),
        ScenarioCase("ef1", _tiny_floa(dim, Policy.EF, 1), 0.05, seed=3),
        ScenarioCase("tci1", _tiny_floa(dim, Policy.TRUNCATED_CI, 1), 0.04,
                     seed=4),
        ScenarioCase("jam2", _tiny_floa(dim, Policy.BEV, 2,
                                        attack=AttackType.GAUSSIAN), 0.05,
                     seed=5),
        ScenarioCase("sf1", _tiny_floa(
            dim, Policy.CI, 1,
            attack=AttackType.SIGN_FLIP_PROTOCOL_POWER), 0.05, seed=6),
    ]


def test_flat_state_strict_matches_tree_state_bitwise():
    """Under strict_numerics (on BOTH engines) the flat-state scan replays
    the tree-state engine bit-for-bit: same grads (the pytree boundary moves
    inside the loss closure, which is exact), same stats (both reduce
    leaf-segmented off the materialized slab), same combine/update ops.
    Without the flag each path lets XLA fuse its stats reduction into a
    different producer, so they only agree to fp rounding (next test)."""
    loss, params, dim, batches = _tiny_problem(rounds=7)
    spec = SweepSpec.build(_grid_cases(dim))
    eval_fn = lambda p: {"accuracy": jnp.mean(p["w1"]) * 0 + 0.5}
    tree = SweepEngine(
        loss, spec, eval_fn=eval_fn, plan=ExecutionPlan(
            flat_state=False, strict_numerics=True)).run(params, batches)
    flat = SweepEngine(
        loss, spec, eval_fn=eval_fn,
        plan=ExecutionPlan(strict_numerics=True)).run(params, batches)
    np.testing.assert_array_equal(tree.loss, flat.loss)
    np.testing.assert_array_equal(tree.grad_norm, flat.grad_norm)
    np.testing.assert_array_equal(
        np.asarray(tree.metrics["accuracy"]),
        np.asarray(flat.metrics["accuracy"]))
    for k in tree.params:
        np.testing.assert_array_equal(np.asarray(tree.params[k]),
                                      np.asarray(flat.params[k]))


def test_flat_state_default_matches_tree_state():
    """Default (fast) flat mode lets XLA fuse the stats reduction into the
    gradient producer, so it only agrees with the tree path to fp rounding."""
    loss, params, dim, batches = _tiny_problem(rounds=7)
    spec = SweepSpec.build(_grid_cases(dim))
    tree = SweepEngine(
        loss, spec, plan=ExecutionPlan(flat_state=False)).run(params, batches)
    flat = SweepEngine(loss, spec).run(params, batches)
    np.testing.assert_allclose(tree.loss, flat.loss, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(tree.grad_norm, flat.grad_norm,
                               rtol=1e-5, atol=1e-6)
    for k in tree.params:
        np.testing.assert_allclose(np.asarray(tree.params[k]),
                                   np.asarray(flat.params[k]),
                                   rtol=1e-5, atol=1e-6)


def test_make_row_unflatten_roundtrip():
    from repro.core.aggregation import flatten_worker_grads
    from repro.fl.sweep import make_row_unflatten

    params = {"a": jnp.arange(6.0).reshape(2, 3),
              "b": jnp.arange(4.0) + 10.0,
              "c": jnp.float32(99.0).reshape(())}
    unflatten_row, sizes = make_row_unflatten(params)
    assert sum(sizes) == 11
    flat, _ = flatten_worker_grads(
        jax.tree_util.tree_map(lambda x: x[None], params), batch_dims=1)
    back = unflatten_row(flat[0])
    for k in params:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params[k]))


def test_flat_scalar_stats_matches_tree_stats():
    """Flat stats (segmented or whole-row) reproduce the pytree stats to fp
    rounding.  (The engine-level bitwise guarantee — strict flat == tree —
    is pinned end-to-end by test_flat_state_strict_matches_tree_state_bitwise;
    eagerly, XLA may vectorize a slice-reduce and a leaf-reduce differently,
    so this unit test only asks for tight closeness.)"""
    import repro.core.standardize as STD

    rng = np.random.default_rng(0)
    grads = {"w": jnp.asarray(rng.normal(size=(U, 7, 3)).astype(np.float32)),
             "b": jnp.asarray(rng.normal(size=(U, 5)).astype(np.float32))}
    gbar_t, eps2_t = STD.per_worker_scalar_stats(grads)
    from repro.core.aggregation import flatten_worker_grads
    flat, _ = flatten_worker_grads(grads, batch_dims=1)
    for sizes in ((21, 5), None):
        gbar_f, eps2_f = STD.flat_scalar_stats(flat, sizes=sizes)
        np.testing.assert_allclose(np.asarray(gbar_t), np.asarray(gbar_f),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(eps2_t), np.asarray(eps2_f),
                                   rtol=1e-6, atol=1e-7)


def test_flat_partial_stats_recombine_and_ignore_zero_padding():
    """The model-sharded stats contract: column-block partial sums, summed
    across shards and finished by `stats_from_partials` with the REAL D,
    reproduce the unsharded `flat_scalar_stats`; zero ghost-pad columns
    contribute exactly nothing."""
    import repro.core.standardize as STD

    rng = np.random.default_rng(1)
    u, d, pad, shards = 5, 37, 11, 4
    flat = jnp.asarray(rng.normal(size=(u, d)).astype(np.float32))
    padded = jnp.pad(flat, ((0, 0), (0, pad)))
    d_loc = (d + pad) // shards
    s1 = jnp.zeros((u,), jnp.float32)
    s2 = jnp.zeros((u,), jnp.float32)
    for m in range(shards):
        p1, p2 = STD.flat_partial_stats(
            padded[:, m * d_loc:(m + 1) * d_loc])
        s1, s2 = s1 + p1, s2 + p2
    gbar, eps2 = STD.stats_from_partials(s1, s2, d)
    gbar_ref, eps2_ref = STD.flat_scalar_stats(flat)
    np.testing.assert_allclose(np.asarray(gbar), np.asarray(gbar_ref),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(eps2), np.asarray(eps2_ref),
                               rtol=1e-6, atol=1e-7)
    # Whole-row partials (single shard, no padding) finish to the same
    # values exactly — the epilogue is the identical mean/floor math.
    w1, w2 = STD.flat_partial_stats(flat)
    g2, e2 = STD.stats_from_partials(w1, w2, d)
    np.testing.assert_array_equal(np.asarray(g2), np.asarray(gbar_ref))
    np.testing.assert_array_equal(np.asarray(e2), np.asarray(eps2_ref))


def test_scenario_pad_lanes():
    cfgs = [_floa(Policy.CI, AttackType.NONE, 0),
            _floa(Policy.BEV, AttackType.STRONGEST, 2)]
    stacked = SC.stack(tuple(SC.from_floa(c, alpha=0.1) for c in cfgs))
    padded = SC.pad_lanes(stacked, 5)
    for leaf_p, leaf_s in zip(jax.tree_util.tree_leaves(padded),
                              jax.tree_util.tree_leaves(stacked)):
        assert leaf_p.shape[0] == 5
        np.testing.assert_array_equal(np.asarray(leaf_p[:2]),
                                      np.asarray(leaf_s))
        for g in range(2, 5):  # ghost lanes replicate the last real lane
            np.testing.assert_array_equal(np.asarray(leaf_p[g]),
                                          np.asarray(leaf_s[-1]))
    assert SC.pad_lanes(stacked, 2) is stacked


def test_run_scan_flat_matches_sweep_lane():
    """FLTrainer.run_scan(flat=True) delegates to a single-lane flat-state
    sweep; it must reproduce that engine's lane bit-for-bit."""
    loss, params, dim, batches = _tiny_problem(rounds=6)
    floa = _tiny_floa(dim, Policy.BEV, 1)
    tr = FLTrainer(loss_fn=loss, floa=floa, alpha=0.05)
    key = jax.random.PRNGKey(7)
    p_flat, logs_flat = tr.run_scan(dict(params), batches, key, eval_every=1,
                                    flat=True)
    eng = SweepEngine(loss, SweepSpec.build(
        [ScenarioCase("scan", floa, 0.05)]), eval_every=0)
    res = eng.run(params, batches, keys=key[None])
    np.testing.assert_array_equal(
        np.asarray([l.loss for l in logs_flat]), res.loss[0])
    np.testing.assert_array_equal(
        np.asarray([l.grad_norm for l in logs_flat]), res.grad_norm[0])
    for k in params:
        np.testing.assert_array_equal(
            np.asarray(p_flat[k]), np.asarray(res.params[k][0]))


def test_run_scan_flat_matches_loop_noiseless():
    """On noiseless channels (where the per-leaf vs flattened noise layouts
    cannot differ) the flat run_scan replays the looped trainer to fp
    rounding."""
    loss, params, dim, batches = _tiny_problem(rounds=6)
    floa = _tiny_floa(dim, Policy.BEV, 1, noise=0.0)
    tr = FLTrainer(loss_fn=loss, floa=floa, alpha=0.05)
    rounds = batches["x"].shape[0]
    p_loop, logs_loop = tr.run(dict(params), _Replay(batches), rounds,
                               jax.random.PRNGKey(9), eval_every=1)
    p_flat, logs_flat = tr.run_scan(dict(params), batches,
                                    jax.random.PRNGKey(9), eval_every=1,
                                    flat=True)
    np.testing.assert_allclose(
        np.asarray([l.loss for l in logs_loop]),
        np.asarray([l.loss for l in logs_flat]), rtol=1e-6, atol=1e-7)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_loop[k]),
                                   np.asarray(p_flat[k]),
                                   rtol=1e-5, atol=1e-6)


def test_sweep_metrics_and_logs_schedule():
    loss, params, dim, batches = _tiny_problem(rounds=6)
    spec = SweepSpec.build(
        [ScenarioCase("a", _tiny_floa(dim), 0.05, seed=0),
         ScenarioCase("b", _tiny_floa(dim, n_atk=0), 0.05, seed=1)])
    eval_fn = lambda p: {"accuracy": jnp.mean(p["w1"]) * 0 + 0.5}
    res = SweepEngine(loss, spec, eval_fn=eval_fn).run(params, batches)
    assert res.loss.shape == (2, 6)
    assert res.metrics["accuracy"].shape == (2, 6)
    logs = res.logs("b", eval_every=2)
    assert [l.step for l in logs] == [0, 2, 4, 5]
    assert logs[-1].accuracy == 0.5


def test_stack_rounds_replays_sampler_stream():
    shards = toy_shards(0, U)
    a = FederatedSampler(shards, batch_per_worker=4, seed=11)
    b = FederatedSampler(shards, batch_per_worker=4, seed=11)
    stacked = a.stack_rounds(3)
    for t in range(3):
        nxt = b.next_round()
        for k in nxt:
            np.testing.assert_array_equal(stacked[k][t], nxt[k])
