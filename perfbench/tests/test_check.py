"""The check that decides `correct`, at sizes a CPU test run can hold.

  * the plain reference agrees with the program on both configurations;
  * the control (the reference in bfloat16, put in the program's place)
    fails the cell's limits;
  * a whole run of the harness, with the chip look skipped and the timed
    path broken underneath (the step returns its state unchanged, each
    worker's gradient leaves out half its batch, the loss the program
    produces is altered), comes out not correct, and comes out correct
    with the path intact.

    JAX_PLATFORMS=cpu PYTHONPATH=perfbench:src python -m pytest -q perfbench/tests
"""
import jax
import jax.numpy as jnp
import pytest

import harness

SMALL_GRID = [
    {"defense": {"name": "floa"}, "policy": ["bev", "ci"], "attackers": [0, 1],
     "attack": "strongest", "noise": "snr", "alpha": {"alpha_hat": 0.1},
     "variants": [{}, {"markov_rho": 0.9, "tag": "markov"},
                  {"participants": 7, "tag": "K7"}]},
    {"defense": {"name": "floa"}, "policy": "bev", "attackers": [1],
     "attack": ["colluding", "omniscient"], "noise": "snr",
     "alpha": {"alpha_hat": 0.1}},
    {"defense": [{"name": "median"}, {"name": "trimmed_mean", "trim": 3},
                 {"name": "multi_krum", "num_byzantine": 3, "multi": 3},
                 {"name": "geometric_median"}],
     "policy": "ef", "attackers": [1], "attack": "strongest", "noise": 0.0,
     "alpha": {"lr": 0.1}, "variants": [{}, {"participants": 7, "tag": "K7"}]},
]

SMALL = {
    "showdown-seeds": {
        "cfg": {"train_samples": 200, "test_samples": 50,
                "batch_per_worker": 4},
        "mix": {"rounds": 3, "eval_every": 3, "replicas": 1, "ref_rounds": 3,
                "lanes": SMALL_GRID}},
    "qwen3-4b-ota": {
        "cfg": {"hidden_size": 64, "intermediate_size": 128,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 16, "vocab_size": 300},
        "mix": {"rounds": 3, "ref_rounds": 3,
                "data": {"kind": "markov_tokens", "seqs_per_worker": 2,
                         "seq_len": 16, "branch": 4}}},
}


def _program_and_reference(name, seed, dtype="float32"):
    cell = harness.Cell(name, overrides=SMALL[name])
    system = harness.System(cell, seed, jax.devices()[:1])
    call = system.traffic.next_call()
    res = system.call(call)
    rounds = cell.mix["ref_rounds"]
    prog = harness.kept_result(res, rounds == cell.mix["rounds"])
    ref = harness.reference_run(system.model, call["lanes"], call["keys"],
                                rounds, dtype=dtype)
    return cell, system, prog, ref, call


@pytest.mark.parametrize("name", sorted(SMALL))
def test_reference_agrees_with_the_program(name):
    cell, system, prog, ref, call = _program_and_reference(name, 2**31 + 11)
    vals = harness.readings(prog, ref, system.model["params0"],
                            cell.mix["ref_rounds"], call["lanes"])
    # float32 on the CPU: both sides differ by rounding only
    assert vals["loss_gap"] < 1e-5, vals
    assert vals["gnorm_gap"] < 1e-4, vals
    assert vals.get("dparam_gap", 0.0) < 1e-4, vals
    assert vals.get("acc1_gap", 0.0) == 0.0, vals


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_fails_the_limits(name):
    import calibrate
    cell = harness.Cell(name, overrides=SMALL[name])
    system = harness.System(cell, 77, jax.devices()[:1])
    call = system.traffic.next_call()
    rounds = cell.mix["ref_rounds"]
    ref32 = harness.reference_run(system.model, call["lanes"], call["keys"],
                                  rounds)
    ref16 = harness.reference_run(system.model, call["lanes"], call["keys"],
                                  rounds, dtype="bfloat16")
    vals = harness.readings(calibrate.as_result(ref16), ref32,
                            system.model["params0"], rounds, call["lanes"])
    checks = harness.judge(vals, cell.limits)
    assert checks, "the cell has no limits"
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def _half_rows(orig):
    def per_worker_grads(loss_fn, params, batch, num_workers, has_aux=False):
        n = jax.tree_util.tree_leaves(batch)[0].shape[0] // num_workers
        idx = jnp.concatenate([jnp.arange(i * n, i * n + n // 2)
                               for i in range(num_workers)])
        half = jax.tree_util.tree_map(lambda x: x[idx], batch)
        return orig(loss_fn, params, half, num_workers, has_aux)
    return per_worker_grads


def _break(monkeypatch, fault, name):
    import repro.fl.sweep as sweep
    if fault == "unchanged":
        orig = sweep.SweepSpec.stacked_params
        monkeypatch.setattr(
            sweep.SweepSpec, "stacked_params",
            lambda self: orig(self)._replace(alpha=jnp.zeros(len(self))))
    elif fault == "half_batch":
        monkeypatch.setattr(sweep, "per_worker_grads",
                            _half_rows(sweep.per_worker_grads))
    elif fault == "exchange_left_out":
        # The lanes of chips 1..3 are read back from chip 0's block: the
        # gather of every chip's results into lane order is left out.
        import dataclasses
        orig = sweep.SC.build_lane_groups

        def first_chip_only(codes, shards):
            g = orig(codes, shards)
            return dataclasses.replace(g, inverse=tuple(
                i % g.lanes_per_shard for i in g.inverse))
        monkeypatch.setattr(sweep.SC, "build_lane_groups", first_chip_only)
    elif fault == "answer_altered":
        if name == "showdown-seeds":
            import repro.models as models
            orig = models.mlp_loss
            monkeypatch.setattr(models, "mlp_loss",
                                lambda p, b: orig(p, b) * 1.01)
        else:
            import repro.models.transformer as tr
            orig = tr.lm_loss
            monkeypatch.setattr(tr, "lm_loss",
                                lambda p, b, c: orig(p, b, c) * 1.01)


def test_a_fault_in_one_defense_family_is_not_correct(monkeypatch):
    """The sort behind the median and trimmed-mean lanes (4 of 22) returns
    each column shifted by one worker: the median lane does not move, the
    family numbers do."""
    import repro.core.defenses as defenses
    orig = defenses.sorted_columns
    monkeypatch.setattr(defenses, "sorted_columns",
                        lambda flat, **kw: jnp.roll(orig(flat, **kw), 1, 0))
    name = "showdown-seeds"
    line = harness.run_cell(name, 5, 0.01, False, 0.0, jax.devices()[:1],
                            overrides=SMALL[name])
    checks = line["checks"]
    assert line["correct"] is False, line
    for k in ("loss_gap_median", "gnorm_gap_median"):
        assert checks[k]["value"] <= checks[k]["limit"], checks
    assert any(checks[k]["value"] > checks[k]["limit"]
               for k in ("loss_gap_family", "gnorm_gap_family")), checks


@pytest.mark.parametrize("extra", [
    {"plan": {"chunk_rounds": 1}},
    {"plan": {"chunk_rounds": 1, "checkpoint": True}, "mesh": {}},
])
def test_a_mix_sets_the_execution_plan(monkeypatch, extra):
    made = []
    real_mkdtemp = harness.tempfile.mkdtemp
    monkeypatch.setattr(harness.tempfile, "mkdtemp",
                        lambda **kw: made.append(real_mkdtemp(**kw)) or made[-1])
    small = SMALL["showdown-seeds"]
    overrides = {**small, "mix": {**small["mix"], **extra}}
    line = harness.run_cell("showdown-seeds", 9, 0.01, False, 0.0,
                            jax.devices()[:1], overrides=overrides)
    assert line["correct"], line
    assert set(line["metrics"]) == {"lane_rounds_per_s", "setup_s"}, line
    ckpt = [d for d in made if "ckpt" in d]
    assert len(ckpt) == int("checkpoint" in extra["plan"])
    assert not any(harness.pathlib.Path(d).exists() for d in ckpt)


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("name", sorted(SMALL))
def test_a_run_with_a_broken_path_is_not_correct(monkeypatch, name, fault):
    _break(monkeypatch, fault, name)
    line = harness.run_cell(name, 5, 0.01, False, 0.0, jax.devices()[:1],
                            overrides=SMALL[name])
    assert line["checks"], line
    assert line["correct"] is (fault is None), line


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch",
                                   "answer_altered", "exchange_left_out"])
def test_a_four_chip_run_with_a_broken_path_is_not_correct(monkeypatch,
                                                           fault):
    """showdown-seeds-data4 on four devices, the reference split over them."""
    name = "showdown-seeds-data4"
    _break(monkeypatch, fault, "showdown-seeds")
    small = SMALL["showdown-seeds"]
    overrides = {**small, "mix": {**small["mix"], "mesh": {}}}
    line = harness.run_cell(name, 5, 0.01, False, 0.0, jax.devices()[:4],
                            overrides=overrides)
    assert line["device"]["count"] == 4, line
    assert line["checks"], line
    assert line["correct"] is (fault is None), line
