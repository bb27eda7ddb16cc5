"""The plain reference split over several chips against the same reference
on one, on four virtual CPU devices (perfbench/tests/conftest.py):

  * every number it returns agrees within 1e-6 relative, for the analog
    policies under each analog attack and for screening lanes;
  * its D-wide draws do not depend on the split;
  * on one device the round program is the one it was before the split
    existed (its jaxpr's digest, taken from that version).

    JAX_PLATFORMS=cpu PYTHONPATH=perfbench:src python -m pytest -q perfbench/tests
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedref
import harness
import traffic
from test_check import SMALL

ANALOG = {"defense": {"name": "floa"}, "policy": ["bev", "ci", "ef"],
          "attackers": [1], "noise": "snr", "alpha": {"alpha_hat": 0.1},
          "attack": ["strongest", "gaussian", "colluding", "omniscient"]}
SCREENED = {"defense": [{"name": "median"},
                        {"name": "multi_krum", "num_byzantine": 3, "multi": 3},
                        {"name": "geometric_median"}],
            "policy": "ef", "attackers": [1], "attack": "strongest",
            "noise": 0.0, "alpha": {"lr": 0.1},
            "variants": [{}, {"participants": 7, "tag": "K7"}]}
GRIDS = {"showdown-seeds": [ANALOG, SCREENED],
         "qwen3-4b-ota": [{**ANALOG, "policy": "bev", "noise": 0.05,
                           "alpha": {"lr": 0.2}}]}


def _call(name, seed):
    small = SMALL[name]
    cell = harness.Cell(name, overrides={
        **small, "mix": {**small["mix"], "lanes": GRIDS[name]}})
    model = cell.cfg_mod.build(cell.cfg, cell.mix, seed)
    call = traffic.Traffic(cell.mix, cell.cfg, model["dim"], seed).next_call()
    return cell, model, call


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


@pytest.mark.parametrize("name", sorted(GRIDS))
def test_the_split_reference_agrees_with_one_device(name):
    assert len(jax.devices()) >= 4
    cell, model, call = _call(name, 2**31 + 29)
    rounds = cell.mix["ref_rounds"]
    one, four = (harness.reference_run(model, call["lanes"], call["keys"],
                                       rounds, devices=jax.devices()[:n])
                 for n in (1, 4))
    for lane, a, b in zip(call["lanes"], four, one):
        for key in ("loss", "grad_norm", "agg_leaf_norms"):
            assert _rel(a[key], b[key]) < 1e-6, (lane["name"], key)
        assert a["accuracy1"] == b["accuracy1"], lane["name"]
        for x, y in zip(jax.tree_util.tree_leaves(a["params"]),
                        jax.tree_util.tree_leaves(b["params"])):
            assert np.max(np.abs(x - y)) <= 1e-6 * np.max(np.abs(y)), \
                lane["name"]


@pytest.mark.parametrize("d", [50_890, 4096])
def test_draws_do_not_depend_on_the_split(d):
    assert jax.config.jax_threefry_partitionable
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("d",))
    key = jax.random.PRNGKey(2**31 + 3)
    split = jax.jit(lambda k: fedref._split(jax.random.normal(k, (d,)), mesh))
    out = split(key)
    assert len(out.sharding.device_set) > 1
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jax.random.normal(key, (d,))))


# sha256 of the one-device round program's jaxpr, per defense, at the sizes
# of _call("showdown-seeds", 7) and _call("qwen3-4b-ota", 7), as the
# reference built it before it could be split.
ONE_DEVICE_JAXPR = {
    "showdown-seeds/floa":
        "3350f35c1ccc24197c8770b88815ca466c553203bf4a09cd05275ec502f9e6f3",
    "showdown-seeds/median":
        "a7c63c1c0441eccffaef536e7f619484c5567c1383265f98726b4afcd7619c16",
    "showdown-seeds/multi_krum":
        "7d78e561389bb33079f0a7639fb61133ca567b1ad19e101e71503e7903bc0b4b",
    "showdown-seeds/geometric_median":
        "3cc6049ed76d96da88e12f24f2faaf29565b4be6583b937638c1127459657a6a",
    "qwen3-4b-ota/floa":
        "330bc4a966222d401b6801e117668327992337f98d8e06c23f6f04f38e706260",
}


def _jaxprs(name):
    cell, model, call = _call(name, 7)
    staged = fedref.Staged(model["params0"], model["batches"], 1,
                           cast_batch=model["cast_batch"])
    out = {}
    for lane, key in zip(call["lanes"], call["keys"]):
        step = fedref._compiled(lane["defense"], lane["num_workers"],
                                lane["gm_iters"], model["ref_loss"],
                                staged.treedef, staged.shapes, staged.sizes,
                                staged.dt, None, staged.mesh)
        key = jnp.asarray(key, jnp.uint32)
        h = fedref._h0(key, float(lane["sigma"]), lane["num_workers"],
                       staged.dt)
        with jax.default_matmul_precision("highest"):
            text = str(jax.make_jaxpr(step)(
                staged.w0, h, key, staged.batches[0],
                fedref.lane_numbers(lane, staged.dt)))
        out[f"{name}/{lane['defense']}"] = hashlib.sha256(
            text.encode()).hexdigest()
    return out


def test_one_device_builds_the_round_program_it_built_before():
    got = {**_jaxprs("showdown-seeds"), **_jaxprs("qwen3-4b-ota")}
    assert got == ONE_DEVICE_JAXPR
