"""Kernel operation and byte counts, FLOPs per lane-round, and the traffic
generator's learning-rate rule, each checked by hand.

    PYTHONPATH=perfbench python -m pytest -q perfbench/tests
"""
import json
import pathlib

import pytest

import harness
import traffic

BENCH = pathlib.Path(__file__).resolve().parents[1]


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _mix(name):
    return json.loads((BENCH / "mixes" / f"{name}.json").read_text())


def test_sort_columns_counts():
    k = harness.load_module(BENCH / "kernels" / "sort_columns.py")
    c = k.cost(lanes=3, u=10, d=2048)
    assert c["bytes"] == 2 * 3 * 10 * 2048 * 4          # read + write
    assert c["ops"] == 3 * 2048 * 45 * 2                # 45 pairs, min + max
    assert c["bound"] == "bytes"


def test_floa_step_batched_counts():
    k = harness.load_module(BENCH / "kernels" / "floa_step_batched.py")
    c = k.cost(lanes=2, u=4, d=4096)
    # G: 2*4*4096 reads, w and z: 2*2*4096 reads, w_new and gagg: 2*2*4096
    assert c["bytes"] == (2 * 4 * 4096 + 4 * 2 * 4096) * 4
    assert c["flops"] == 2 * 4096 * (2 * 4 + 5)
    assert c["bound"] == "bytes"


def test_paper_mlp_flops_per_lane_round():
    m = harness.load_module(BENCH / "configs" / "paper-mlp.py")
    cfg = _cfg("paper-mlp")
    assert m.num_params(cfg) == 50_890
    n = 784 * 64 + 64 * 10                               # 50,816 matmul weights
    # 8 N per sample of the 320-sample round batch, plus the two 1,000-sample
    # accuracy passes (rounds 0 and 99) spread over the 100 rounds
    want = 8 * n * 320 + 2 * n * 1000 * 2 / 100
    assert m.flops_per_lane_round(cfg, _mix("showdown-seeds")) == want
    assert want == pytest.approx(132_121_600)


def test_qwen_flops_per_lane_round_and_size():
    m = harness.load_module(BENCH / "configs" / "qwen3-4b-1l.py")
    cfg = _cfg("qwen3-4b-1l")
    assert m.padded_vocab(cfg) == 19_200
    n_layer = (2 * 2560 * 32 * 128 + 2 * 2560 * 8 * 128 + 3 * 2560 * 9728)
    n = n_layer + 2560 * 19_200                            # tied head
    tokens = 4 * 2 * 512
    want = tokens * (8 * n + 16 * 32 * 128 * 512)
    assert m.flops_per_lane_round(cfg, _mix("qwen3-4b-ota")) == want
    assert want == pytest.approx(5.052e12, rel=1e-3)
    # D from the shapes: embedding, final norm, and the layer's leaves
    import math
    import jax
    d = sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        m.shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    assert d == 150_085_376


@pytest.mark.parametrize("policy,n,want", [
    # BEV, U = 10, D = 50,890, N = 0: omega = 10 t, Omega = 10 * 10 * 2 / D
    ("bev", 0, 0.1 * 10 * (3.141592653589793 / (2 * 50_890)) ** 0.5
     / (100 * 2 / 50_890)),
    ("ef", 3, 0.1),
])
def test_theory_alpha(policy, n, want):
    assert traffic.theory_alpha(policy, 10, n, 50_890, 0.1) == pytest.approx(want)


def test_theory_alpha_matches_the_paper_formulas_in_the_program():
    theory = pytest.importorskip("repro.core.theory")
    for policy in ("bev", "ci"):
        for n in (0, 1, 3, 4):
            tp = theory.TheoryParams(num_workers=10, num_attackers=n,
                                     dim=50_890)
            assert traffic.theory_alpha(policy, 10, n, 50_890, 0.1) == \
                pytest.approx(theory.alpha_from_alpha_hat(tp, policy, 0.1))


def test_showdown_grid_has_the_examples_68_lanes():
    lanes = traffic.expand_lanes(_mix("showdown-seeds"), _cfg("paper-mlp"),
                                 50_890)
    assert len(lanes) == 68
    assert len({x["name"] for x in lanes}) == 68
    by = {}
    for x in lanes:
        by[x["defense"]] = by.get(x["defense"], 0) + 1
    assert by == {"floa": 36, "mean": 4, "median": 8, "trimmed_mean": 8,
                  "krum": 4, "multi_krum": 4, "geometric_median": 4}
    t = traffic.Traffic(_mix("showdown-seeds"), _cfg("paper-mlp"), 50_890, 2**33 + 5)
    assert t.num_lanes == 272
    a, b = t.next_call(), t.next_call()
    assert a["keys"].shape == (272, 2) and (a["keys"] != b["keys"]).any()


def test_keys_equal_prng_keys():
    import jax
    import numpy as np
    seeds = np.array([0, 5, 2**31 - 1])
    want = np.stack([np.asarray(jax.random.key_data(jax.random.PRNGKey(int(s))))
                     for s in seeds])
    assert (traffic.keys_of(seeds) == want).all()
