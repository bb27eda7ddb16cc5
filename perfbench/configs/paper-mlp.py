"""paper-mlp: the paper's section IV model, MLP 784-64-10 with ReLU and
cross-entropy (D = 50,890), and its plain reference.

`build` hands the harness the program's own loss and accuracy functions,
weights drawn on the device from the seed, and the round batches of the
cell's mix; `ref_loss` / `ref_accuracy` are the plain forward pass the
reference runs in the program's place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def num_params(cfg: dict) -> int:
    i, h, c = cfg["d_in"], cfg["d_hidden"], cfg["n_classes"]
    return i * h + h + h * c + c


def matmul_params(cfg: dict) -> int:
    return cfg["d_in"] * cfg["d_hidden"] + cfg["d_hidden"] * cfg["n_classes"]


def init_weights(cfg: dict, seed: int):
    """He-normal matrices and zero biases, made on the device in one call."""
    i, h, c = cfg["d_in"], cfg["d_hidden"], cfg["n_classes"]

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (i, h), jnp.float32) * (2.0 / i) ** 0.5,
                "b1": jnp.zeros((h,), jnp.float32),
                "w2": jax.random.normal(k2, (h, c), jnp.float32) * (2.0 / h) ** 0.5,
                "b2": jnp.zeros((c,), jnp.float32)}

    return make(jax.random.PRNGKey(seed))


def ref_logits(p, x):
    h = jnp.maximum(x @ p["w1"] + p["b1"], 0)
    return h @ p["w2"] + p["b2"]


def ref_loss(p, batch):
    """Mean cross-entropy of the batch, in the dtype of the weights."""
    logits = ref_logits(p, batch["x"].astype(p["w1"].dtype))
    logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=-1))


def ref_accuracy(p, x, y):
    return jnp.mean(jnp.argmax(ref_logits(p, x.astype(p["w1"].dtype)), -1) == y)


def flops_per_lane_round(cfg: dict, mix: dict) -> float:
    """Model FLOPs of one lane-round: 6 N T for the per-worker gradients and
    2 N T for the round's reported loss (T = U x B samples), plus the test
    accuracy forward passes, 2 N per test sample at each evaluated round,
    spread over the call's rounds.  N counts the matmul weights."""
    n = matmul_params(cfg)
    t = cfg["num_workers"] * cfg["batch_per_worker"]
    rounds, every = mix["rounds"], mix.get("eval_every", 0)
    evals = len({r for r in range(rounds)
                 if r == rounds - 1 or (every > 0 and r % every == 0)})
    return 8.0 * n * t + 2.0 * n * cfg["test_samples"] * evals / rounds


def build(cfg: dict, mix: dict, seed: int) -> dict:
    """The program's loss and evaluation, weights and batches for one run."""
    import traffic
    from repro.models import mlp_accuracy, mlp_loss

    seeds = np.random.SeedSequence([seed, 2]).generate_state(4)
    x, y = traffic.make_digits(cfg["train_samples"], int(seeds[0]))
    # A fixed test set: the program's evaluation holds it as a constant, so
    # every seed runs the same compiled program.
    xt, yt = traffic.make_digits(cfg["test_samples"], cfg["test_seed"])
    batches = traffic.digit_rounds(x, y, cfg["num_workers"],
                                   cfg["batch_per_worker"], mix["rounds"],
                                   int(seeds[2]))
    xt_d, yt_d = jnp.asarray(xt), jnp.asarray(yt)
    return {
        "dim": num_params(cfg),
        "params0": init_weights(cfg, int(seeds[3] >> 1)),
        "batches": batches,
        "loss_fn": mlp_loss,
        "eval_fn": lambda p: {"accuracy": mlp_accuracy(p, xt_d, yt_d)},
        "ref_loss": ref_loss,
        "ref_eval": lambda p: ref_accuracy(p, xt_d, yt_d),
        "cast_batch": lambda b, dt: {"x": b["x"].astype(dt), "y": b["y"]},
        "flops_per_lane_round": flops_per_lane_round(cfg, mix),
    }
