"""qwen3-4b-1l: one decoder layer of Qwen3-4B at its published widths, run
through the program's own `init_lm`-layout parameters and `lm_loss`, and
its plain reference.

The reference is the Qwen3 decoder written out in jnp: embedding lookup,
RMSNorm, grouped-query attention with per-head q/k RMSNorm and RoPE, causal
softmax, SwiGLU feed-forward, final RMSNorm, tied output head, next-token
cross-entropy over the vocabulary slice.  It reads the same parameter tree
as the program, so it follows two layout choices of the program, noted in
the configuration's `assumed`: norm scales are offsets from 1, and RoPE
rotates interleaved pairs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def shapes(cfg: dict) -> dict:
    """The parameter tree's leaf shapes ([layers, ...] under `blocks.b0`)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n = cfg["num_hidden_layers"]
    return {
        "embed": (padded_vocab(cfg), d),
        "final_norm": (d,),
        "blocks": {"b0": {
            "ln1": (n, d), "ln2": (n, d),
            "attn": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                     "wv": (n, d, kv, hd), "wo": (n, h, hd, d),
                     "q_norm": (n, hd), "k_norm": (n, hd)},
            "ffn": {"wi": (n, d, f), "wg": (n, d, f), "wo": (n, f, d)}}},
    }


def init_weights(cfg: dict, seed: int):
    """Truncated-normal matrices scaled by 1/sqrt(fan_in) and zero norm
    offsets, made on the device in one jitted call."""
    tree = shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda x: isinstance(x, tuple))[0]]

    def fan_in(path, shape):
        if "norm" in path or "ln" in path:
            return None
        if path.endswith("['embed']"):
            return shape[1]
        if "['attn']['wo']" in path:
            return shape[1] * shape[2]
        return shape[1]  # [layers, fan_in, ...]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shape, path in zip(keys, leaves, paths):
            fi = fan_in(path, shape)
            if fi is None:
                out.append(jnp.zeros(shape, jnp.float32))
            else:
                out.append(jax.random.truncated_normal(
                    k, -2.0, 2.0, shape, jnp.float32) / math.sqrt(fi))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(jax.random.PRNGKey(seed))


def program_config(cfg: dict):
    """The program's ModelConfig for this configuration."""
    from repro.models.common import ModelConfig

    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qk_norm=True, rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], dtype=jnp.float32,
        model_parallel=1, remat=False, norm_eps=cfg["rms_norm_eps"])


# ------------------------------------------------------------ reference


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1 + scale)


def _rope(x, theta):
    """x [B, S, H, hd]; interleaved pairs (2i, 2i+1) rotated by pos*theta^-2i/hd."""
    b, s, h, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
    ang = np.arange(s)[:, None] * inv[None, :]                  # [S, hd/2]
    cos = jnp.asarray(np.cos(ang), x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), x.dtype)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def make_ref_loss(cfg: dict):
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    vocab, hd = cfg["vocab_size"], cfg["head_dim"]
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]

    def loss(p, batch):
        tokens = batch["tokens"]
        inp, lab = tokens[:, :-1], tokens[:, 1:]
        s = inp.shape[1]
        x = p["embed"][inp]                                         # [B,S,d]
        blk = p["blocks"]["b0"]
        causal = np.tril(np.ones((s, s), bool))
        for li in range(cfg["num_hidden_layers"]):
            a = jax.tree_util.tree_map(lambda t: t[li], blk)
            h = _rms(x, a["ln1"], eps)
            q = jnp.einsum("bsd,dhk->bshk", h, a["attn"]["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, a["attn"]["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, a["attn"]["wv"])
            q = _rope(_rms(q, a["attn"]["q_norm"], eps), theta)
            k = _rope(_rms(k, a["attn"]["k_norm"], eps), theta)
            k = jnp.repeat(k, group, axis=2)     # query head j reads kv head j // group
            v = jnp.repeat(v, group, axis=2)
            sc = jnp.einsum("bqhk,bshk->bhqs", q, k) / math.sqrt(hd)
            sc = jnp.where(causal, sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("bhqs,bshk->bqhk", pr, v)
            x = x + jnp.einsum("bqhk,hkd->bqd", o, a["attn"]["wo"])
            h2 = _rms(x, a["ln2"], eps)
            ff = (jax.nn.silu(h2 @ a["ffn"]["wi"]) * (h2 @ a["ffn"]["wg"]))
            x = x + ff @ a["ffn"]["wo"]
        x = _rms(x, p["final_norm"], eps)
        logits = jnp.einsum("bsd,vd->bsv", x, p["embed"][:vocab])
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - ll)

    return loss


def flops_per_lane_round(cfg: dict, mix: dict) -> float:
    """Model FLOPs of one lane-round.  Per token: 6 N for the per-worker
    gradient and 2 N for the round's reported loss, N the matmul weights
    (attention and feed-forward projections, and the tied output head over
    the padded vocabulary it computes); attention scores and values add
    12 L H hd S (gradient) and 4 L H hd S (loss), S the full context the
    program computes.  Recomputation is not counted."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layers = cfg["num_hidden_layers"]
    per_layer = 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f
    n = layers * per_layer + d * padded_vocab(cfg)
    data = mix["data"]
    s = data["seq_len"]
    tokens = cfg["num_workers"] * data["seqs_per_worker"] * s
    attn = layers * h * hd * s
    return tokens * (8.0 * n + 16.0 * attn)


def build(cfg: dict, mix: dict, seed: int) -> dict:
    import traffic
    from repro.models.transformer import lm_loss

    mc = program_config(cfg)
    seeds = np.random.SeedSequence([seed, 2]).generate_state(2)
    data = mix["data"]
    tokens = traffic.markov_tokens(
        mix["rounds"], cfg["num_workers"] * data["seqs_per_worker"],
        data["seq_len"] + 1, cfg["vocab_size"], int(seeds[0]),
        data.get("branch", 16))
    params0 = init_weights(cfg, int(seeds[1] >> 1))
    return {
        "dim": sum(math.prod(x.shape)
                   for x in jax.tree_util.tree_leaves(params0)),
        "params0": params0,
        "batches": {"tokens": tokens},
        "loss_fn": lambda p, b: lm_loss(p, b, mc),
        "eval_fn": None,
        "ref_loss": make_ref_loss(cfg),
        "ref_eval": None,
        "cast_batch": lambda b, dt: b,
        "flops_per_lane_round": flops_per_lane_round(cfg, mix),
    }
