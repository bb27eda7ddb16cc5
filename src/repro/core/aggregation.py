"""FLOA gradient aggregation — the paper's eq. (6)-(8) as a JAX transform.

TPU-native realization of over-the-air computation (see DESIGN.md §2): the
wireless MAC's superposition IS a weighted reduction over the worker axis, so
on a ("data","model") mesh the whole pipeline lowers to

    per-worker grads  g[U, ...]   (U sharded on "data" via vmap(grad))
    round stats       gbar, eps2  (two scalar all-reduces — the side channel)
    channel + power   s[U]        (replicated scalars)
    OTA superposition sum_i s_i g_i   ==  one all-reduce over "data"
    de-standardize    + bias_w * gbar * 1
    receiver noise    + eps * z,  z ~ N(0, z^2)  (sharded draw)

`aggregate` is pure and jit-safe; the FL trainer and every architecture's
train_step call it as a drop-in replacement for the plain gradient mean.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import attacks as A
from repro.core import standardize as S
from repro.core.channel import ChannelConfig, sample_channel_gains
from repro.core.power_control import Policy, PowerConfig, received_coefficients

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class FLOAConfig:
    """Everything needed to simulate one FLOA round."""

    channel: ChannelConfig
    power: PowerConfig
    attack: A.AttackConfig = dataclasses.field(
        default_factory=lambda: A.AttackConfig()
    )

    @property
    def num_workers(self) -> int:
        return self.channel.num_workers

    def validate(self) -> "FLOAConfig":
        assert self.channel.num_workers == self.power.num_workers
        if self.attack.byzantine_mask:
            assert len(self.attack.byzantine_mask) == self.channel.num_workers
        return self


def per_worker_grads(
    loss_fn: Callable,
    params,
    batch,
    num_workers: int,
    has_aux: bool = False,
):
    """Per-worker gradients via vmap(grad) over a worker-split batch.

    batch leaves are split [global_B, ...] -> [U, B/U, ...]; the leading U axis
    is what gets sharded over the "data" mesh axis, so each device computes its
    own worker's gradient only (FLOA's privacy property: raw per-worker
    gradients never leave their shard).
    Returns (grads_u, aux_u) with leading U axes.
    """
    def split(x):
        assert x.shape[0] % num_workers == 0, (
            f"global batch {x.shape[0]} not divisible by U={num_workers}"
        )
        return x.reshape(num_workers, x.shape[0] // num_workers, *x.shape[1:])

    worker_batch = jax.tree_util.tree_map(split, batch)
    gfn = jax.grad(loss_fn, has_aux=has_aux)
    if has_aux:
        grads_u, aux_u = jax.vmap(gfn, in_axes=(None, 0))(params, worker_batch)
        return grads_u, aux_u
    grads_u = jax.vmap(gfn, in_axes=(None, 0))(params, worker_batch)
    return grads_u, None


def _weighted_reduce(grads_u, weights: Array):
    """sum_i weights[i] * g_i over the leading worker axis (the OTA sum)."""
    return jax.tree_util.tree_map(
        lambda g: jnp.tensordot(weights.astype(g.dtype), g, axes=(0, 0)), grads_u
    )


def _sharded_noise(key: Array, template, std) -> "jax.tree_util.PyTreeDef":
    """Pytree of N(0, std^2) draws matching `template`'s shapes/dtypes.

    Uses a distinct folded key per leaf; with jax_threefry_partitionable the
    draw is generated shard-locally (never materialized replicated).
    """
    leaves, treedef = jax.tree_util.tree_flatten(template)
    keys = [jax.random.fold_in(key, i) for i in range(len(leaves))]
    noise = [
        (std * jax.random.normal(k, x.shape, jnp.float32)).astype(x.dtype)
        for k, x in zip(keys, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, noise)


def aggregate(
    grads_u,
    key: Array,
    cfg: FLOAConfig,
) -> Tuple[object, dict]:
    """One FLOA round: per-worker grads [U, ...] -> noisy aggregate (eq. 7).

    Returns (gagg, aux) where aux carries the round's channel draw, received
    coefficients and stats (for logging / theory cross-checks).
    """
    cfg.validate()
    k_ch, k_z, k_jam = jax.random.split(key, 3)

    # --- standardization side-channel (workers report truthful scalar stats).
    gbar_i, eps2_i = S.per_worker_scalar_stats(grads_u)
    gbar, eps2 = S.global_stats(gbar_i, eps2_i)

    if cfg.power.policy == Policy.EF:
        # Error-free benchmark: perfect aggregation (h=1, z=0). Attackers (if
        # any) contribute a sign-flipped mean share — the digital analogue.
        u = cfg.num_workers
        sign = jnp.where(cfg.attack.mask(), -1.0, 1.0) if cfg.attack.byzantine_mask else jnp.ones((u,))
        if cfg.attack.attack == A.AttackType.NONE:
            sign = jnp.ones((u,))
        s = sign / u
        gagg = _weighted_reduce(grads_u, s)
        aux = dict(h_abs=jnp.ones((u,)), coeffs=s, gbar=gbar, eps2=eps2,
                   bias_w=jnp.zeros(()))
        return gagg, aux

    # --- channel draw + per-worker signed coefficients (honest & Byzantine).
    h_abs = sample_channel_gains(k_ch, cfg.channel)
    s, bias_w = A.signed_coefficients(
        h_abs, cfg.power, cfg.channel, cfg.attack, gbar, eps2
    )

    # --- OTA superposition == all-reduce over the "data" axis.
    gagg = _weighted_reduce(grads_u, s)

    # --- de-standardization bias from attackers (eq. 7 third term).
    gagg = jax.tree_util.tree_map(
        lambda g: g + (bias_w * gbar).astype(g.dtype), gagg
    )

    # --- receiver AWGN, scaled by eps_t (eq. 7 fourth term).
    eps = jnp.sqrt(eps2)
    if cfg.channel.noise_std > 0.0:
        z = _sharded_noise(k_z, gagg, cfg.channel.noise_std)
        gagg = jax.tree_util.tree_map(lambda g, n: g + eps.astype(g.dtype) * n, gagg, z)

    # --- unstructured jamming (GAUSSIAN ablation only; 0 otherwise).
    jam_std = A.gaussian_jam_std(h_abs, cfg.power, cfg.attack, eps2)
    if cfg.attack.attack == A.AttackType.GAUSSIAN and cfg.attack.num_attackers:
        jam = _sharded_noise(k_jam, gagg, 1.0)
        gagg = jax.tree_util.tree_map(
            lambda g, n: g + jam_std.astype(g.dtype) * n, gagg, jam
        )

    aux = dict(h_abs=h_abs, coeffs=s, gbar=gbar, eps2=eps2, bias_w=bias_w)
    return gagg, aux


# Below this flat size the einsum oracle beats the kernel's grid overhead;
# above it (and on TPU, where the kernel compiles to Mosaic rather than the
# interpreter) the fused single-pass kernel wins — it is bandwidth-bound.
BATCHED_KERNEL_MIN_D = 1 << 16


def flatten_worker_grads(grads_u, batch_dims: int = 1):
    """Pytree with [*lead, ...] leaves -> ([*lead, D] matrix, unflatten fn).

    batch_dims counts the leading axes shared by every leaf ([U] for a single
    scenario, [S, U] for a stacked sweep).  unflatten maps a [*lead[:-1], D]
    aggregate (the worker axis reduced away) back to the parameter pytree.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads_u)
    lead = leaves[0].shape[:batch_dims]
    lead_n = 1
    for n in lead:
        lead_n *= int(n)
    sizes = [int(x.size) // lead_n for x in leaves]
    shapes = [x.shape[batch_dims:] for x in leaves]
    flat = jnp.concatenate(
        [x.reshape(*lead, -1).astype(jnp.float32) for x in leaves], axis=-1
    )

    def unflatten(vec):
        out, off = [], 0
        out_lead = vec.shape[:-1]
        for n, shp, x in zip(sizes, shapes, leaves):
            out.append(vec[..., off:off + n].reshape(*out_lead, *shp)
                       .astype(x.dtype))
            off += n
        return jax.tree_util.tree_unflatten(treedef, out)

    return flat, unflatten


def batched_floa_combine(
    coeffs: Array,
    flat: Array,
    noise: Array,
    bias: Array,
    eps: Array,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
) -> Array:
    """[S, U, D] OTA combine: out[s] = coeffs[s] @ flat[s] + bias[s] + eps[s] z[s].

    The sweep engine's hot spot.  use_kernel=None routes through the fused
    Pallas kernel when the flattened gradient is large and the backend is
    the TPU, and through the einsum reference otherwise.  A requested kernel
    compiles for the TPU unless interpret=True asks for the Pallas
    interpreter (correctness tests on CPU hosts).
    """
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and flat.shape[-1] >= BATCHED_KERNEL_MIN_D)
    if use_kernel:
        from repro.kernels import ops
        return ops.floa_aggregate_batched(coeffs, flat, noise, bias, eps,
                                          interpret=interpret)
    from repro.kernels import ref
    return ref.floa_aggregate_batched_ref(coeffs, flat, noise, bias, eps)


def batched_floa_step(
    w: Array,
    alpha: Array,
    coeffs: Array,
    flat: Array,
    noise: Array,
    bias: Array,
    eps: Array,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """Fused [S, U, D] OTA combine + PS update (eq. 7 + eq. 8), flat state.

        gagg[s]  = coeffs[s] @ flat[s] + bias[s] + eps[s] * noise[s]
        w_new[s] = w[s] - alpha[s] * gagg[s]

    Returns (w_new, gagg); gagg is materialized so the sweep engine can log
    grad norms without re-deriving it from the update.  Same TPU-kernel /
    einsum-oracle routing and oracle-equivalence contract as
    `batched_floa_combine` — on TPU with a large flat gradient the whole
    round update is one pass over the [S, U, D] slab.
    """
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and flat.shape[-1] >= BATCHED_KERNEL_MIN_D)
    if use_kernel:
        from repro.kernels import ops
        return ops.floa_step_batched(w, coeffs, flat, noise, bias, eps,
                                     alpha, interpret=interpret)
    from repro.kernels import ref
    return ref.floa_step_batched_ref(w, coeffs, flat, noise, bias, eps, alpha)


def mean_aggregate(grads_u) -> object:
    """Plain FedSGD mean (the EF path without the FLOA bookkeeping)."""
    return jax.tree_util.tree_map(lambda g: jnp.mean(g, axis=0), grads_u)


def floa_grad(
    loss_fn: Callable,
    params,
    batch,
    key: Array,
    cfg: FLOAConfig,
    has_aux: bool = False,
):
    """Convenience: per-worker grads + FLOA aggregation in one call.

    Returns (gagg, aux) — aux includes per-worker loss-fn aux if has_aux.
    """
    grads_u, fn_aux = per_worker_grads(
        loss_fn, params, batch, cfg.num_workers, has_aux=has_aux
    )
    gagg, aux = aggregate(grads_u, key, cfg)
    if fn_aux is not None:
        aux["loss_aux"] = fn_aux
    return gagg, aux
