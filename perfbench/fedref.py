"""The plain reference: one federated round of one scenario lane, written
out from the paper (arXiv:2110.09660 eqs. 3-8, Thms 1-3) and the scenario
semantics the mixes name, in straightforward jnp.  It imports nothing of the
program and takes nothing the program made: the weights, batches and lane
keys are the benchmark's, and every random draw is made here from the
lane's key on the schedule the lane's scenario defines:

  per round      key, sub = split(key)
  channel        |h| = sigma sqrt(2 E), E ~ Exp(1), from split(sub, 3)[0]
  receiver noise z ~ N(0, I_D) from split(sub, 3)[1]
  jamming        n ~ N(0, I_D) from split(sub, 3)[2]
  colluding      d ~ N(0, I_D) from fold_in(sub, 3), scaled to unit RMS
  Gauss-Markov   h_t = rho h_(t-1) + sqrt(1 - rho^2) w_t on the complex
                 gain, w_t from fold_in(sub, 4), h_0 from fold_in(key0, 7)
  K of U         the K workers with the smallest U(0,1) scores drawn from
                 fold_in(sub, 5)

A round: every worker's gradient on its own rows of the batch; analog lanes
standardize (eq. 3), weigh each worker by its received coefficient (power
policy CI / BEV / EF, Byzantine payloads of Thm 1 and the adaptive cohorts),
add the de-standardization bias of attackers that never standardized,
receiver noise scaled by eps (eq. 7) and any jamming or cohort direction;
digital lanes sign-flip the attackers' reports and screen them (mean,
coordinate median, trimmed mean, (multi-)Krum, Weiszfeld geometric median)
over the round's participants.  Then w <- w - alpha * aggregate (eq. 8) and
the reported loss is the loss of the new weights on the whole round batch.

`dtype` runs the whole reference in another precision: bfloat16 is the
control that must come out as not correct.  `fault` plants one of the
faults the benchmark's checks are held against.

On a cell of several chips the reference follows each lane over all of
them (axis "d" of a one-axis mesh): the weights, the aggregate and the
D-wide draws are split along D; each loss gathers the leaves it reads; each
chip takes the gradients of its own share of the workers and trades them
for a block of D of every worker's, so that no chip holds the [U, D]
gradients.  Between rounds the weights travel zero-padded to a multiple of
the chip count, so that every chip holds an equal block.  With
jax_threefry_partitionable (JAX's default) no draw's values depend on the
split.  On one chip nothing is split and the round program is the plain
one.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

POLICIES = {"ci": 0, "bev": 1, "ef": 2, "truncated_ci": 3}
ATTACKS = {"none": 0, "strongest": 1, "sign_flip_protocol_power": 2,
           "gaussian": 3, "colluding": 4, "omniscient": 5}


def _unflat(w, template):
    leaves, treedef = jax.tree_util.tree_flatten(template)
    out, off = [], 0
    for leaf in leaves:
        n = math.prod(leaf.shape)
        out.append(w[off:off + n].reshape(leaf.shape))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def _split(x, mesh, axis=-1):
    """x split over the mesh along `axis`: the last, the parameter row, by
    default; the first, the workers, for the gradients."""
    if mesh is None:
        return x
    spec = [None] * x.ndim
    spec[axis] = "d"
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*spec)))


def _screen(name, f, part, k, num, gm_iters):
    """Digital screening over the k participating rows of f [U, D]."""
    u = f.shape[0]
    pf = part[:, None]
    idx = jnp.arange(u)
    if name == "mean":
        return jnp.sum(jnp.where(pf, f, 0), 0) / k
    if name in ("median", "trimmed_mean"):
        srt = jnp.sort(jnp.where(pf, f, jnp.inf), axis=0)   # participants first
        if name == "median":
            return (jnp.take(srt, (k - 1) // 2, axis=0)
                    + jnp.take(srt, k // 2, axis=0)) / 2
        trim = num["trim"]
        keep = (idx >= trim) & (idx < k - trim)
        return jnp.sum(jnp.where(keep[:, None], srt, 0), 0) / (k - 2 * trim)
    if name in ("krum", "multi_krum"):
        d2 = jnp.sum((f[:, None, :] - f[None, :, :]) ** 2, -1)
        ok = part[:, None] & part[None, :] & ~jnp.eye(u, dtype=bool)
        d2 = jnp.sort(jnp.where(ok, d2, jnp.inf), axis=1)
        closest = jnp.maximum(k - num["num_byzantine"] - 2, 1)
        score = jnp.sum(jnp.where(idx[None, :] < closest, d2, 0), 1)
        ranked = f[jnp.argsort(jnp.where(part, score, jnp.inf))]
        multi = num["multi"]
        return jnp.sum(jnp.where((idx < multi)[:, None], ranked, 0), 0) / multi
    if name == "geometric_median":
        z = jnp.sum(jnp.where(pf, f, 0), 0) / k
        for _ in range(gm_iters):
            wgt = jnp.where(part, 1 / jnp.maximum(
                jnp.sqrt(jnp.sum((f - z) ** 2, 1)), 1e-8), 0)
            z = jnp.sum(wgt[:, None] * f, 0) / jnp.sum(wgt)
        return z
    raise ValueError(f"unknown defense {name!r}")


def _analog(g, w, h, sub, part, k, byz, num, dt, mesh):
    """Eq. (7) for one lane: standardization stats, the channel, the power
    policy's and the attack's coefficients, bias, noise, jamming and the
    adaptive cohorts' direction.  Every policy and attack is computed and
    the lane's is selected by its code."""
    u, d = g.shape
    ks = jax.random.split(sub, 3)
    sigma = jnp.full((u,), num["sigma"], dt)
    p_max = jnp.full((u,), num["p_max"], dt)
    dim, rho = num["dim"], num["markov_rho"]
    w_in = sigma[:, None] * jax.random.normal(
        jax.random.fold_in(sub, 4), (u, 2)).astype(dt)
    h = rho * h + jnp.sqrt(1 - rho * rho) * w_in
    e = jax.random.exponential(ks[0], (u,)).astype(dt)
    h_abs = jnp.where(rho > 0, jnp.sqrt(jnp.sum(h * h, -1)),
                      sigma * jnp.sqrt(2 * e))
    pf = part.astype(dt)
    gbar_i = jnp.mean(g, 1)
    eps2_i = jnp.maximum(jnp.mean(g * g, 1) - gbar_i ** 2, 1e-20)
    gbar = jnp.sum(pf * gbar_i) / k
    eps2 = jnp.sum(pf * eps2_i) / k
    eps = jnp.sqrt(eps2)
    policy, attack = num["policy"], num["attack"]
    ef = policy == POLICIES["ef"]
    active = attack != ATTACKS["none"]
    amp_bev = jnp.sqrt(p_max / dim)
    amp_ci = jnp.sqrt(jnp.min(p_max) / dim / jnp.sum(1 / (2 * sigma ** 2))) / h_abs
    amp = jnp.where(policy == POLICIES["ci"], amp_ci,
                    jnp.where(policy == POLICIES["truncated_ci"],
                              jnp.minimum(amp_ci, amp_bev), amp_bev))
    honest = jnp.where(ef, 1.0 / k, amp * h_abs).astype(dt)
    phat = jnp.sqrt(p_max / (dim * (gbar ** 2 + eps2)))
    attacker = jnp.where(
        ef | (attack == ATTACKS["sign_flip_protocol_power"]), -honest,
        jnp.where(attack == ATTACKS["strongest"], -eps * phat * h_abs, 0))
    s = jnp.where(byz & active, attacker, honest) * pf
    cohort = byz & part
    biased = active & ~ef & (attack != ATTACKS["sign_flip_protocol_power"])
    bias = jnp.where(biased, jnp.sum(jnp.where(cohort, honest, 0)), 0)
    z = _split(jax.random.normal(ks[1], (d,)), mesh).astype(dt)
    agg = s @ g + bias * gbar + eps * jnp.where(ef, 0, num["noise_std"]) * z
    on = active & ~ef
    jam = jnp.sqrt(eps2 * jnp.sum(jnp.where(cohort, amp_bev * h_abs, 0) ** 2))
    agg = agg + jnp.where(on & (attack == ATTACKS["gaussian"]), jam, 0) * \
        _split(jax.random.normal(ks[2], (d,)), mesh).astype(dt)
    dvec = _split(jax.random.normal(jax.random.fold_in(sub, 3), (d,)),
                  mesh).astype(dt)
    dvec = dvec / jnp.maximum(jnp.sqrt(jnp.mean(dvec * dvec)), 1e-20)
    collude = eps * jnp.sum(jnp.where(cohort, amp_bev * h_abs, 0))
    agg = agg + jnp.where(on & (attack == ATTACKS["colluding"]), collude, 0) * dvec
    hon = (~byz & part).astype(dt)
    mean_h = (hon @ g) / jnp.maximum(jnp.sum(hon), 1)
    omni = -eps * jnp.sum(jnp.where(cohort, phat * h_abs, 0))
    agg = agg + jnp.where(on & (attack == ATTACKS["omniscient"]), omni, 0) * mean_h
    return agg, h


def _round(w, h, key, batch, num, defense, u, gm_iters, loss_fn, template,
           sizes, dt, fault, mesh):
    """One round of one lane; `num` holds the lane's numbers and codes.
    Returns the next (w, h, key), the round's loss and aggregate norm, and
    the aggregate's norm per parameter leaf."""
    if mesh is not None:                           # drop the padding
        w = _split(w[:sum(sizes)], mesh)
    key, sub = jax.random.split(key)
    byz = jnp.arange(u) < num["attackers"]

    def worker_loss(wr, rows):
        return loss_fn(_unflat(wr, template), rows)

    b = jax.tree_util.tree_leaves(batch)[0].shape[0] // u
    take = b // 2 if fault == "half_batch" else b
    rows = jax.tree_util.tree_map(                 # worker i: rows [i b, i b + take)
        lambda x: _split(x.reshape((u, b) + x.shape[1:])[:, :take], mesh, 0),
        batch)
    if mesh is None:
        g = jax.vmap(jax.grad(worker_loss), in_axes=(None, 0))(w, rows)  # [U, D]
    else:
        # Each chip takes the gradients of its own workers from the gathered
        # leaves, then all chips trade them for a block of D of every
        # worker's.
        p = _unflat(w, template)
        g = jax.vmap(lambda r: jnp.concatenate([
            x.reshape(-1) for x in jax.tree_util.tree_leaves(
                jax.grad(loss_fn)(p, r))]))(rows)
        g = _split(_split(g, mesh, 0), mesh)

    k = num["participants"]
    sc = jax.random.uniform(jax.random.fold_in(sub, 5), (u,))
    part = jnp.argsort(jnp.argsort(sc)) < k        # all True when k == U
    if defense == "floa":
        agg, h = _analog(g, w, h, sub, part, k, byz, num, dt, mesh)
    else:
        flip = jnp.where(byz & (num["attack"] != ATTACKS["none"]), -1, 1)
        agg = _screen(defense, g * flip[:, None].astype(dt), part, k, num,
                      gm_iters)
    w_new = w if fault == "unchanged" else w - num["alpha"] * agg
    loss = loss_fn(_unflat(w_new, template), batch)
    a32 = agg.astype(jnp.float32)
    offs = np.cumsum((0,) + sizes)
    if mesh is None:
        leaf = jnp.stack([jnp.sqrt(jnp.sum(a32[o:o + n] ** 2))
                          for o, n in zip(offs, sizes)])
    else:   # masked, since a leaf's slice would gather it onto every chip
        pos = _split(jnp.arange(a32.shape[0]), mesh)
        leaf = jnp.stack([jnp.sqrt(jnp.sum(jnp.where(
            (pos >= o) & (pos < o + n), a32 * a32, 0)))
            for o, n in zip(offs, sizes)])
        d = w.shape[0]
        w_new = _split(jnp.pad(w_new, (0, _padded(d, mesh) - d)), mesh)
    return w_new, h, key, loss, jnp.sqrt(jnp.sum(a32 ** 2)), leaf


def lane_numbers(lane: dict, dt) -> dict:
    """A lane's numbers and codes, the arguments of its round program."""
    u = lane["num_workers"]
    num = {k: np.asarray(lane[k], dt) for k in
           ("alpha", "noise_std", "sigma", "p_max", "dim", "markov_rho")}
    num.update({
        "policy": np.int32(POLICIES[lane["policy"]]),
        "attack": np.int32(ATTACKS[lane["attack"]]),
        "attackers": np.int32(lane["attackers"]),
        "participants": np.int32(lane["participants"] or u),
        "trim": np.int32(lane["trim"]),
        "num_byzantine": np.int32(lane["num_byzantine"]),
        "multi": np.int32(lane["multi"]),
    })
    return num


def _padded(d: int, mesh) -> int:
    """The row's length between rounds: d rounded up to the chip count."""
    return -(-d // mesh.size) * mesh.size


class Staged:
    """What every lane of one reference run shares, made once on the
    device: the initial weights (flat, in the run's dtype) and the round
    batches.  Over several devices the weights are split along D and the
    batches replicated; the weights are flattened on the host, so that no
    chip holds a second whole copy beside the program's."""

    def __init__(self, params0, batches, rounds: int, dtype=jnp.float32,
                 cast_batch=None, devices=()):
        self.dt = jnp.dtype(dtype)
        leaves, self.treedef = jax.tree_util.tree_flatten(params0)
        self.shapes = tuple(tuple(x.shape) for x in leaves)
        self.sizes = tuple(math.prod(x) for x in self.shapes)
        self.mesh = Mesh(np.array(devices), ("d",)) if len(devices) > 1 else None
        w0 = np.concatenate([np.asarray(x).reshape(-1) for x in leaves])
        if self.mesh is None:
            row, put = None, jnp.asarray
        else:
            w0 = np.pad(w0, (0, _padded(w0.size, self.mesh) - w0.size))
            row = NamedSharding(self.mesh, PartitionSpec("d"))
            put = functools.partial(jax.device_put, device=NamedSharding(
                self.mesh, PartitionSpec()))
        self.w0 = jax.device_put(w0, row).astype(self.dt)
        cast = cast_batch or (lambda b, dt: b)
        self.batches = [cast(jax.tree_util.tree_map(
            lambda x: put(x[t]), batches), self.dt) for t in range(rounds)]

    def unflat_host(self, w: np.ndarray):
        out, off = [], 0
        for shape, n in zip(self.shapes, self.sizes):
            out.append(w[off:off + n].reshape(shape))
            off += n
        return jax.tree_util.tree_unflatten(self.treedef, out)


@functools.lru_cache(maxsize=None)
def _compiled(defense, u, gm_iters, loss_fn, treedef, shapes, sizes, dt, fault,
              mesh):
    template = jax.tree_util.tree_unflatten(
        treedef, [jax.ShapeDtypeStruct(s, dt) for s in shapes])
    return jax.jit(functools.partial(
        _round, defense=defense, u=u, gm_iters=gm_iters, loss_fn=loss_fn,
        template=template, sizes=sizes, dt=dt, fault=fault, mesh=mesh))


@functools.lru_cache(maxsize=None)
def _compiled_eval(eval_fn, treedef, shapes, dt):
    template = jax.tree_util.tree_unflatten(
        treedef, [jax.ShapeDtypeStruct(s, dt) for s in shapes])
    return jax.jit(lambda w: eval_fn(_unflat(w, template)))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _h0(key0, sigma, u, dt):
    return (sigma * jax.random.normal(jax.random.fold_in(key0, 7), (u, 2))
            ).astype(dt)


def run_lane(lane: dict, key0, staged: Staged, loss_fn: Callable,
             rounds: int, eval_fn: Optional[Callable] = None,
             fault: Optional[str] = None):
    """Follow one lane for `rounds` rounds from the staged weights with lane
    key key0.

    Returns dict(loss [rounds], grad_norm [rounds], accuracy1 (eval after
    round 1, or None), params (host tree after `rounds` rounds),
    agg_leaf_norms (per-leaf norm of round 1's aggregate)).  Matmuls run at
    `highest` precision in float32; the bfloat16 control at its own."""
    dt = staged.dt
    precision = "highest" if dt == jnp.float32 else "default"
    step = _compiled(lane["defense"], lane["num_workers"], lane["gm_iters"],
                     loss_fn, staged.treedef, staged.shapes, staged.sizes, dt,
                     fault, staged.mesh)
    num = lane_numbers(lane, dt)
    key = jnp.asarray(key0, jnp.uint32)
    losses, norms, acc1, leaf0 = [], [], None, None
    with jax.default_matmul_precision(precision):
        w = staged.w0
        h = _h0(key, float(lane["sigma"]), lane["num_workers"], dt)
        for t in range(rounds):
            w, h, key, loss, gn, leaf = step(w, h, key, staged.batches[t], num)
            losses.append(loss)
            norms.append(gn)
            if t == 0:
                leaf0 = leaf
                if eval_fn is not None:
                    acc1 = _compiled_eval(eval_fn, staged.treedef,
                                          staged.shapes, dt)(w)
    losses, norms, leaf0, acc1, w = jax.device_get(
        (jnp.stack(losses), jnp.stack(norms), leaf0, acc1, w))
    return {"loss": np.asarray(losses, np.float64),
            "grad_norm": np.asarray(norms, np.float64),
            "accuracy1": None if acc1 is None else float(acc1),
            "params": staged.unflat_host(np.asarray(w, np.float32)),
            "agg_leaf_norms": [float(x) for x in leaf0]}
