"""The one traffic generator: a mix file (mixes/<name>.json) plus the run's
seed in, the grid of scenario lanes, the round batches and the per-call lane
keys out.

A mix is data only.  Its `lanes` list holds lane groups; every list-valued
field of a group (defense, policy, attack, attackers, variants) is expanded
as a product, so a group is one family of lanes.  A lane here is a plain
dict (`expand_lanes`), which the plain reference reads; `to_cases` turns the
same dicts into the program's `ScenarioCase`s.

The data generators are copies, made for the benchmark, of the program's
`data/synthetic_digits.py`, `data/pipeline.py` (`worker_split`,
`FederatedSampler.stack_rounds`) and `data/text.py`, and the learning-rate
rule is a copy of `core/theory.py`'s `alpha_from_alpha_hat`: the yardstick
does not move when the program's copies do.
"""
from __future__ import annotations

import itertools
import math

import numpy as np

# ------------------------------------------------------------------ digits

_SIZE = 28
_TEMPLATES = {
    0: [[(0.5, 0.1), (0.8, 0.3), (0.8, 0.7), (0.5, 0.9), (0.2, 0.7), (0.2, 0.3), (0.5, 0.1)]],
    1: [[(0.35, 0.25), (0.55, 0.1), (0.55, 0.9)], [(0.35, 0.9), (0.75, 0.9)]],
    2: [[(0.2, 0.25), (0.5, 0.1), (0.8, 0.3), (0.2, 0.9), (0.8, 0.9)]],
    3: [[(0.2, 0.15), (0.7, 0.15), (0.45, 0.45), (0.8, 0.7), (0.5, 0.92), (0.2, 0.8)]],
    4: [[(0.65, 0.9), (0.65, 0.1), (0.2, 0.6), (0.85, 0.6)]],
    5: [[(0.8, 0.1), (0.25, 0.1), (0.25, 0.5), (0.65, 0.45), (0.8, 0.7), (0.55, 0.92), (0.2, 0.82)]],
    6: [[(0.7, 0.1), (0.35, 0.4), (0.25, 0.75), (0.5, 0.92), (0.75, 0.72), (0.55, 0.5), (0.3, 0.62)]],
    7: [[(0.2, 0.1), (0.8, 0.1), (0.45, 0.9)], [(0.35, 0.5), (0.7, 0.5)]],
    8: [[(0.5, 0.1), (0.75, 0.28), (0.5, 0.48), (0.25, 0.28), (0.5, 0.1)],
        [(0.5, 0.48), (0.8, 0.7), (0.5, 0.92), (0.2, 0.7), (0.5, 0.48)]],
    9: [[(0.75, 0.35), (0.5, 0.5), (0.3, 0.3), (0.5, 0.1), (0.75, 0.25), (0.72, 0.6), (0.5, 0.9)]],
}


def _render(digit: int, rng: np.random.Generator) -> np.ndarray:
    img = np.zeros((_SIZE, _SIZE), np.float32)
    ang = rng.uniform(-0.25, 0.25)
    scale = rng.uniform(0.8, 1.1)
    dx, dy = rng.uniform(-0.08, 0.08, size=2)
    ca, sa = np.cos(ang), np.sin(ang)
    thick = rng.uniform(0.7, 1.4)

    def tx(p):
        x, y = p[0] - 0.5, p[1] - 0.5
        x, y = ca * x - sa * y, sa * x + ca * y
        return ((x * scale + 0.5 + dx) * (_SIZE - 1),
                (y * scale + 0.5 + dy) * (_SIZE - 1))

    yy, xx = np.mgrid[0:_SIZE, 0:_SIZE].astype(np.float32)
    for line in _TEMPLATES[digit]:
        pts = [tx(p) for p in line]
        for (x0, y0), (x1, y1) in zip(pts[:-1], pts[1:]):
            vx, vy = x1 - x0, y1 - y0
            ll = max(vx * vx + vy * vy, 1e-6)
            t = np.clip(((xx - x0) * vx + (yy - y0) * vy) / ll, 0.0, 1.0)
            d2 = (xx - (x0 + t * vx)) ** 2 + (yy - (y0 + t * vy)) ** 2
            img = np.maximum(img, np.exp(-d2 / (2.0 * thick**2)))
    img += rng.normal(0.0, 0.05, img.shape).astype(np.float32)
    return np.clip(img, 0.0, 1.0)


def make_digits(n: int, seed: int):
    """(x [n, 784] f32 in [0, 1], y [n] int32) procedural digits."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 10, size=n).astype(np.int32)
    x = np.stack([_render(int(d), rng).reshape(-1) for d in y])
    return x, y


def digit_rounds(x, y, num_workers: int, batch_per_worker: int, rounds: int,
                 seed: int):
    """[R, U * B, ...] batches: an i.i.d. split of (x, y) over the workers,
    then each round every worker draws B samples of its own shard; rows are
    worker-major, so rows [u*B, (u+1)*B) are worker u's."""
    rng = np.random.default_rng(seed)
    shards = np.array_split(rng.permutation(len(x)), num_workers)
    draw = np.random.default_rng(seed + 1)
    xs, ys = [], []
    for _ in range(rounds):
        idx = np.concatenate([s[draw.integers(0, len(s), size=batch_per_worker)]
                              for s in shards])
        xs.append(x[idx])
        ys.append(y[idx])
    return {"x": np.stack(xs), "y": np.stack(ys)}


# ------------------------------------------------------------ token stream


def markov_tokens(rounds: int, n_seqs: int, seq_len: int, vocab: int,
                  seed: int, branch: int = 16) -> np.ndarray:
    """[R, n_seqs, seq_len] int32 from a Zipf-prior Markov chain with `branch`
    likely successors per token and 10% random restarts."""
    rng = np.random.default_rng(seed)
    zipf = 1.0 / np.arange(1, vocab + 1) ** 1.1
    succ = rng.choice(vocab, size=(vocab, branch), p=zipf / zipf.sum())
    out = np.empty((rounds, n_seqs, seq_len), np.int32)
    for r in range(rounds):
        cur = rng.integers(0, vocab, size=n_seqs)
        for t in range(seq_len):
            out[r, :, t] = cur
            nxt = succ[cur, rng.integers(0, branch, size=n_seqs)]
            restart = rng.random(n_seqs) < 0.1
            cur = np.where(restart, rng.integers(0, vocab, size=n_seqs), nxt)
    return out


# ------------------------------------------------------------ lane grid


def theory_alpha(policy: str, u: int, n: int, dim: int, alpha_hat: float,
                 sigma: float = 1.0, p_max: float = 1.0) -> float:
    """Paper section IV: raw rate alpha = alpha_hat * omega / Omega (Thms 2-3,
    iso workers, the first n Byzantine); |omega| when omega <= 0 so the lane
    still runs (and diverges, as in the paper's Fig. 3)."""
    if policy == "ci":
        b0 = math.sqrt(p_max / dim / (u / (2.0 * sigma**2)))
        w = (u - n) * b0 - n * math.sqrt(math.pi * sigma**2 * p_max / (2.0 * dim))
        big = (u + n) * (u * b0**2 + n * 2.0 * sigma**2 * p_max / dim)
    elif policy == "bev":
        term = math.sqrt(p_max * math.pi / (2.0 * dim)) * sigma
        w = (u - n) * term - n * term
        big = (u + n) * u * 2.0 * sigma**2 * p_max / dim
    else:
        w, big = 1.0, 1.0
    w = abs(w) if w != 0 else 1e-12
    return alpha_hat * w / big


def _as_list(v):
    return v if isinstance(v, list) else [v]


def expand_lanes(mix: dict, cfg: dict, dim: int, alpha_hat=None) -> list:
    """Plain lane dicts, in mix order, for one replica of the grid.

    dim is the power-accounting dimension D (the flat parameter count);
    alpha_hat, when given, replaces the mix's alpha_hat for every lane whose
    rate follows the theory rule."""
    u = cfg["num_workers"]
    lanes = []
    for g in mix["lanes"]:
        for dfn, pol, atk, n, var in itertools.product(
                _as_list(g["defense"]), _as_list(g["policy"]),
                _as_list(g["attack"]), _as_list(g["attackers"]),
                g.get("variants", [{}])):
            lane = {
                "defense": dfn["name"], "trim": dfn.get("trim", 1),
                "num_byzantine": dfn.get("num_byzantine", 0),
                "multi": dfn.get("multi", 1), "gm_iters": dfn.get("gm_iters", 8),
                "policy": pol, "attack": atk if n else "none", "attackers": n,
                "markov_rho": var.get("markov_rho", 0.0),
                "participants": var.get("participants"),
                "sigma": cfg["sigma"], "p_max": cfg["p_max"], "dim": dim,
                "num_workers": u,
            }
            noise = g["noise"]
            if noise == "snr":
                noise = math.sqrt(cfg["p_max"]
                                  / (dim * 10.0 ** (cfg["snr_db"] / 10.0)))
            lane["noise_std"] = 0.0 if pol == "ef" else float(noise)
            a = g["alpha"]
            if "alpha_hat" in a:
                lane["alpha"] = theory_alpha(
                    pol, u, n, dim,
                    a["alpha_hat"] if alpha_hat is None else alpha_hat,
                    cfg["sigma"], cfg["p_max"])
            else:
                lane["alpha"] = float(a["lr"])
            tag = "/".join(t for t in (dfn["name"], pol, lane["attack"],
                                       var.get("tag", "")) if t)
            lane["name"] = f"{tag}@N{n}"
            lanes.append(lane)
    return lanes


def to_cases(lanes: list, seeds) -> list:
    """Plain lane dicts -> the program's ScenarioCase list (one per lane)."""
    from repro.core import (AttackConfig, AttackType, ChannelConfig,
                            DefenseSpec, FLOAConfig, Policy, PowerConfig)
    from repro.fl import ScenarioCase

    cases = []
    for lane, seed in zip(lanes, seeds):
        u, n = lane["num_workers"], lane["attackers"]
        floa = FLOAConfig(
            channel=ChannelConfig(num_workers=u, sigma=lane["sigma"],
                                  noise_std=lane["noise_std"],
                                  markov_rho=lane["markov_rho"]),
            power=PowerConfig(num_workers=u, dim=lane["dim"],
                              p_max=lane["p_max"],
                              policy=Policy(lane["policy"])),
            attack=AttackConfig(attack=AttackType(lane["attack"]),
                                byzantine_mask=tuple(i < n for i in range(u))))
        defense = DefenseSpec(name=lane["defense"], trim=lane["trim"],
                              num_byzantine=lane["num_byzantine"],
                              multi=lane["multi"], gm_iters=lane["gm_iters"])
        cases.append(ScenarioCase(lane["name"], floa, lane["alpha"],
                                  seed=int(seed), defense=defense,
                                  participants=lane["participants"]))
    return cases


class Traffic:
    """Everything a run of one cell sends: the grid, the batches, and for
    each call its lane seeds (and, for a `run_sweep` mix, its alpha_hat)."""

    def __init__(self, mix: dict, cfg: dict, dim: int, seed: int):
        self.mix, self.cfg, self.dim = mix, cfg, dim
        self.rng = np.random.default_rng([seed, 1])
        self.base_lanes = expand_lanes(mix, cfg, dim)
        self.replicas = mix.get("replicas", 1)
        self.rounds = mix["rounds"]

    @property
    def num_lanes(self) -> int:
        return len(self.base_lanes) * self.replicas

    def lanes(self, alpha_hat=None) -> list:
        base = (self.base_lanes if alpha_hat is None
                else expand_lanes(self.mix, self.cfg, self.dim, alpha_hat))
        out = []
        for r in range(self.replicas):
            for lane in base:
                lane = dict(lane)
                if self.replicas > 1:
                    lane["name"] = f"{lane['name']}#r{r}"
                out.append(lane)
        return out

    def next_call(self) -> dict:
        """The values that change from call to call: a 31-bit seed per lane
        (the lane's key is PRNGKey(seed)), and for a run_sweep mix a fresh
        alpha_hat.  Shapes and lane families stay the same."""
        seeds = self.rng.integers(0, 2**31, size=self.num_lanes,
                                  dtype=np.uint64)
        call = {"seeds": seeds}
        rng_a = self.mix.get("alpha_hat_per_call")
        call["alpha_hat"] = (None if rng_a is None
                             else float(self.rng.uniform(*rng_a)))
        call["lanes"] = self.lanes(call["alpha_hat"])
        call["keys"] = keys_of(seeds)
        return call


def keys_of(seeds) -> np.ndarray:
    """[S, 2] uint32 raw keys equal to jax.random.PRNGKey(seed) for 32-bit
    seeds (the default threefry key is [high word, low word])."""
    seeds = np.asarray(seeds, np.uint64)
    return np.stack([np.zeros_like(seeds), seeds], axis=1).astype(np.uint32)
