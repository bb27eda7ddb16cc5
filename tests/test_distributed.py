"""Distributed-runtime tests, run in subprocesses so the host device count
can be forced per-test (smoke tests must keep seeing 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

# Each test spawns a fresh interpreter that recompiles its mesh program —
# tens of seconds apiece on CPU, so the whole module sits behind `slow`.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_mesh_construction():
    out = run_py("""
        import jax
        jax.config.update("jax_threefry_partitionable", True)
        from repro.launch.mesh import make_debug_mesh, batch_axes, num_workers
        m = make_debug_mesh((4, 2), ("data", "model"))
        assert batch_axes(m) == ("data",)
        assert num_workers(m) == 4
        m3 = make_debug_mesh((2, 2, 2), ("pod", "data", "model"))
        assert batch_axes(m3) == ("pod", "data")
        assert num_workers(m3) == 4
        print("MESH_OK")
    """)
    assert "MESH_OK" in out


def test_train_step_compiles_and_runs_on_mesh():
    """Real (allocated) FLOA train step on a 4x2 mesh: runs 2 steps, loss
    finite, params change, FLOA state updates."""
    out = run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        jax.config.update("jax_threefry_partitionable", True)
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import make_train_step, init_model, init_floa_state
        from repro.configs import get_smoke
        mesh = make_debug_mesh((4, 2), ("data", "model"))
        cfg = dataclasses.replace(get_smoke("qwen3-4b"), model_parallel=2)
        shape = dict(seq_len=64, global_batch=8, kind="train")
        art = make_train_step(cfg, mesh, shape, alpha=0.05)
        params, _ = init_model(cfg, jax.random.PRNGKey(0))
        state = init_floa_state()
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 65), 0, cfg.vocab_size)}
        with mesh:
            step = jax.jit(art.fn, in_shardings=art.in_shardings)
            p1, s1, m1 = step(params, state, batch, jnp.uint32(0))
            p2, s2, m2 = step(p1, s1, batch, jnp.uint32(1))
        l1, l2 = float(m1["loss"]), float(m2["loss"])
        assert np.isfinite(l1) and np.isfinite(l2), (l1, l2)
        assert l2 < l1 + 0.5
        d = float(jnp.sum(jnp.abs(p2["embed"] - params["embed"])))
        assert d > 0
        assert float(s2["eps2"]) != 1.0  # stats EMA updated
        print("TRAIN_OK", l1, l2)
    """)
    assert "TRAIN_OK" in out


def test_decode_step_on_mesh_matches_single_device():
    out = run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        jax.config.update("jax_threefry_partitionable", True)
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import make_decode_step, init_model
        from repro.models import transformer as T
        from repro.configs import get_smoke
        mesh = make_debug_mesh((4, 2), ("data", "model"))
        cfg = dataclasses.replace(get_smoke("starcoder2-3b"), model_parallel=2)
        shape = dict(seq_len=32, global_batch=8, kind="decode")
        art = make_decode_step(cfg, mesh, shape, "decode_32k")
        params, _ = init_model(cfg, jax.random.PRNGKey(0))
        caches = T.init_caches(cfg, 8, 32, window=cfg.window)
        toks = jax.random.randint(jax.random.PRNGKey(2), (8, 1), 0, cfg.vocab_size)
        with mesh:
            step = jax.jit(art.fn, in_shardings=art.in_shardings)
            logits_mesh, caches2 = step(params, caches, toks, jnp.int32(0))
        logits_1dev, _ = T.decode_step(params, T.init_caches(cfg, 8, 32, window=cfg.window), toks, jnp.int32(0), cfg, window=cfg.window)
        np.testing.assert_allclose(np.asarray(logits_mesh), np.asarray(logits_1dev), rtol=2e-3, atol=2e-3)
        print("DECODE_OK")
    """)
    assert "DECODE_OK" in out


def test_floa_weighted_loss_equals_vmap_aggregate():
    """The LLM-scale weighted-loss path must produce the same OTA aggregate
    as the paper-exact vmap(grad) path, given identical coefficients."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        jax.config.update("jax_threefry_partitionable", True)
        from repro.core.aggregation import per_worker_grads, _weighted_reduce
        U = 4
        def loss(params, batch):
            pred = batch["x"] @ params["w"]
            return jnp.mean((pred - batch["y"]) ** 2)
        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (6, 1))}
        batch = {"x": jax.random.normal(key, (U * 8, 6)),
                 "y": jax.random.normal(key, (U * 8, 1))}
        s = jnp.asarray([0.5, -0.2, 0.9, 0.1])
        # path 1: vmap per-worker grads then weighted reduce
        g_u, _ = per_worker_grads(loss, params, batch, U)
        agg1 = _weighted_reduce(g_u, s)
        # path 2: weighted scalar loss, single backward
        def per_ex(params, batch):
            pred = batch["x"] @ params["w"]
            return jnp.mean((pred - batch["y"]) ** 2, axis=-1)
        def wloss(params):
            pe = per_ex(params, batch)
            pw = pe.reshape(U, -1).mean(1)
            return jnp.dot(s, pw)
        agg2 = jax.grad(wloss)(params)
        np.testing.assert_allclose(np.asarray(agg1["w"]), np.asarray(agg2["w"]), rtol=1e-5)
        print("EQUIV_OK")
    """, devices=1)
    assert "EQUIV_OK" in out


def test_seqsharded_decode_partial_softmax():
    """Flash-decoding combine over a sequence-sharded KV cache (shard_map)
    matches the single-device reference exactly."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.models.attention import decode_local_partial, combine_partials
        from repro.kernels.ref import decode_attention_ref
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "model"))
        B, H, KV, dh, S = 2, 8, 2, 32, 256
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, dh))
        k = jax.random.normal(ks[1], (B, S, KV, dh))
        v = jax.random.normal(ks[2], (B, S, KV, dh))
        pos = 200
        def inner(q_loc, k_loc, v_loc):
            sloc = k_loc.shape[1]
            start = jax.lax.axis_index("model") * sloc
            valid = jnp.broadcast_to((start + jnp.arange(sloc))[None, :] <= pos,
                                     (q_loc.shape[0], sloc))
            m, l, acc = decode_local_partial(q_loc, k_loc, v_loc, valid)
            return combine_partials(m, l, acc, ("model",))
        f = jax.shard_map(inner, mesh=mesh,
                          in_specs=(P(), P(None, "model", None, None),
                                    P(None, "model", None, None)),
                          out_specs=P(), check_vma=False)
        got = f(q, k, v)
        want = decode_attention_ref(q, k, v, jnp.int32(pos))
        err = float(jnp.max(jnp.abs(got - want.astype(jnp.float32))))
        assert err < 1e-5, err
        print("SEQSHARD_OK", err)
    """)
    assert "SEQSHARD_OK" in out


def test_multipod_mesh_lowering():
    """The pod axis shards: tiny config lowers+compiles on a (2,2,2) mesh."""
    out = run_py("""
        import dataclasses, jax, jax.numpy as jnp
        jax.config.update("jax_threefry_partitionable", True)
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import make_train_step
        from repro.configs import get_smoke
        mesh = make_debug_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = dataclasses.replace(get_smoke("granite-8b"), model_parallel=2)
        shape = dict(seq_len=32, global_batch=8, kind="train")
        art = make_train_step(cfg, mesh, shape)
        with mesh:
            compiled = jax.jit(art.fn, in_shardings=art.in_shardings).lower(*art.args).compile()
        assert compiled.cost_analysis() is not None
        print("MULTIPOD_OK")
    """)
    assert "MULTIPOD_OK" in out
