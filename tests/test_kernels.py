"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops


@pytest.mark.parametrize("u", [4, 10, 32])
@pytest.mark.parametrize("d", [512, 2048, 5000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_floa_aggregate_sweep(u, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(u * d), 4)
    coeffs = jax.random.normal(ks[0], (u,))
    grads = jax.random.normal(ks[1], (u, d)).astype(dtype)
    noise = jax.random.normal(ks[2], (d,)).astype(dtype)
    bias, eps = jnp.float32(-0.2), jnp.float32(1.3)
    got = ops.floa_aggregate(coeffs, grads, noise, bias, eps, interpret=True)
    want = ops.floa_aggregate_ref(coeffs, grads, noise, bias, eps)
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("s,u,d", [(1, 4, 512), (3, 10, 2048), (4, 8, 5000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_floa_step_batched_sweep(s, u, d, dtype):
    """Fused combine+update kernel vs oracle across shapes/dtypes."""
    ks = jax.random.split(jax.random.PRNGKey(s * u + d), 7)
    w = jax.random.normal(ks[0], (s, d)).astype(dtype)
    coeffs = jax.random.normal(ks[1], (s, u))
    grads = jax.random.normal(ks[2], (s, u, d)).astype(dtype)
    noise = jax.random.normal(ks[3], (s, d)).astype(dtype)
    bias = jax.random.normal(ks[4], (s,))
    eps = jax.random.normal(ks[5], (s,))
    alpha = jax.random.uniform(ks[6], (s,), minval=0.01, maxval=0.2)
    wn, gg = ops.floa_step_batched(w, coeffs, grads, noise, bias, eps, alpha,
                                   interpret=True)
    wr, gr = ops.floa_step_batched_ref(w, coeffs, grads, noise, bias, eps,
                                       alpha)
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(wn, np.float32),
                               np.asarray(wr, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(gg, np.float32),
                               np.asarray(gr, np.float32), rtol=tol, atol=tol)


def test_floa_step_ref_is_combine_plus_update():
    """The fused oracle decomposes exactly into combine oracle + PS update."""
    s, u, d = 3, 6, 777
    ks = jax.random.split(jax.random.PRNGKey(11), 7)
    w = jax.random.normal(ks[0], (s, d))
    coeffs = jax.random.normal(ks[1], (s, u))
    grads = jax.random.normal(ks[2], (s, u, d))
    noise = jax.random.normal(ks[3], (s, d))
    bias = jax.random.normal(ks[4], (s,))
    eps = jax.random.normal(ks[5], (s,))
    alpha = jax.random.uniform(ks[6], (s,))
    wn, gg = ops.floa_step_batched_ref(w, coeffs, grads, noise, bias, eps,
                                       alpha)
    want_g = ops.floa_aggregate_batched_ref(coeffs, grads, noise, bias, eps)
    np.testing.assert_array_equal(np.asarray(gg), np.asarray(want_g))
    np.testing.assert_array_equal(np.asarray(wn),
                                  np.asarray(w - alpha[:, None] * want_g))


@pytest.mark.parametrize("s,d,tile_d", [
    (2, 300, 128), (2, 5000, 2048), (2, 129, 128), (2, 127, 128),
    # S > 8: a ragged lane block (8 + 3 lanes) beside a ragged column block
    (11, 127, None), (11, 129, None), (11, 5077, None), (11, 5077, 512)])
def test_batched_kernel_pads_non_multiple_d(s, d, tile_d):
    """D not a multiple of the column block, and S not a multiple of the
    lane block: the grid's last blocks are ragged, no operand is padded and
    no output sliced, and every real entry matches the oracle.  Interpret
    mode, both batched kernels; tile_d=None takes the derived block."""
    from repro.kernels.floa_aggregate import (floa_aggregate_batched,
                                              floa_step_batched)
    u = 5
    ks = jax.random.split(jax.random.PRNGKey(d + s), 7)
    w = jax.random.normal(ks[0], (s, d))
    coeffs = jax.random.normal(ks[1], (s, u))
    grads = jax.random.normal(ks[2], (s, u, d))
    noise = jax.random.normal(ks[3], (s, d))
    bias = jax.random.normal(ks[4], (s,))
    eps = jax.random.normal(ks[5], (s,))
    alpha = jax.random.uniform(ks[6], (s,))
    out = floa_aggregate_batched(coeffs, grads, noise, bias, eps,
                                 interpret=True, tile_d=tile_d)
    want = ops.floa_aggregate_batched_ref(coeffs, grads, noise, bias, eps)
    assert out.shape == (s, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    wn, gg = floa_step_batched(w, coeffs, grads, noise, bias, eps, alpha,
                               interpret=True, tile_d=tile_d)
    wr, gr = ops.floa_step_batched_ref(w, coeffs, grads, noise, bias, eps,
                                       alpha)
    assert wn.shape == (s, d) and gg.shape == (s, d)
    np.testing.assert_allclose(np.asarray(wn), np.asarray(wr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("s", [1, 2, 16, 272])
@pytest.mark.parametrize("u", [1, 4, 10, 32])
def test_batched_blocks_fit_vmem_limit(s, u):
    """The derived (s_blk, T): a lane block Mosaic accepts (all of S up to
    8, else 8), T the widest multiple of 128 whose double-buffered blocks
    fit the budget, under the scoped-VMEM limit the kernels ask for, and
    each grid step moving at least a megabyte of HBM at a wide D."""
    from repro.kernels import floa_aggregate as FA
    assert FA.BATCHED_BLOCK_BUDGET <= FA.BATCHED_VMEM_LIMIT
    for itemsize in (4, 2):
        for n_rows in (2, 4):           # aggregate: noise, out; step: +w, w_new
            s_blk, t = FA.batched_blocks(s, u, 1 << 27, itemsize, n_rows)
            assert s_blk == min(s, 8)
            assert t % 128 == 0 and t >= 128
            vmem = FA.batched_vmem_bytes(s_blk, u, t, itemsize, n_rows)
            assert vmem <= FA.BATCHED_BLOCK_BUDGET
            assert FA.batched_vmem_bytes(s_blk, u, t + 128, itemsize,
                                         n_rows) > FA.BATCHED_BLOCK_BUDGET
            assert (s_blk * u + n_rows * s_blk) * t * itemsize >= 10 ** 6
    # sublane padding is counted: U = 1 costs a whole 8-row tile of f32
    assert (FA.batched_vmem_bytes(1, 1, 128, 4, 0)
            == FA.batched_vmem_bytes(1, 8, 128, 4, 0) == 2 * 8 * 128 * 4)
    # a narrow D takes one block no wider than D needs
    assert FA.batched_blocks(s, u, 300, 4, 4)[1] == 384


def test_floa_step_property_random_shapes():
    """Hypothesis property: kernel == oracle for arbitrary small shapes and
    tile sizes (including D < tile_d, D == tile_d, D % tile_d != 0)."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.kernels.floa_aggregate import floa_step_batched

    @settings(max_examples=10, deadline=None)
    @given(s=st.integers(1, 4), u=st.integers(1, 8), d=st.integers(1, 600),
           tile_p=st.integers(0, 2), seed=st.integers(0, 2**31 - 1))
    def prop(s, u, d, tile_p, seed):
        tile_d = 128 * (2 ** tile_p)
        ks = jax.random.split(jax.random.PRNGKey(seed), 7)
        w = jax.random.normal(ks[0], (s, d))
        coeffs = jax.random.normal(ks[1], (s, u))
        grads = jax.random.normal(ks[2], (s, u, d))
        noise = jax.random.normal(ks[3], (s, d))
        bias = jax.random.normal(ks[4], (s,))
        eps = jax.random.normal(ks[5], (s,))
        alpha = jax.random.uniform(ks[6], (s,))
        wn, gg = floa_step_batched(w, coeffs, grads, noise, bias, eps, alpha,
                                   interpret=True, tile_d=tile_d)
        wr, gr = ops.floa_step_batched_ref(w, coeffs, grads, noise, bias,
                                           eps, alpha)
        np.testing.assert_allclose(np.asarray(wn), np.asarray(wr),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4)

    prop()


@pytest.mark.parametrize("u,d", [(4, 256), (10, 2048), (16, 5000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grad_stats_sweep(u, d, dtype):
    g = (jax.random.normal(jax.random.PRNGKey(u + d), (u, d)) * 0.7).astype(dtype)
    got = ops.grad_stats(g, interpret=True)
    want = ops.grad_stats_ref(g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("b,h,kv,dh,s", [
    (1, 4, 1, 64, 512),     # MQA
    (2, 8, 2, 64, 1024),    # GQA
    (2, 8, 8, 128, 777),    # MHA, ragged length
    (1, 16, 4, 128, 2048),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, h, kv, dh, s, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * s + h), 3)
    q = jax.random.normal(ks[0], (b, h, dh)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, dh)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, dh)).astype(dtype)
    pos = jnp.int32(s - 3)
    got = ops.decode_attention(q, k, v, pos, interpret=True)
    want = ops.decode_attention_ref(q, k, v, pos)
    tol = 2e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_decode_attention_masks_future():
    """Entries beyond pos must not affect the output."""
    b, h, kv, dh, s = 1, 4, 2, 32, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, dh))
    k = jax.random.normal(ks[1], (b, s, kv, dh))
    v = jax.random.normal(ks[2], (b, s, kv, dh))
    pos = jnp.int32(100)
    out1 = ops.decode_attention(q, k, v, pos, interpret=True)
    k2 = k.at[:, 101:].set(99.0)
    v2 = v.at[:, 101:].set(-99.0)
    out2 = ops.decode_attention(q, k2, v2, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


# ------------------------------------------------ threshold-routing contracts
# The production engines auto-route to the Pallas kernels on TPU once the
# flat gradient crosses a static size threshold (BATCHED_KERNEL_MIN_D = 2^16
# for the fused FLOA step, SORT_KERNEL_MIN_D = 2^14 for the screening sort).
# The LM sweep lane (D ~ 3e6) lives far past both, so the kernel == oracle
# contract is pinned at D just below / at / above each threshold — the exact
# sizes where a routing regression would flip the implementation.


@pytest.mark.parametrize("d", [(1 << 16) - 1, 1 << 16, (1 << 16) + 1])
def test_floa_step_batched_kernel_oracle_at_routing_threshold(d):
    from repro.core.aggregation import BATCHED_KERNEL_MIN_D, batched_floa_step
    assert BATCHED_KERNEL_MIN_D == 1 << 16
    s, u = 2, 3
    ks = jax.random.split(jax.random.PRNGKey(d), 7)
    w = jax.random.normal(ks[0], (s, d))
    coeffs = jax.random.normal(ks[1], (s, u))
    grads = jax.random.normal(ks[2], (s, u, d))
    noise = jax.random.normal(ks[3], (s, d))
    bias = jax.random.normal(ks[4], (s,))
    eps = jax.random.normal(ks[5], (s,))
    alpha = jax.random.uniform(ks[6], (s,), minval=0.01, maxval=0.2)
    wn, gg = batched_floa_step(w, alpha, coeffs, grads, noise, bias, eps,
                               use_kernel=True, interpret=True)
    wr, gr = batched_floa_step(w, alpha, coeffs, grads, noise, bias, eps,
                               use_kernel=False)
    assert wn.shape == (s, d) and gg.shape == (s, d)
    np.testing.assert_allclose(np.asarray(wn), np.asarray(wr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d", [(1 << 14) - 1, 1 << 14, (1 << 14) + 1,
                               (1 << 16) - 1, 1 << 16, (1 << 16) + 1])
def test_grad_stats_kernel_oracle_at_routing_thresholds(d):
    """The standardization-stats kernel feeds the same engines, so its
    oracle contract is pinned across both routing thresholds too."""
    u = 6
    g = jax.random.normal(jax.random.PRNGKey(d), (u, d)) * 0.7
    got = ops.grad_stats(g, interpret=True)
    want = ops.grad_stats_ref(g)
    assert got.shape == (u, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("u", [8, 64])   # unrolled network / bitonic stages
@pytest.mark.parametrize("d", [(1 << 14) - 1, 1 << 14, (1 << 14) + 1])
def test_sorted_columns_kernel_oracle_at_routing_threshold(u, d):
    from repro.core.defenses import SORT_KERNEL_MIN_D, sorted_columns
    assert SORT_KERNEL_MIN_D == 1 << 14
    x = jax.random.normal(jax.random.PRNGKey(u + d), (u, d))
    got = sorted_columns(x, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.sort(x, axis=0)))


def test_routing_predicate_resolves_off_tpu():
    """use_kernel=None must resolve False off-TPU at ANY size (CPU hosts
    would otherwise drop into interpret-mode Pallas on the hot path); the
    oracle route is the same function the kernels are pinned against."""
    if jax.default_backend() == "tpu":
        pytest.skip("predicate under test is the off-TPU resolution")
    from repro.core.aggregation import batched_floa_combine
    from repro.core.defenses import sorted_columns
    from repro.kernels import ref
    s, u, d = 1, 3, (1 << 16) + 5
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    coeffs = jax.random.normal(ks[0], (s, u))
    grads = jax.random.normal(ks[1], (s, u, d))
    noise = jax.random.normal(ks[2], (s, d))
    bias = jax.random.normal(ks[3], (s,))
    eps = jax.random.normal(ks[4], (s,))
    np.testing.assert_array_equal(
        np.asarray(batched_floa_combine(coeffs, grads, noise, bias, eps)),
        np.asarray(ref.floa_aggregate_batched_ref(coeffs, grads, noise,
                                                  bias, eps)))
    x = grads[0, :, : (1 << 14) + 5]
    np.testing.assert_array_equal(np.asarray(sorted_columns(x)),
                                  np.asarray(jnp.sort(x, axis=0)))
