"""Per-kernel allclose vs the ref.py oracles: shape/dtype sweeps
(interpret=True executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

f32 = jnp.float32
SIZES = [100, 1000, 32768, 100_003]


def _x(n, seed=0, dtype=f32, scale=0.1):
    return (jax.random.normal(jax.random.key(seed), (n,)) * scale).astype(dtype)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("levels", [4, 16, 64])
def test_qsgd_kernel(n, levels):
    x = _x(n)
    u = jax.random.uniform(jax.random.key(1), (n,))
    codes, norm = ops.qsgd_quantize(x, u, levels=levels)
    expected = ref.qsgd_ref(x, u, jnp.linalg.norm(x), levels)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(expected))
    np.testing.assert_allclose(float(norm[0]), float(jnp.linalg.norm(x)), rtol=1e-6)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("decay", [1.0, 0.9])
def test_qsgd_ef_fused(n, decay):
    g, e = _x(n, 0), _x(n, 1, scale=0.05)
    u = jax.random.uniform(jax.random.key(2), (n,))
    codes, norm, enew = ops.qsgd_ef_fused(g, e, u, levels=16, decay=decay)
    a_norm = jnp.linalg.norm(e * decay + g)
    cr, er = ref.qsgd_ef_ref(g, e, u, a_norm, 16, decay)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(cr))
    np.testing.assert_allclose(np.asarray(enew), np.asarray(er), rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("dtype", [f32, jnp.bfloat16])
def test_terngrad_kernel(n, dtype):
    x = _x(n, dtype=dtype)
    u = jax.random.uniform(jax.random.key(1), (n,))
    tern, smax = ops.terngrad_quantize(x, u)
    expected = ref.terngrad_ref(x.astype(f32), u, jnp.max(jnp.abs(x.astype(f32))))
    np.testing.assert_array_equal(np.asarray(tern), np.asarray(expected))


@pytest.mark.parametrize("n", [64, 1000, 65536, 100_003])
def test_sign_pack_roundtrip(n):
    x = _x(n)
    packed = ops.sign_pack(x)
    assert packed.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(ref.sign_pack_ref(x)))
    out = ops.sign_unpack(packed, n)
    np.testing.assert_array_equal(np.asarray(out), np.where(np.asarray(x) >= 0, 1.0, -1.0))


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("tau", [0.0, 0.05, 10.0])
def test_threshold_kernel(n, tau):
    x = _x(n)
    masked, nnz = ops.threshold_sparsify(x, tau)
    exp_masked, _ = ref.threshold_ref(x, jnp.asarray(tau))
    np.testing.assert_allclose(np.asarray(masked), np.asarray(exp_masked))
    if tau > 0:
        assert int(nnz) == int(np.sum(np.abs(np.asarray(exp_masked)) > 0))


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (1, 32, 1, 16, 16), (2, 96, 3, 16, 32), (1, 64, 2, 64, 64), (2, 100, 2, 32, 32),
])
def test_wkv6_kernel(B, S, H, hd, chunk):
    k0 = jax.random.key(10)
    r, k, v = (jax.random.normal(jax.random.fold_in(k0, i), (B, S, H, hd)) * 0.5 for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(k0, 5), (B, S, H, hd))) * 0.5 + 0.4
    u = jax.random.normal(jax.random.fold_in(k0, 6), (H, hd)) * 0.1
    s0 = jax.random.normal(jax.random.fold_in(k0, 7), (B, H, hd, hd)) * 0.1
    y, sT = ops.wkv6(r, k, v, w, u, s0, chunk=chunk)
    yr, sr = ref.wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sr), rtol=3e-4, atol=3e-5)


def test_wkv6_matches_model_scan():
    """Kernel agrees with the model's lax.scan path (rwkv.wkv_scan)."""
    from repro.models.rwkv import wkv_scan

    k0 = jax.random.key(11)
    B, S, H, hd = 2, 40, 2, 16
    r, k, v = (jax.random.normal(jax.random.fold_in(k0, i), (B, S, H, hd)) * 0.5 for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(k0, 5), (B, S, H, hd))) * 0.5 + 0.4
    u = jax.random.normal(jax.random.fold_in(k0, 6), (H, hd)) * 0.1
    s0 = jnp.zeros((B, H, hd, hd), f32)
    y1, s1 = ops.wkv6(r, k, v, w, u, s0, chunk=8)
    y2, s2 = wkv_scan(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=3e-4, atol=3e-5)
