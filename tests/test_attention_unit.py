"""Unit tests for the attention core: window-sliced K/V (the §Perf pair-1
optimization) must be exactly equivalent to full-row masked attention, for
any window/chunk/seq combination."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests.hypothesis_compat import given, settings, st

from repro.models.layers import sdpa_chunked

f32 = jnp.float32


def _attn_ref(q, k, v, window, causal):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, Sq, KV, g, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(f32) * hd**-0.5, k.astype(f32))
    qp, kp = jnp.arange(Sq), jnp.arange(k.shape[1])
    diff = qp[:, None] - kp[None, :]
    ok = diff < window
    if causal:
        ok &= diff >= 0
    s = jnp.where(ok[None, None, None], s, -1e30)
    a = jax.nn.softmax(s, -1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", a, v.astype(f32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


@given(
    st.sampled_from([32, 64, 128]),   # seq
    st.sampled_from([8, 16, 31, 1000]),  # window
    st.sampled_from([16, 32, 64]),    # q_chunk
)
@settings(max_examples=25, deadline=None)
def test_window_slice_equals_masked(S, window, q_chunk):
    B, H, KV, hd = 2, 4, 2, 16
    key = jax.random.key(S * 1000 + window)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))
    # sel-gather the kv per q head to group=1 (as attention() does) or use
    # aligned grouping — here H % KV == 0, use grouping directly
    out = sdpa_chunked(q, k, v, window=window, causal=True, q_chunk=q_chunk)
    ref = _attn_ref(q, k, v, window, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_noncausal_cross_attention_path():
    B, Sq, Sk, H, hd = 2, 8, 24, 4, 16
    key = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, Sq, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sk, H, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sk, H, hd))
    out = sdpa_chunked(q, k, v, window=Sk + Sq, causal=False, q_chunk=8)
    ref = _attn_ref(q, k, v, Sk + Sq + 100, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
