"""The fused attention kernel (``kernels/flash_attention.py``, interpreted on
the CPU) against ``sdpa_chunked``'s jnp path, output and gradients; and
which shapes and backends ``sdpa_chunked`` hands to the kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.models import layers as L

f32, bf16 = jnp.float32, jnp.bfloat16
BLOCK = 128  # the kernel's smallest block: several blocks at small shapes


def _inputs(B, Sq, Sk, H, KV, hd):
    key = jax.random.key(Sq * 7 + H * 3 + hd)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, Sq, H, hd), bf16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sk, KV, hd), bf16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sk, KV, hd), bf16)
    do = jax.random.normal(jax.random.fold_in(key, 3), (B, Sq, H, hd), bf16)
    return q, k, v, do


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


# (B, Sq, Sk, H, KV, hd, window, q_offset); with 128-blocks every case has
# KV blocks that some query block never sees, which the kernel skips
CASES = {
    "causal": (1, 256, 256, 2, 2, 128, 256, 0),
    "window": (1, 512, 512, 2, 1, 128, 100, 0),  # sweeps of 3 blocks of 4
    "q_offset": (1, 256, 512, 2, 2, 128, 512, 256),
    "gqa16": (1, 256, 256, 16, 1, 128, 256, 0),
    "hd256": (1, 256, 256, 2, 1, 256, 256, 0),
}


@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_jnp_path(case):
    B, Sq, Sk, H, KV, hd, window, off = CASES[case]
    q, k, v, do = _inputs(B, Sq, Sk, H, KV, hd)

    def kernel(q, k, v):
        return ops.flash_attention(q, k, v, off, window=window, block_q=BLOCK, block_k=BLOCK)

    def jnp_path(q, k, v):
        return L.sdpa_chunked(q, k, v, window=window, causal=True, q_offset=off, q_chunk=128)

    o, vjp = jax.vjp(kernel, q, k, v)
    ro, rvjp = jax.vjp(jnp_path, q, k, v)
    # the kernel feeds P and dS to the MXU in bf16, as the chip runs the jnp
    # path's f32 einsums; the CPU runs those in f32: a bf16 rounding apart
    for name, a, b in zip(("o", "dq", "dk", "dv"), (o, *vjp(do)), (ro, *rvjp(do))):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < 1e-2, (name, _rel(a, b))


def test_kernel_scale_with_padded_head_dim():
    """MLA's q/k head dim 192 zero-padded to 256 with the scale of 192 (and
    YaRN's temperature), v at 128: the kernel against the jnp path at the
    unpadded 192, output and the gradients of the unpadded q, k, v."""
    key = jax.random.key(192)
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (1, 256, 2, 192), bf16) for i in (0, 1))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 2, 128), bf16)
    do = jax.random.normal(jax.random.fold_in(key, 3), (1, 256, 2, 128), bf16)
    scale = 192**-0.5 * 1.5896
    pad = [(0, 0)] * 3 + [(0, 64)]

    def kernel(q, k, v):
        return ops.flash_attention(jnp.pad(q, pad), jnp.pad(k, pad), v, 0, window=256,
                                   block_q=BLOCK, block_k=BLOCK, scale=scale)

    def jnp_path(q, k, v):
        return L.sdpa_chunked(q, k, v, window=256, causal=True, q_chunk=128, scale=scale)

    o, vjp = jax.vjp(kernel, q, k, v)
    ro, rvjp = jax.vjp(jnp_path, q, k, v)
    for name, a, b in zip(("o", "dq", "dk", "dv"), (o, *vjp(do)), (ro, *rvjp(do))):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < 1e-2, (name, _rel(a, b))


GLM = ((1, 4096, 32, 128), (1, 4096, 2, 128), (1, 4096, 2, 128))
QWEN3 = ((1, 2048, 16, 128), (1, 2048, 8, 128), (1, 2048, 8, 128))
MLA = ((1, 4096, 16, 192), (1, 4096, 16, 192), (1, 4096, 16, 128))
DISPATCH = {
    # (backend, shapes, causal) -> takes the kernel
    "glm4-9b cell": ("tpu", GLM, True, True),
    "qwen3-0.6b cell": ("tpu", QWEN3, True, True),
    "gemma3 hd256": ("tpu", ((1, 4096, 8, 256), (1, 4096, 4, 256), (1, 4096, 4, 256)), True, True),
    "seqpar shard": ("tpu", ((1, 512, 32, 128), (1, 4096, 2, 128), (1, 4096, 2, 128)), True, True),
    "cpu": ("cpu", GLM, True, False),
    "mla qk 192": ("tpu", MLA, True, False),
    "mla qk padded to 256": ("tpu", ((4, 4096, 16, 256), (4, 4096, 16, 256), (4, 4096, 16, 128)),
                             True, True),
    "cross attention": ("tpu", ((1, 512, 16, 128), (1, 1536, 16, 128), (1, 1536, 16, 128)),
                        False, False),
    "seq not a multiple of 128": ("tpu", ((1, 1000, 4, 128),) + ((1, 1000, 2, 128),) * 2,
                                  True, False),
    "hd 64": ("tpu", ((1, 2048, 8, 64),) + ((1, 2048, 8, 64),) * 2, True, False),
}


@pytest.mark.parametrize("case", DISPATCH)
def test_dispatch(case):
    backend, shapes, causal, takes = DISPATCH[case]
    blocks = L.flash_blocks(backend, *shapes, causal)
    if takes:
        assert blocks == fa.block_sizes(shapes[0][1], shapes[1][1]) is not None
    else:
        assert blocks is None


def test_sdpa_dispatch_and_kv_gather(monkeypatch):
    """On the CPU sdpa_chunked never calls the kernel; on a TPU it does, with
    K/V at their KV heads where the head map is the grouping, and gathered
    to every query head where the map is traced."""
    q, k, v, _ = _inputs(1, 256, 256, 4, 2, 128)
    calls = []

    def spy(q, k, v, q_offset, **kw):
        calls.append(k.shape[2])
        return L._sdpa_jnp(q, k, v, q_pos=q_offset + jnp.arange(q.shape[1]),
                           k_pos=jnp.arange(k.shape[1]), window=kw["window"], causal=True,
                           q_chunk=1024)

    monkeypatch.setattr(ops, "flash_attention", spy)
    grouping = np.arange(4) // 2
    cpu = L.sdpa_chunked(q, k, v, window=256, kv_map=grouping)
    assert calls == []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tpu = L.sdpa_chunked(q, k, v, window=256, kv_map=grouping)
    jax.jit(lambda m: L.sdpa_chunked(q, k, v, window=256, kv_map=m))(jnp.asarray(grouping))
    assert calls == [2, 4]
    np.testing.assert_allclose(np.asarray(cpu, f32), np.asarray(tpu, f32), rtol=1e-2, atol=1e-2)
