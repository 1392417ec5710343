"""Compressors backed by the Pallas TPU kernels (repro.kernels).

Same wire semantics as their jnp counterparts (tested equal), but the
compression pass is a single fused VMEM-tiled kernel, and SignSGD gets true
1-bit packing (32x wire reduction — int8 payloads are only 4x).

Batchability note: ``qsgd_kernel`` passes ``levels`` into the kernel as a
TRACED (1,1) scalar block (mask-style, like the top-k rank mask) rather
than a specialization constant, so it declares ``BATCH_KNOBS`` /
``RUNTIME_KNOBS`` exactly like the jnp ``qsgd`` — sweep cells that differ
only in levels share one compiled program at both layers
(``engine_cache_stats`` asserts it in tests/test_sweep_batched.py).  The
fused EF kernel runs inside the batched sweep via ``roundtrip_ef_p``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.compression.base import Compressed, register
from repro.kernels import ops

f32 = jnp.float32


@register("qsgd_kernel")
@dataclass
class QSGDKernel:
    levels: int = 16
    unbiased: bool = True
    reduce_mode: str = "none"
    wire_reduce: str = "int8_acc"  # compressed-domain: int8 codes on the wire
    BATCH_KNOBS = ("levels",)
    RUNTIME_KNOBS = ("levels",)

    def _check(self):
        # the int8 wire format caps |code| at s — fail loudly, don't wrap
        if self.levels > 127:
            raise ValueError(f"qsgd_kernel levels={self.levels} exceeds the "
                             "int8 wire format (max 127)")
        return {"levels": self.levels}

    def batch_params(self, dim: int) -> dict:
        return self._check()

    def runtime_params(self) -> dict:
        return self._check()

    def compress_wire_p(self, key, x, p) -> Compressed:
        """The wire payload of the compressed-domain aggregation: the codes
        as the kernel's 32-bit words (``ops.qsgd_quantize``), which the
        all-gather and ``ops.int8_weighted_sum`` take as they are."""
        u = jax.random.uniform(key, x.shape)
        words, norm = ops.qsgd_quantize(x, u, levels=(p or {}).get("levels", self.levels))
        return Compressed({"code": words, "norm": norm}, x.size)

    def compress_p(self, key, x, p) -> Compressed:
        c = self.compress_wire_p(key, x, p)
        return Compressed({"code": ops.int8_unpack(c.payload["code"], c.n),
                           "norm": c.payload["norm"]}, c.n)

    def decompress_p(self, c, p) -> jax.Array:
        return ops.qsgd_dequantize(c.payload["code"], c.payload["norm"],
                                   levels=p.get("levels", self.levels))

    def compress(self, key, x) -> Compressed:
        return self.compress_p(key, x, {})

    def decompress(self, c) -> jax.Array:
        return self.decompress_p(c, {})

    def _bits(self, n, p) -> jax.Array:
        s = jnp.asarray(p.get("levels", self.levels), f32)
        return n * (jnp.log2(s) + 1.0) + 32.0

    def roundtrip_p(self, key, x, p):
        c = self.compress_p(key, x, p)
        return self.decompress_p(c, p), self._bits(x.size, p)

    def roundtrip_ef_p(self, key, g, e, p):
        """Fused EF+quantize (one Pallas pass instead of three dense ones),
        with levels traced."""
        lv = p.get("levels", self.levels)
        u = jax.random.uniform(key, g.shape)
        words, norm, e_new = ops.qsgd_ef_fused(g, e, u, levels=lv)
        codes = ops.int8_unpack(words, g.size)
        return ops.qsgd_dequantize(codes, norm, levels=lv), e_new, self._bits(g.size, p)

    def compress_decompress_ef(self, key, g, e):
        """Knob-free fused path (kept for direct callers)."""
        out, e_new, _ = self.roundtrip_ef_p(key, g, e, {})
        return out, e_new

    def compress_ef_p(self, key, g, e, p, decay):
        """Fused EF+quantize that returns the WIRE payload (for the
        compressed-domain aggregation path): one Pallas pass yields the int8
        codes, as ``compress_wire_p``'s words, and the residual update, with
        levels and decay traced.  Uses
        the same uniform draw as ``compress_p`` after ``pre_compress``'s
        a = e*decay + g, so the codes match the composed path bit for bit
        (the residual differs by one reciprocal rounding)."""
        lv = (p or {}).get("levels", self.levels)
        u = jax.random.uniform(key, g.shape)
        words, norm, e_new = ops.qsgd_ef_fused(g, e, u, levels=lv, decay=decay)
        return Compressed({"code": words, "norm": norm}, g.size), e_new

    def wire_bits(self, n) -> float:
        import math

        return n * (math.log2(self.levels) + 1) + 32


@register("terngrad_kernel")
@dataclass
class TernGradKernel:
    unbiased: bool = True
    reduce_mode: str = "none"
    wire_reduce: str = "tern_acc"  # compressed-domain: 2-bit packed wire

    def compress(self, key, x) -> Compressed:
        u = jax.random.uniform(key, x.shape)
        tern, smax = ops.terngrad_quantize(x, u)
        return Compressed({"tern": tern, "scale": smax}, x.size)

    def decompress(self, c) -> jax.Array:
        return c.payload["tern"].astype(f32) * c.payload["scale"][0]

    def wire_bits(self, n) -> float:
        return n * 2.0 + 32


@register("signsgd_packed")
@dataclass
class SignSGDPacked:
    """SignSGD with true bit packing: 1 bit/element on the wire."""

    unbiased: bool = False
    reduce_mode: str = "none"
    wire_reduce: str = "sign_acc"  # compressed-domain: mean of ±1 votes

    def compress(self, key, x) -> Compressed:
        return Compressed({"packed": ops.sign_pack(x)}, x.size)

    def decompress(self, c) -> jax.Array:
        return ops.sign_unpack(c.payload["packed"], c.n)

    def wire_bits(self, n) -> float:
        return n * 1.0
