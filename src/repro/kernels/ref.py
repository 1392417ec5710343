"""Pure-jnp oracles for every Pallas kernel (allclose-tested in
tests/test_kernels.py across shape/dtype sweeps)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.sign_pack import LANES, WORD_ROWS, block_rows

f32 = jnp.float32


def qsgd_ref(x: jax.Array, u: jax.Array, norm: jax.Array, levels: int) -> jax.Array:
    """Stochastic dithering codes: sign(x) * level, |level| <= levels (int8)."""
    y = jnp.abs(x).astype(f32) / jnp.maximum(norm, 1e-30) * levels
    l = jnp.floor(y)
    l = l + (u < (y - l))
    return (jnp.sign(x) * l).astype(jnp.int8)


def qsgd_ef_ref(
    g: jax.Array, e: jax.Array, u: jax.Array, norm: jax.Array, levels: int, decay: float
) -> tuple[jax.Array, jax.Array]:
    """Fused: a = e*decay + g; code = Q(a); e_new = a - deQ(code)."""
    a = e.astype(f32) * decay + g.astype(f32)
    code = qsgd_ref(a, u, norm, levels)
    deq = code.astype(f32) / levels * norm
    return code, a - deq


def terngrad_ref(x: jax.Array, u: jax.Array, smax: jax.Array) -> jax.Array:
    p = jnp.abs(x).astype(f32) / jnp.maximum(smax, 1e-30)
    b = (u < p).astype(jnp.int8)
    return (jnp.sign(x).astype(jnp.int8) * b).astype(jnp.int8)


def _slot_view(flat: jax.Array, slots: int, pad) -> jax.Array:
    """Flat (n,) -> (steps, slots, R, 128): the packed-wire tile layout of
    :mod:`repro.kernels.sign_pack` (slot j of word row w in a grid step is
    element row j*R + w), padded to whole tiles with ``pad``."""
    tile = slots * WORD_ROWS * LANES
    x = jnp.pad(flat.reshape(-1), (0, (-flat.size) % tile), constant_values=pad)
    r = block_rows(x.size // (slots * LANES))
    return x.reshape(-1, slots, r, LANES)


def _pack_slots(codes: jax.Array, bits: int) -> jax.Array:
    """(steps, slots, R, 128) small unsigned codes -> flat uint32 words."""
    shifts = (bits * jnp.arange(codes.shape[1], dtype=jnp.uint32)).reshape(1, -1, 1, 1)
    return jnp.sum(codes.astype(jnp.uint32) << shifts, axis=1,
                   dtype=jnp.uint32).reshape(-1)


def _unpack_slots(words: jax.Array, slots: int, bits: int) -> jax.Array:
    """Flat uint32 words -> (steps, slots, R, 128) codes."""
    r = block_rows(words.size // LANES)
    w = words.reshape(-1, 1, r, LANES)
    shifts = (bits * jnp.arange(slots, dtype=jnp.uint32)).reshape(1, -1, 1, 1)
    return (w >> shifts) & ((1 << bits) - 1)


def sign_pack_ref(x: jax.Array) -> jax.Array:
    """Flat x (n,) -> uint32 words, 32 sign bits each (bit=1 means x>=0);
    the pad tail packs as +1."""
    return _pack_slots(_slot_view(x, 32, 1.0) >= 0, 1)


def sign_unpack_ref(packed: jax.Array, n: int) -> jax.Array:
    """uint32 words -> (n,) f32 in {-1, +1}."""
    bits = _unpack_slots(packed, 32, 1)
    return (bits.astype(f32) * 2.0 - 1.0).reshape(-1)[:n]


def _sequential_sum(vals: jax.Array, weights: jax.Array) -> jax.Array:
    """sum_w weights[w]*vals[w], accumulated in worker order (the order the
    fused kernels use, so float sums match bit for bit)."""
    acc = vals[0].astype(f32) * weights[0].astype(f32)
    for w in range(1, vals.shape[0]):
        acc = acc + vals[w].astype(f32) * weights[w].astype(f32)
    return acc


def sign_vote_ref(signs: jax.Array, weights: jax.Array) -> jax.Array:
    """signs (W, n) in {-1,+1}, weights (W,) -> weighted vote sums (n,)."""
    return _sequential_sum(signs, weights)


def tern_pack_ref(tern: jax.Array) -> jax.Array:
    """tern (n,) int8 in {-1,0,+1} -> uint32 words of 16 two-bit slots;
    code 0=zero, 1=+1, 3=-1 (bit0 nonzero, bit1 negative)."""
    t = _slot_view(tern, 16, 0)
    return _pack_slots((t != 0).astype(jnp.uint32)
                       | ((t < 0).astype(jnp.uint32) << 1), 2)


def tern_unpack_ref(packed: jax.Array, n: int) -> jax.Array:
    """uint32 words -> (n,) f32 in {-1, 0, +1}."""
    slot = _unpack_slots(packed, 16, 2)
    val = (slot == 1).astype(f32) - (slot == 3).astype(f32)
    return val.reshape(-1)[:n]


def int8_pack_ref(codes: jax.Array) -> jax.Array:
    """codes (n,) int8 -> uint32 words of 4 byte slots, the layout of the
    qsgd kernels: padded with zero codes to whole (256, 128) blocks, whose
    64 word rows each hold code rows w, 64 + w, 128 + w and 192 + w."""
    c = jnp.pad(codes.reshape(-1), (0, (-codes.size) % (256 * LANES)))
    return _pack_slots(_slot_view(c, 4, 0).astype(jnp.uint8), 8)


def int8_unpack_ref(packed: jax.Array, n: int) -> jax.Array:
    """uint32 words of ``int8_pack_ref`` -> the (n,) int8 codes."""
    return _unpack_slots(packed, 4, 8).astype(jnp.uint8).astype(jnp.int8).reshape(-1)[:n]


def weighted_sum_ref(vals: jax.Array, weights: jax.Array) -> jax.Array:
    """vals (W, n), weights (W,) -> sum_w weights[w]*vals[w] as (n,) f32."""
    return _sequential_sum(vals, weights)


def threshold_ref(x: jax.Array, tau: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(masked values, per-row kept counts (int32))."""
    keep = jnp.abs(x) >= tau
    return jnp.where(keep, x, 0.0), jnp.sum(keep, axis=-1, dtype=jnp.int32)


def wkv6_ref(
    r: jax.Array,  # (B, S, H, hd) f32
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decay in (0,1)
    u: jax.Array,  # (H, hd)
    s0: jax.Array,  # (B, H, hd, hd)
) -> tuple[jax.Array, jax.Array]:
    """Sequential WKV6 (same math as repro.models.rwkv.wkv_scan)."""

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp
        kv = k_t[..., :, None] * v_t[..., None, :]
        y = jnp.einsum("bhi,bhij->bhj", r_t, S + u[..., :, None] * kv)
        S = w_t[..., :, None] * S + kv
        return S, y

    seq = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (r, k, v, w))
    S, ys = jax.lax.scan(step, s0.astype(f32), seq)
    return jnp.moveaxis(ys, 0, 1), S
