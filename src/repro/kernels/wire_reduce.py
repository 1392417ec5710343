"""Compressed-domain reduction kernels: unpack+accumulate fused in one pass.

The collective epilogue for compressed wire formats.  After an all-gather of
*packed* payloads — 1-bit sign bitmaps (`sign_pack`), 2-bit ternary codes
(`tern_pack_2d`), or 8-bit quantizer codes (`repro.kernels.qsgd`) — these
kernels decode each worker's payload and accumulate the per-worker weighted
sum in f32 without ever materializing the (W, n) dense decode in HBM.  The
worker weight input carries the whole per-worker epilogue: participation
mask (churn `alive`), ternary scale, or qsgd `norm/levels`, so the kernels
stay linear-algebra-free and the callers (``repro.core.aggregate``) keep the
denominator logic.

Packed payloads are 32-bit words (the chip's bit arithmetic and reductions
work on 32-bit lanes): 32 sign bits, 16 two-bit ternary slots or 4 int8
codes per word, in the tile layout described in
:mod:`repro.kernels.sign_pack`.  Workers are accumulated in index order, so
the sums match a sequential reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.qsgd import CODES, code_slot
from repro.kernels.sign_pack import BITS, LANES, block_rows

TERN_SLOTS = 16  # 2-bit ternary slots per 32-bit word
f32 = jnp.float32
i32 = jnp.int32


def _weighted(decode, p_ref, w_ref):
    """sum_k w[k] * decode(p[k]) over the gathered workers, in index order."""
    acc = None
    for k in range(p_ref.shape[0]):
        term = decode(p_ref[k]) * w_ref[k:k + 1, :]
        acc = term if acc is None else acc + term
    return acc


def _vote_kernel(p_ref, w_ref, o_ref):
    # p (W, R, 128) int32 bitmaps, w (W, 128) f32 -> o (BITS*R, 128) f32
    # vote sums: sum_w w[w] * (2*bit - 1)
    r = p_ref.shape[1]
    for j in range(BITS):
        o_ref[j * r:(j + 1) * r, :] = _weighted(
            lambda p: ((p >> j) & 1).astype(f32) * 2.0 - 1.0, p_ref, w_ref)


def _acc_call(kernel, packed, weights, slots, interpret, name, *scalars):
    n_w, word_rows, _ = packed.shape
    r = block_rows(word_rows)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((slots * word_rows, LANES), f32),
        grid=(word_rows // r,),
        in_specs=[
            pl.BlockSpec((n_w, r, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((n_w, LANES), lambda i: (0, 0)),
        ] + [pl.BlockSpec((1, 1), lambda i: (0, 0)) for _ in scalars],
        out_specs=pl.BlockSpec((slots * r, LANES), lambda i: (i, 0)),
        name=name,
        interpret=interpret,
    )(packed, weights, *scalars)


def sign_vote_3d(packed: jax.Array, weights: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """packed (W, word_rows, 128) int32, weights (W, 128) f32 ->
    (BITS*word_rows, 128) f32 weighted vote sums."""
    return _acc_call(_vote_kernel, packed, weights, BITS, interpret,
                     "sign_vote")


def _tern_pack_kernel(t_ref, o_ref):
    # t (16R, 128) int8 in {-1, 0, +1} -> (R, 128) int32, 2 bits/slot:
    # 0 = zero, 1 = +1, 3 = -1 (bit0 = nonzero, bit1 = negative)
    r = o_ref.shape[0]
    t = t_ref[...].astype(i32)
    acc = jnp.zeros(o_ref.shape, i32)
    for j in range(TERN_SLOTS):
        tj = t[j * r:(j + 1) * r, :]
        code = (tj != 0).astype(i32) | ((tj < 0).astype(i32) << 1)
        acc = acc | (code << (2 * j))
    o_ref[...] = acc


def tern_pack_2d(t2: jax.Array, *, interpret: bool = False) -> jax.Array:
    """t2 (16*word_rows, 128) int8 -> (word_rows, 128) int32 (2-bit codes)."""
    word_rows = t2.shape[0] // TERN_SLOTS
    r = block_rows(word_rows)
    return pl.pallas_call(
        _tern_pack_kernel,
        out_shape=jax.ShapeDtypeStruct((word_rows, LANES), i32),
        grid=(word_rows // r,),
        in_specs=[pl.BlockSpec((TERN_SLOTS * r, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((r, LANES), lambda i: (i, 0)),
        name="tern_pack",
        interpret=interpret,
    )(t2)


def _tern_acc_kernel(p_ref, w_ref, o_ref):
    # p (W, R, 128) int32 2-bit codes, w (W, 128) f32 -> (16R, 128) f32
    r = p_ref.shape[1]

    def decode(j):
        def f(p):
            slot = (p >> (2 * j)) & 3
            return (slot == 1).astype(f32) - (slot == 3).astype(f32)
        return f

    for j in range(TERN_SLOTS):
        o_ref[j * r:(j + 1) * r, :] = _weighted(decode(j), p_ref, w_ref)


def tern_acc_3d(packed: jax.Array, weights: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """packed (W, word_rows, 128) int32, weights (W, 128) f32 ->
    (16*word_rows, 128) f32 = sum_w weights[w] * decode(packed[w])."""
    return _acc_call(_tern_acc_kernel, packed, weights, TERN_SLOTS,
                     interpret, "tern_acc")


def _int8_acc_kernel(p_ref, w_ref, d_ref, o_ref):
    # p (W, R, 128) int32 words of 4 int8 codes, w (W, 128) f32, d (1, 1) f32
    # -> (4R, 128) f32 widening sum over d
    r = p_ref.shape[1]
    for j in range(CODES):
        o_ref[j * r:(j + 1) * r, :] = _weighted(
            lambda p: code_slot(p, j).astype(f32), p_ref, w_ref) / d_ref[0, 0]


def int8_acc_3d(packed: jax.Array, weights: jax.Array, denom: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """packed (W, word_rows, 128) int32 code words of ``repro.kernels.qsgd``
    (word_rows a multiple of its WORD_ROWS, so a grid step covers whole
    quantize blocks), weights (W, 128) f32, denom (1, 1) f32 ->
    (4*word_rows, 128) f32 = (sum_w weights[w] * codes[w]) / denom, the
    sum f32-widening and the division one IEEE divide after it."""
    return _acc_call(_int8_acc_kernel, packed, weights, CODES, interpret,
                     "int8_weighted_sum", denom)
