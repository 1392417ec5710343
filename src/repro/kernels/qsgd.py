"""QSGD stochastic-quantization Pallas TPU kernel.

The compression operators are the paper's compute hot-spot on the gradient
path: one full pass over a gradient-sized tensor per step, strictly
HBM-bandwidth-bound.  The kernel fuses abs/scale/dither/sign into a single
VMEM-tiled pass (the pure-jnp version materializes 3 intermediates).

Layout: the flat gradient is padded and reshaped to (rows, 128) lanes;
blocks of (BLOCK_ROWS, 128) stream through VMEM.  The int8 codes leave the
kernel as 32-bit wire words, four to a word, in the tile layout of
:mod:`repro.kernels.sign_pack` with 8-bit slots: a block writes
``WORD_ROWS`` word rows, and byte j of word row w holds code row
``j * WORD_ROWS + w`` of the block.  The words go over the wire as they are
and the widening sum (``repro.kernels.wire_reduce``) reads them there, so
no pass repacks the codes on either side of the collective.

The tensor norm AND the quantization level count are prescalars
(SMEM-style (1,1) blocks) computed / supplied by the wrapper — ``levels``
is a *traced* value, not a kernel specialization constant, so sweep cells
that differ only in levels share one compiled program (mask-style, like the
top-k rank mask).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256  # (256, 128) f32 = 128 KiB in, 32 KiB out — well under VMEM
LANES = 128
CODES = 4  # int8 codes per 32-bit wire word
WORD_ROWS = BLOCK_ROWS // CODES  # word rows a block writes

f32 = jnp.float32
i32 = jnp.int32


def pack_codes(code: jax.Array) -> jax.Array:
    """(CODES * R, 128) f32 integer-valued codes -> (R, 128) int32 words:
    byte j of word row w is code row j*R + w as a two's-complement int8,
    saturated to [-128, 127] as an int8 cast saturates."""
    r = code.shape[0] // CODES
    c = jnp.clip(code, -128.0, 127.0).astype(i32) & 0xFF
    acc = c[:r]
    for j in range(1, CODES):
        acc = acc | (c[j * r:(j + 1) * r] << (8 * j))
    return acc


def code_slot(words: jax.Array, j: int) -> jax.Array:
    """Byte j of ``pack_codes``'s int32 words, sign-extended: shifted to the
    top of the word and arithmetically back."""
    return (words << (24 - 8 * j)) >> 24


def _qsgd_kernel(x_ref, u_ref, inv_norm_ref, levels_ref, o_ref):
    x = x_ref[...].astype(f32)
    y = jnp.abs(x) * inv_norm_ref[0, 0] * levels_ref[0, 0]
    l = jnp.floor(y)
    l = l + (u_ref[...] < (y - l)).astype(f32)
    o_ref[...] = pack_codes(jnp.sign(x) * l)


def qsgd_2d(x2: jax.Array, u2: jax.Array, inv_norm: jax.Array,
            levels: jax.Array, *, interpret: bool = False) -> jax.Array:
    """x2, u2: (rows, 128) with rows % BLOCK_ROWS == 0; inv_norm and levels
    (1,1) f32 traced scalars -> (rows / CODES, 128) int32 code words."""
    rows = x2.shape[0]
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        _qsgd_kernel,
        out_shape=jax.ShapeDtypeStruct((rows // CODES, LANES), i32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((WORD_ROWS, LANES), lambda i: (i, 0)),
        name="qsgd_quantize",
        interpret=interpret,
    )(x2, u2, inv_norm, levels)
