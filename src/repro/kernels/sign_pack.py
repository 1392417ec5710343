"""Sign bit-packing kernels: f32 -> 1 bit/element wire format.

SignSGD's paper-claimed 32x reduction needs true bit packing — an int8 sign
payload is only 4x.  The packed 32-bit word bitmap is what goes through the
all-gather; majority voting unpacks and sums.  Packing/unpacking are pure
VPU bit ops on 32-bit lanes, fused here into single passes.

Layout (shared with ``wire_reduce``): the flat vector is padded to whole
tiles and viewed as ``(rows, 128)``.  A grid step covers ``BITS * R`` element
rows and ``R`` word rows; element row ``r`` of the step is bit ``r // R`` of
word row ``r % R``.  ``R`` (``block_rows``) only depends on the word-row
count, so pack and unpack agree on it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BITS = 32  # sign bits per 32-bit word
WORD_ROWS = 8  # the (8, 128) native tile of 32-bit words
LANES = 128
f32 = jnp.float32
i32 = jnp.int32


def tiles_per_step(n_tiles: int) -> int:
    """Native tiles per grid step: the largest of 8/4/2/1 dividing
    ``n_tiles``, so no padding beyond one tile is ever needed."""
    return next(t for t in (8, 4, 2, 1) if n_tiles % t == 0)


def block_rows(word_rows: int) -> int:
    """Word rows per grid step (``word_rows`` is a multiple of WORD_ROWS)."""
    return WORD_ROWS * tiles_per_step(word_rows // WORD_ROWS)


def _pack_kernel(x_ref, o_ref):
    r = o_ref.shape[0]
    acc = jnp.zeros(o_ref.shape, i32)
    for j in range(BITS):
        acc = acc | ((x_ref[j * r:(j + 1) * r, :] >= 0).astype(i32) << j)
    o_ref[...] = acc


def sign_pack_2d(x2: jax.Array, *, interpret: bool = False) -> jax.Array:
    """x2: (BITS * word_rows, 128) f32 -> (word_rows, 128) int32 bitmap."""
    word_rows = x2.shape[0] // BITS
    r = block_rows(word_rows)
    return pl.pallas_call(
        _pack_kernel,
        out_shape=jax.ShapeDtypeStruct((word_rows, LANES), i32),
        grid=(word_rows // r,),
        in_specs=[pl.BlockSpec((BITS * r, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((r, LANES), lambda i: (i, 0)),
        name="sign_pack",
        interpret=interpret,
    )(x2)


def _unpack_kernel(p_ref, o_ref):
    r = p_ref.shape[0]
    p = p_ref[...]
    for j in range(BITS):
        o_ref[j * r:(j + 1) * r, :] = ((p >> j) & 1).astype(f32) * 2.0 - 1.0


def sign_unpack_2d(packed: jax.Array, *, interpret: bool = False) -> jax.Array:
    """(word_rows, 128) int32 -> (BITS * word_rows, 128) f32 of {-1, +1}."""
    word_rows = packed.shape[0]
    r = block_rows(word_rows)
    return pl.pallas_call(
        _unpack_kernel,
        out_shape=jax.ShapeDtypeStruct((BITS * word_rows, LANES), f32),
        grid=(word_rows // r,),
        in_specs=[pl.BlockSpec((r, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BITS * r, LANES), lambda i: (i, 0)),
        name="sign_unpack",
        interpret=interpret,
    )(packed)
