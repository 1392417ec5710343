"""QSGD stochastic-quantization Pallas TPU kernel.

The compression operators are the paper's compute hot-spot on the gradient
path: one full pass over a gradient-sized tensor per step, strictly
HBM-bandwidth-bound.  The kernel fuses abs/scale/dither/sign into a single
VMEM-tiled pass (the pure-jnp version materializes 3 intermediates).

Layout: the flat gradient is padded and reshaped to (rows, 128) lanes;
blocks of (BLOCK_ROWS, 128) stream through VMEM.  The tensor norm AND the
quantization level count are prescalars (SMEM-style (1,1) blocks) computed /
supplied by the wrapper — ``levels`` is a *traced* value, not a kernel
specialization constant, so sweep cells that differ only in levels share one
compiled program (mask-style, like the top-k rank mask).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256  # (256, 128) f32 = 128 KiB in, 32 KiB out — well under VMEM
LANES = 128

f32 = jnp.float32


def _qsgd_kernel(x_ref, u_ref, inv_norm_ref, levels_ref, o_ref):
    x = x_ref[...].astype(f32)
    y = jnp.abs(x) * inv_norm_ref[0, 0] * levels_ref[0, 0]
    l = jnp.floor(y)
    l = l + (u_ref[...] < (y - l)).astype(f32)
    o_ref[...] = (jnp.sign(x) * l).astype(jnp.int8)


def qsgd_2d(x2: jax.Array, u2: jax.Array, inv_norm: jax.Array,
            levels: jax.Array, *, interpret: bool = False) -> jax.Array:
    """x2, u2: (rows, 128) with rows % BLOCK_ROWS == 0; inv_norm and levels
    (1,1) f32 traced scalars."""
    rows = x2.shape[0]
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        _qsgd_kernel,
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.int8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        name="qsgd_quantize",
        interpret=interpret,
    )(x2, u2, inv_norm, levels)
