"""TernGrad ternarization Pallas kernel (fused bernoulli + sign)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256
LANES = 128
f32 = jnp.float32


def _tern_kernel(x_ref, u_ref, inv_smax_ref, o_ref):
    x = x_ref[...].astype(f32)
    p = jnp.abs(x) * inv_smax_ref[0, 0]
    b = (u_ref[...] < p).astype(f32)
    o_ref[...] = (jnp.sign(x) * b).astype(jnp.int8)


def terngrad_2d(x2: jax.Array, u2: jax.Array, inv_smax: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    rows = x2.shape[0]
    blk = lambda: pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _tern_kernel,
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.int8),
        grid=(rows // BLOCK_ROWS,),
        in_specs=[blk(), blk(), pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=blk(),
        name="terngrad_quantize",
        interpret=interpret,
    )(x2, u2, inv_smax)
