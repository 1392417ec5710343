"""Threshold sparsification kernel (Strom [133] / adaptive [142]):
fused |x|>=tau mask + per-block kept-count in one pass.  The counts feed the
adaptive-threshold controller and the analytic wire-bits accounting.

Each grid step writes its per-lane kept counts into one native (8, 128)
int32 tile (the same row repeated), so the counts output is tile-aligned."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256
LANES = 128
COUNT_ROWS = 8
f32 = jnp.float32


def _thresh_kernel(x_ref, tau_ref, vals_ref, cnt_ref):
    x = x_ref[...].astype(f32)
    keep = jnp.abs(x) >= tau_ref[0, 0]
    vals_ref[...] = jnp.where(keep, x, 0.0)
    lane_counts = jnp.sum(keep.astype(jnp.int32), axis=0, keepdims=True)
    cnt_ref[...] = jnp.broadcast_to(lane_counts, cnt_ref.shape)


def threshold_2d(x2: jax.Array, tau: jax.Array, *, interpret: bool = False):
    """x2 (rows,128); tau (1,1). Returns (masked (rows,128), per-lane kept
    counts (nblk*8, 128) — rows 8i..8i+7 of block i all hold its counts)."""
    rows = x2.shape[0]
    nblk = rows // BLOCK_ROWS
    return pl.pallas_call(
        _thresh_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x2.shape, f32),
            jax.ShapeDtypeStruct((nblk * COUNT_ROWS, LANES), jnp.int32),
        ),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((COUNT_ROWS, LANES), lambda i: (i, 0)),
        ),
        name="threshold_sparsify",
        interpret=interpret,
    )(x2, tau)
