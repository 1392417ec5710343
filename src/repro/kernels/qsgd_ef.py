"""Fused error-feedback + QSGD quantization kernel.

Unfused, the §IX-A pipeline is three bandwidth-bound passes over
gradient-sized tensors:
    a = e + g            (read e, g; write a)
    code = Q(a)          (read a; write code)
    e'   = a - deQ(code) (read a, code; write e')
= 5 reads + 3 writes of N floats.  Fused: read g, e, u; write code (1 byte)
and e' — 3 reads + 1.25 writes.  ~2.4x less HBM traffic on the dominant
non-matmul pass of a compressed training step (see EXPERIMENTS.md §Perf).
The codes leave as the 32-bit wire words of :mod:`repro.kernels.qsgd`, so
the wire takes either producer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.qsgd import BLOCK_ROWS, CODES, LANES, WORD_ROWS, pack_codes

f32 = jnp.float32


def _qsgd_ef_kernel(g_ref, e_ref, u_ref, inv_norm_ref, levels_ref, decay_ref,
                    code_ref, enew_ref):
    levels = levels_ref[0, 0]
    a = e_ref[...].astype(f32) * decay_ref[0, 0] + g_ref[...].astype(f32)
    inv = inv_norm_ref[0, 0]
    y = jnp.abs(a) * inv * levels
    l = jnp.floor(y)
    l = l + (u_ref[...] < (y - l)).astype(f32)
    code = jnp.sign(a) * l
    code_ref[...] = pack_codes(code)
    deq = code / levels / jnp.maximum(inv, 1e-38)
    enew_ref[...] = a - deq


def qsgd_ef_2d(g2, e2, u2, inv_norm, levels, decay, *, interpret: bool = False):
    """``levels`` and ``decay`` are (1,1) f32 traced scalars — the kernel no
    longer specializes on them, so knob-varied cells share one program.
    Returns ((rows / CODES, 128) int32 code words, (rows, 128) f32 e')."""
    rows = g2.shape[0]
    grid = (rows // BLOCK_ROWS,)
    blk = lambda: pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    scalar = lambda: pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        _qsgd_ef_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((rows // CODES, LANES), jnp.int32),
            jax.ShapeDtypeStruct(g2.shape, f32),
        ),
        grid=grid,
        in_specs=[blk(), blk(), blk(), scalar(), scalar(), scalar()],
        out_specs=(pl.BlockSpec((WORD_ROWS, LANES), lambda i: (i, 0)), blk()),
        name="qsgd_ef_fused",
        interpret=interpret,
    )(g2, e2, u2, inv_norm, levels, decay)
