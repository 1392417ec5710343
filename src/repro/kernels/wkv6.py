"""WKV6 recurrence Pallas kernel — chunked, VMEM-resident state.

The RWKV6 time-mix is the architecture's compute hot-spot.  The GPU
reference is a CUDA kernel with one thread per channel; the TPU-native
formulation instead keeps the per-head state S (hd x hd, f32) in VMEM
scratch and streams time chunks of r/k/v/w through VMEM, iterating the
in-chunk recurrence with vector ops (VPU outer products + matvecs).  Grid:
(B*H heads, S/chunk) with the time dimension sequential ("arbitrary"
semantics) so scratch carries S across chunks.

Within-chunk the recurrence is sequential; a blocked-parallel form (chunked
prefix products like FLA) is a further optimization — see EXPERIMENTS.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
CHUNK = 64


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref, S):
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _init():
        S[...] = s0_ref[0].astype(f32)

    u = u_ref[0, 0, :].astype(f32)  # (hd,)

    def step(t, _):
        r_t = r_ref[0, t, :].astype(f32)  # (hd,)
        k_t = k_ref[0, t, :].astype(f32)
        v_t = v_ref[0, t, :].astype(f32)
        w_t = w_ref[0, t, :].astype(f32)
        kv = k_t[:, None] * v_t[None, :]  # (hd, hd)
        y = (r_t[None, :] @ (S[...] + u[:, None] * kv))[0]  # (hd,)
        y_ref[0, t, :] = y
        S[...] = w_t[:, None] * S[...] + kv
        return 0

    jax.lax.fori_loop(0, r_ref.shape[1], step, 0)
    sout_ref[0] = S[...]


def wkv6_chunked(
    r: jax.Array,  # (BH, S, hd) f32
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,  # (BH, 1, hd) (head bonus broadcast per batch)
    s0: jax.Array,  # (BH, hd, hd)
    *,
    chunk: int = CHUNK,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    BH, S, hd = r.shape
    assert S % chunk == 0, (S, chunk)
    grid = (BH, S // chunk)
    seq_spec = pl.BlockSpec((1, chunk, hd), lambda i, j: (i, j, 0))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
    y, s_out = pl.pallas_call(
        _wkv_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((BH, S, hd), f32),
            jax.ShapeDtypeStruct((BH, hd, hd), f32),
        ),
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, 1, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(
            seq_spec,
            pl.BlockSpec((1, hd, hd), lambda i, j: (i, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((hd, hd), f32)],
        name="wkv6",
        interpret=interpret,
        **kwargs,
    )(r, k, v, w, u, s0)
    return y, s_out
