"""Fused causal attention Pallas TPU kernels: forward, dQ and dK/dV.

The attention core of the training and prefill step (``sdpa_chunked``) is a
score matrix per head: written out in f32 it is the largest tensor of the
step, and its residuals and the softmax backward move it through HBM several
times.  These kernels keep every score tile in VMEM (flash attention):

- forward: online softmax over KV blocks; only the output and the row
  log-sum-exp leave the kernel, and the backward recomputes the scores;
- dQ: per query block, a sweep over the KV blocks it sees;
- dK/dV: per KV block, a sweep over the query heads that share it (GQA) and
  the query blocks that see it, so K/V stay at their KV heads.

Scores are q.k times ``scale``, hd^-0.5 unless given (a q/k head dim
zero-padded to the lanes keeps the scale of its unpadded dim).

The mask is causal plus an optional sliding window on ``q_pos = offset +
arange(Sq)`` and ``k_pos = arange(Sk)``: key k is seen by query q iff
``0 <= q - k < window``.  Blocks that lie wholly outside it are skipped: the
sweep only visits the blocks in range, and a block index map clamped to that
range fetches nothing for a skipped step.  The offset is a scalar-prefetch
operand, so a traced offset (a sequence shard's) costs no recompile.

Precision: q, k, v enter the MXU as the bf16 values they are stored in, with
f32 accumulation; scores, the running max and sum, the log-sum-exp and every
accumulator are f32; the probabilities and dS enter the MXU in one bf16 pass,
as the f32 einsums of the unfused path do on a TPU v5e (PERF.md, Findings).

Layout: (B, S, H, hd) arrays are viewed as (B, S, H*hd) with no copy; a head
is a (block, hd) tile of the last axis, so hd must be a multiple of 128.
Row statistics travel as (B, H, 1, Sq) f32, lane-dense.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
LANES = 128
MASK = -0.7 * float(jnp.finfo(f32).max)  # a score no key keeps
# chosen on a TPU v5e chip at GLM-4-9B's and Qwen3-0.6B's shapes (PERF.md, Findings)
BLOCK_Q = 1024
BLOCK_K = 1024
VMEM_LIMIT = 64 * 1024 * 1024
NT = (((1,), (1,)), ((), ()))  # contract the last dims: a @ b.T
NN = (((1,), (0,)), ((), ()))


def block_sizes(sq: int, sk: int) -> tuple[int, int] | None:
    """(block_q, block_k) for these sequence lengths: the largest power-of-two
    halving of BLOCK_Q / BLOCK_K, not below 128, that divides each; None
    where none does."""
    def pick(n, b):
        while b >= LANES and n % b:
            b //= 2
        return b if b >= LANES else None

    bq, bk = pick(sq, BLOCK_Q), pick(sk, BLOCK_K)
    return (bq, bk) if bq and bk else None


def _span(lo, steps: int, n: int):
    """The first block of a sweep of ``steps`` blocks of n that starts at lo."""
    return jnp.clip(lo, 0, n - steps)


def _clamp(x, lo, hi, n: int):
    """Block index of a sweep step: x held inside [lo, hi] (so a skipped step
    re-uses the block already fetched) and inside the array."""
    return jnp.clip(jnp.minimum(jnp.maximum(x, lo), hi), 0, n - 1)


def _kv_range(off, i, *, bq, bk, window, nk):
    """KV blocks that query block i sees: [lo, hi]."""
    qlo = off + i * bq
    lo = jnp.maximum(qlo - window + 1, 0) // bk
    hi = jnp.minimum((qlo + bq - 1) // bk, nk - 1)
    return lo, hi


def _q_range(off, j, *, bq, bk, window, nq):
    """Query blocks that see KV block j: [lo, hi]."""
    klo = j * bk
    lo = jnp.maximum(klo - off, 0) // bq
    hi = jnp.minimum((klo + bk - 1 + window - 1 - off) // bq, nq - 1)
    return lo, hi


def _steps(block: int, other: int, window: int, n: int) -> int:
    """Sweep length: blocks of size ``other`` that a block of size ``block``
    can see through a window (all n without one)."""
    return min(n, -(-(block + window - 1) // other) + 1)


def _visible(qlo, klo, bq, bk, window):
    """(some key of the tile is seen, every key of the tile is seen)."""
    d_min = qlo - (klo + bk - 1)
    d_max = qlo + bq - 1 - klo
    return (d_max >= 0) & (d_min <= window - 1), (d_min >= 0) & (d_max <= window - 1)


def _masked(s, qlo, klo, window, *, transposed=False):
    """Scores outside the causal window set to MASK; s is (q, k), or (k, q)
    when transposed."""
    rows = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    diff = (qlo + cols) - (klo + rows) if transposed else (qlo + rows) - (klo + cols)
    return jnp.where((diff >= 0) & (diff < window), s, MASK)


def _run(seen, full, body):
    """Run ``body(mask)`` on a seen tile, masking only where it is partial."""
    pl.when(seen & full)(lambda: body(False))
    pl.when(seen & jnp.logical_not(full))(lambda: body(True))


def _params(interpret: bool, n_parallel: int, n_arbitrary: int) -> dict:
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel + ("arbitrary",) * n_arbitrary,
        vmem_limit_bytes=VMEM_LIMIT)}


# ------------------------------------------------------------------ forward


def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, window, bq, bk, nk, steps):
    i, j = pl.program_id(2), pl.program_id(3)
    off = off_ref[0]
    lo, _ = _kv_range(off, i, bq=bq, bk=bk, window=window, nk=nk)
    jj = _span(lo, steps, nk) + j
    qlo, klo = off + i * bq, jj * bk

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, MASK, f32)
        l_sc[...] = jnp.zeros(l_sc.shape, f32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, f32)

    def body(mask):
        s = lax.dot_general(q_ref[...], k_ref[...], NT, preferred_element_type=f32) * scale
        if mask:
            s = _masked(s, qlo, klo, window)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[...]
        acc_sc[...] = acc_sc[...] * alpha[:, :1] + lax.dot_general(
            p.astype(v.dtype), v, NN, preferred_element_type=f32)
        m_sc[...] = m_new

    seen, full = _visible(qlo, klo, bq, bk, window)
    _run(seen, full, body)

    @pl.when(j == steps - 1)
    def _out():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = jnp.transpose(m_sc[...] + jnp.log(l))[:1]


def flash_fwd(q, k, v, offset, *, window: int, block_q: int, block_k: int,
              interpret: bool = False, scale: float | None = None):
    """q (B, Sq, H, hd), k (B, Sk, KV, hd), v (B, Sk, KV, hd_v), offset (1,)
    int32 -> (o (B, Sq, H, hd_v) in q's dtype, lse (B, H, 1, Sq) f32)."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, hd_v = v.shape
    group, bq, bk = H // KV, block_q, block_k
    nq, nk = Sq // bq, Sk // bk
    scale = hd**-0.5 if scale is None else scale
    steps = _steps(bq, bk, window, nk)

    def kv_block(b, h, i, j, off_ref):
        lo, hi = _kv_range(off_ref[0], i, bq=bq, bk=bk, window=window, nk=nk)
        return b, _clamp(_span(lo, steps, nk) + j, lo, hi, nk), h // group

    kernel = functools.partial(_fwd_kernel, scale=scale, window=window, bq=bq, bk=bk,
                               nk=nk, steps=steps)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, steps),
            in_specs=[
                pl.BlockSpec((None, bq, hd), lambda b, h, i, j, o: (b, i, h)),
                pl.BlockSpec((None, bk, hd), kv_block),
                pl.BlockSpec((None, bk, hd_v), kv_block),
            ],
            out_specs=[
                pl.BlockSpec((None, bq, hd_v), lambda b, h, i, j, o: (b, i, h)),
                pl.BlockSpec((None, None, 1, bq), lambda b, h, i, j, o: (b, h, 0, i)),
            ],
            scratch_shapes=[pltpu.VMEM((bq, LANES), f32), pltpu.VMEM((bq, LANES), f32),
                            pltpu.VMEM((bq, hd_v), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Sq, H * hd_v), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, Sq), f32)],
        name="flash_attention_fwd",
        interpret=interpret,
        **_params(interpret, 3, 1),
    )(offset, q.reshape(B, Sq, H * hd), k.reshape(B, Sk, KV * hd), v.reshape(B, Sk, KV * hd_v))
    return o.reshape(B, Sq, H, hd_v), lse


# ------------------------------------------------------------------ backward


def _dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_sc, *,
               scale, window, bq, bk, nk, steps):
    i, j = pl.program_id(2), pl.program_id(3)
    off = off_ref[0]
    lo, _ = _kv_range(off, i, bq=bq, bk=bk, window=window, nk=nk)
    jj = _span(lo, steps, nk) + j
    qlo, klo = off + i * bq, jj * bk

    @pl.when(j == 0)
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, f32)

    def body(mask):
        k = k_ref[...]
        s = lax.dot_general(q_ref[...], k, NT, preferred_element_type=f32) * scale
        if mask:
            s = _masked(s, qlo, klo, window)
        p = jnp.exp(s - lse_ref[0][:, None])
        dp = lax.dot_general(do_ref[...], v_ref[...], NT, preferred_element_type=f32)
        ds = p * (dp - di_ref[0][:, None])
        acc_sc[...] += lax.dot_general(ds.astype(k.dtype), k, NN, preferred_element_type=f32)

    seen, full = _visible(qlo, klo, bq, bk, window)
    _run(seen, full, body)

    @pl.when(j == steps - 1)
    def _out():
        dq_ref[...] = (acc_sc[...] * scale).astype(dq_ref.dtype)


def flash_bwd_dq(q, k, v, offset, do, lse, di, *, window: int, block_q: int, block_k: int,
                 interpret: bool = False, scale: float | None = None):
    """dQ (B, Sq, H, hd) from the forward's inputs, the output cotangent do
    (B, Sq, H, hd_v), the forward's lse and di = rowsum(o * do), both
    (B, H, 1, Sq) f32."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, hd_v = v.shape
    group, bq, bk = H // KV, block_q, block_k
    nq, nk = Sq // bq, Sk // bk
    scale = hd**-0.5 if scale is None else scale
    steps = _steps(bq, bk, window, nk)

    def kv_block(b, h, i, j, off_ref):
        lo, hi = _kv_range(off_ref[0], i, bq=bq, bk=bk, window=window, nk=nk)
        return b, _clamp(_span(lo, steps, nk) + j, lo, hi, nk), h // group

    q_block = lambda b, h, i, j, o: (b, i, h)  # noqa: E731
    row = pl.BlockSpec((None, None, 1, bq), lambda b, h, i, j, o: (b, h, 0, i))
    kernel = functools.partial(_dq_kernel, scale=scale, window=window, bq=bq, bk=bk,
                               nk=nk, steps=steps)
    dq = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, steps),
            in_specs=[
                pl.BlockSpec((None, bq, hd), q_block),
                pl.BlockSpec((None, bk, hd), kv_block),
                pl.BlockSpec((None, bk, hd_v), kv_block),
                pl.BlockSpec((None, bq, hd_v), q_block),
                row, row,
            ],
            out_specs=pl.BlockSpec((None, bq, hd), q_block),
            scratch_shapes=[pltpu.VMEM((bq, hd), f32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Sq, H * hd), q.dtype),
        name="flash_attention_dq",
        interpret=interpret,
        **_params(interpret, 3, 1),
    )(offset, q.reshape(B, Sq, H * hd), k.reshape(B, Sk, KV * hd),
      v.reshape(B, Sk, KV * hd_v), do.reshape(B, Sq, H * hd_v), lse, di)
    return dq.reshape(B, Sq, H, hd)


def _dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, window, bq, bk, nq, steps, group):
    j, g, t = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    off = off_ref[0]
    lo, _ = _q_range(off, j, bq=bq, bk=bk, window=window, nq=nq)
    ii = _span(lo, steps, nq) + t
    qlo, klo = off + ii * bq, j * bk

    @pl.when((g == 0) & (t == 0))
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, f32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, f32)

    def body(mask):
        # transposed tiles (keys on sublanes, queries on lanes): the row
        # statistics broadcast as lane-dense rows, every product is a @ b or
        # a @ b.T
        q, do = q_ref[...], do_ref[...]
        s = lax.dot_general(k_ref[...], q, NT, preferred_element_type=f32) * scale
        if mask:
            s = _masked(s, qlo, klo, window, transposed=True)
        p = jnp.exp(s - lse_ref[...])
        dv_sc[...] += lax.dot_general(p.astype(do.dtype), do, NN, preferred_element_type=f32)
        dp = lax.dot_general(v_ref[...], do, NT, preferred_element_type=f32)
        ds = p * (dp - di_ref[...])
        dk_sc[...] += lax.dot_general(ds.astype(q.dtype), q, NN, preferred_element_type=f32)

    seen, full = _visible(qlo, klo, bq, bk, window)
    _run(seen, full, body)

    @pl.when((g == group - 1) & (t == steps - 1))
    def _out():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def flash_bwd_dkv(q, k, v, offset, do, lse, di, *, window: int, block_q: int, block_k: int,
                  interpret: bool = False, scale: float | None = None):
    """(dK (B, Sk, KV, hd), dV (B, Sk, KV, hd_v)), summed over the query heads
    of each KV head; arguments as for :func:`flash_bwd_dq`."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, hd_v = v.shape
    group, bq, bk = H // KV, block_q, block_k
    nq, nk = Sq // bq, Sk // bk
    scale = hd**-0.5 if scale is None else scale
    steps = _steps(bk, bq, window, nq)

    def q_block(b, c, j, g, t, off_ref):
        lo, hi = _q_range(off_ref[0], j, bq=bq, bk=bk, window=window, nq=nq)
        return b, _clamp(_span(lo, steps, nq) + t, lo, hi, nq), c * group + g

    def row(b, c, j, g, t, off_ref):
        b, i, h = q_block(b, c, j, g, t, off_ref)
        return b, h, 0, i

    kv_block = lambda b, c, j, g, t, o: (b, j, c)  # noqa: E731
    kernel = functools.partial(_dkv_kernel, scale=scale, window=window, bq=bq, bk=bk,
                               nq=nq, steps=steps, group=group)
    dk, dv = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KV, nk, group, steps),
            in_specs=[
                pl.BlockSpec((None, bq, hd), q_block),
                pl.BlockSpec((None, bk, hd), kv_block),
                pl.BlockSpec((None, bk, hd_v), kv_block),
                pl.BlockSpec((None, bq, hd_v), q_block),
                pl.BlockSpec((None, None, 1, bq), row),
                pl.BlockSpec((None, None, 1, bq), row),
            ],
            out_specs=[pl.BlockSpec((None, bk, hd), kv_block),
                       pl.BlockSpec((None, bk, hd_v), kv_block)],
            scratch_shapes=[pltpu.VMEM((bk, hd), f32), pltpu.VMEM((bk, hd_v), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Sk, KV * hd), k.dtype),
                   jax.ShapeDtypeStruct((B, Sk, KV * hd_v), v.dtype)],
        name="flash_attention_dkv",
        interpret=interpret,
        **_params(interpret, 3, 2),
    )(offset, q.reshape(B, Sq, H * hd), k.reshape(B, Sk, KV * hd),
      v.reshape(B, Sk, KV * hd_v), do.reshape(B, Sq, H * hd_v), lse, di)
    return dk.reshape(B, Sk, KV, hd), dv.reshape(B, Sk, KV, hd_v)
