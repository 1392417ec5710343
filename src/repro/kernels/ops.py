"""jit'd wrappers around the Pallas kernels: flat-vector API, padding and
(rows, 128)-lane reshaping, backend dispatch (compiled on a TPU, interpreted
on the CPU so the same code validates in the tests)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import qsgd as _qsgd
from repro.kernels import qsgd_ef as _qsgd_ef
from repro.kernels import sign_pack as _sign
from repro.kernels import terngrad as _tern
from repro.kernels import threshold_sparsify as _thr
from repro.kernels import wire_reduce as _wire_k
from repro.kernels import wkv6 as _wkv

f32 = jnp.float32
_TILE = _qsgd.BLOCK_ROWS * _qsgd.LANES  # elements per full block


def _interpret() -> bool:
    """Compiled on a TPU, interpreted on the CPU; any other backend is an
    error rather than a silent interpreter run."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels run on a TPU (or interpreted on "
                           f"the CPU); the default backend is {backend!r}")
    return backend == "cpu"


def _to2d(x: jax.Array) -> tuple[jax.Array, int]:
    n = x.size
    pad = (-n) % _TILE
    xp = jnp.pad(x.reshape(-1), (0, pad))
    return xp.reshape(-1, _qsgd.LANES), n


@jax.jit
def qsgd_quantize(x: jax.Array, u: jax.Array, *, levels=16) -> tuple[jax.Array, jax.Array]:
    """Flat x, uniform noise u -> (codes int8 (n,), norm (1,) f32).

    ``levels`` is TRACED (a value, not a jit specialization constant): cells
    that differ only in levels share this compiled program."""
    norm = jnp.maximum(jnp.linalg.norm(x.astype(f32)), 1e-30)
    x2, n = _to2d(x.astype(f32))
    u2, _ = _to2d(u.astype(f32))
    codes = _qsgd.qsgd_2d(x2, u2, (1.0 / norm).reshape(1, 1),
                          jnp.asarray(levels, f32).reshape(1, 1),
                          interpret=_interpret())
    return codes.reshape(-1)[:n], norm[None]


@jax.jit
def qsgd_dequantize(codes: jax.Array, norm: jax.Array, *, levels=16) -> jax.Array:
    """Inverse of qsgd_quantize / the codes half of qsgd_ef_fused."""
    return codes.astype(f32) / jnp.asarray(levels, f32) * norm[0]


@jax.jit
def qsgd_ef_fused(g: jax.Array, e: jax.Array, u: jax.Array, *, levels=16,
                  decay=1.0):
    """Fused EF+quantize: returns (codes (n,) int8, norm (1,), e_new (n,)).
    ``levels`` and ``decay`` are traced scalars."""
    decay = jnp.asarray(decay, f32)
    a_norm = jnp.maximum(jnp.linalg.norm((e * decay + g).astype(f32)), 1e-30)
    g2, n = _to2d(g.astype(f32))
    e2, _ = _to2d(e.astype(f32))
    u2, _ = _to2d(u.astype(f32))
    codes, enew = _qsgd_ef.qsgd_ef_2d(
        g2, e2, u2, (1.0 / a_norm).reshape(1, 1),
        jnp.asarray(levels, f32).reshape(1, 1), decay.reshape(1, 1),
        interpret=_interpret(),
    )
    return codes.reshape(-1)[:n], a_norm[None], enew.reshape(-1)[:n]


@jax.jit
def terngrad_quantize(x: jax.Array, u: jax.Array) -> tuple[jax.Array, jax.Array]:
    smax = jnp.maximum(jnp.max(jnp.abs(x.astype(f32))), 1e-30)
    x2, n = _to2d(x.astype(f32))
    u2, _ = _to2d(u.astype(f32))
    tern = _tern.terngrad_2d(x2, u2, (1.0 / smax).reshape(1, 1), interpret=_interpret())
    return tern.reshape(-1)[:n], smax[None]


def _words(packed: jax.Array) -> jax.Array:
    """uint32 wire words -> the int32 view the kernels compute on."""
    return jax.lax.bitcast_convert_type(packed, jnp.int32)


def _wire(words: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(-1)


@jax.jit
def sign_pack(x: jax.Array) -> jax.Array:
    """Flat f32 (n,) -> uint32 bitmap (32 signs per word) in the tile layout
    of :mod:`repro.kernels.sign_pack`.  Returns the full padded word array —
    unpack with ``sign_unpack(packed, n)``; pad overhead is < one tile."""
    n = x.size
    tile = _sign.BITS * _sign.WORD_ROWS * _sign.LANES
    xp = jnp.pad(x.reshape(-1), (0, (-n) % tile), constant_values=1.0)
    words = _sign.sign_pack_2d(xp.reshape(-1, _sign.LANES),
                               interpret=_interpret())
    return _wire(words)


@functools.partial(jax.jit, static_argnames=("n",))
def sign_unpack(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of sign_pack (same tile layout)."""
    x2 = _sign.sign_unpack_2d(_words(packed).reshape(-1, _sign.LANES),
                              interpret=_interpret())
    return x2.reshape(-1)[:n]


def _worker_weights(weights: jax.Array, n_w: int) -> jax.Array:
    """(W,) f32 per-worker weights -> (W, 128) lane-broadcast kernel input."""
    return jnp.broadcast_to(weights.astype(f32).reshape(n_w, 1),
                            (n_w, _wire_k.LANES))


@functools.partial(jax.jit, static_argnames=("n",))
def sign_vote(packed: jax.Array, weights: jax.Array, *, n: int) -> jax.Array:
    """Gathered packed bitmaps (W, words) + per-worker vote weights (W,) ->
    weighted vote sums (n,) f32: sum_w weights[w]*(2*bit-1), decoded and
    accumulated in ONE Pallas pass (the packed payload never expands to a
    per-worker dense decode in HBM).  sign_pack's +1 pad bits only affect
    the sliced-off tail."""
    n_w = packed.shape[0]
    p3 = _words(packed).reshape(n_w, -1, _wire_k.LANES)
    votes = _wire_k.sign_vote_3d(p3, _worker_weights(weights, n_w),
                                 interpret=_interpret())
    return votes.reshape(-1)[:n]


@jax.jit
def tern_pack(tern: jax.Array) -> jax.Array:
    """int8 {-1,0,+1} (n,) -> uint32 wire words, 16 two-bit slots per word
    (returns the full padded word array; zero pad slots decode to 0 so
    accumulation is unaffected).  Layout matches ``tern_acc``."""
    n = tern.size
    tile = _wire_k.TERN_SLOTS * _sign.WORD_ROWS * _wire_k.LANES
    t2 = jnp.pad(tern.reshape(-1), (0, (-n) % tile)).reshape(-1, _wire_k.LANES)
    return _wire(_wire_k.tern_pack_2d(t2, interpret=_interpret()))


@functools.partial(jax.jit, static_argnames=("n",))
def tern_acc(packed: jax.Array, weights: jax.Array, *, n: int) -> jax.Array:
    """Gathered 2-bit payloads (W, words) + per-worker weights (W,) (e.g.
    ternary scale x churn mask) -> sum_w weights[w]*tern_w as (n,) f32,
    decode fused with the accumulate."""
    n_w = packed.shape[0]
    p3 = _words(packed).reshape(n_w, -1, _wire_k.LANES)
    out = _wire_k.tern_acc_3d(p3, _worker_weights(weights, n_w),
                              interpret=_interpret())
    return out.reshape(-1)[:n]


@jax.jit
def int8_weighted_sum(codes: jax.Array, weights: jax.Array) -> jax.Array:
    """Gathered int8 quantizer codes (W, n) + per-worker decode weights (W,)
    (norm_w/levels x churn mask) -> sum_w weights[w]*codes[w] as (n,) f32.
    The widening accumulate happens inside the kernel — the (W, n) f32
    decode is never materialized."""
    n_w, n = codes.shape
    tile = _wire_k.INT8_ROWS * _wire_k.LANES
    c3 = jnp.pad(codes, ((0, 0), (0, (-n) % tile))).reshape(n_w, -1,
                                                           _wire_k.LANES)
    out = _wire_k.int8_acc_3d(c3, _worker_weights(weights, n_w),
                              interpret=_interpret())
    return out.reshape(-1)[:n]


@jax.jit
def threshold_sparsify(x: jax.Array, tau: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Returns (masked (n,), nnz scalar int32)."""
    x2, n = _to2d(x.astype(f32))
    vals, cnts = _thr.threshold_2d(x2, jnp.asarray(tau, f32).reshape(1, 1),
                                   interpret=_interpret())
    # padded tail contributes zeros (|0| >= tau only if tau<=0; guard)
    masked = vals.reshape(-1)[:n]
    nnz = jnp.sum(jnp.abs(masked) > 0).astype(jnp.int32)
    return masked, nnz


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array, u: jax.Array,
         s0: jax.Array, *, chunk: int = 64):
    """(B,S,H,hd) inputs, u (H,hd), s0 (B,H,hd,hd) -> (y (B,S,H,hd), sT)."""
    B, S, H, hd = r.shape
    pad = (-S) % chunk

    def prep(t):
        tp = jnp.pad(t.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        return jnp.moveaxis(tp, 2, 1).reshape(B * H, S + pad, hd)

    rr, kk, vv = prep(r), prep(k), prep(v)
    # pad decay with 1.0 (identity for state)
    wp = jnp.pad(w.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
    ww = jnp.moveaxis(wp, 2, 1).reshape(B * H, S + pad, hd)
    uu = jnp.broadcast_to(u.astype(f32)[None], (B, H, hd)).reshape(B * H, 1, hd)
    ss = s0.astype(f32).reshape(B * H, hd, hd)
    y, sT = _wkv.wkv6_chunked(rr, kk, vv, ww, uu, ss, chunk=chunk,
                              interpret=_interpret())
    y = jnp.moveaxis(y.reshape(B, H, S + pad, hd), 1, 2)[:, :S]
    return y, sT.reshape(B, H, hd, hd)
