"""Transformer building blocks (executed *inside* shard_map, manual axes).

Conventions
-----------
* Every array argument is the *local shard*; weights carry their global
  ``ParamDef.spec`` so shard_map slices them.
* ``ax`` is the :class:`~repro.models.sharding.AxisCtx`; tensor-parallel
  collectives use ``ax.model``.
* Activations ``x`` are (B_local, S, d) with d replicated over the model
  axis.  Attention/FFN use Megatron-style column/row parallelism with an
  explicit ``psum`` (recorded by ``repro.core.comms`` accounting).
* Decode KV caches are sharded along the *sequence* dimension over the model
  axis (context-parallel decode with log-sum-exp combining) because most
  assigned architectures have too few KV heads to shard 16-way.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


from repro.configs.base import ModelConfig
from repro.core.comms import all_gather, all_to_all, pmax, psum
from repro.kernels import flash_attention, ops
from repro.models.sharding import AxisCtx, ParamDef, ShapePlan

f32 = jnp.float32

# ---------------------------------------------------------------------------
# Norms.
# ---------------------------------------------------------------------------


def rmsnorm_def(d: int) -> ParamDef:
    return ParamDef((d,), P(None), init="ones")


def rmsnorm(w: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    h = x.astype(f32)
    h = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), axis=-1, keepdims=True) + eps)
    return (h * w.astype(f32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE family.
# ---------------------------------------------------------------------------


def _rope_cos_sin(pos: jax.Array, dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """pos (...,) -> cos/sin (..., dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=f32) / dim))
    ang = pos.astype(f32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (DeepSeek-V2's ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: ModelConfig, dim: int) -> np.ndarray:
    """YaRN's RoPE frequencies over ``dim`` rotated dims, as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` computes them: the extrapolated
    frequencies theta^(-2i/dim) for the dims that turn more than
    ``yarn_beta_fast`` times over ``rope_original_len`` positions, the
    interpolated ones (divided by ``rope_factor``) for those that turn fewer
    than ``yarn_beta_slow`` times, and a linear ramp between."""
    def correction_dim(rotations):
        return (dim * math.log(cfg.rope_original_len / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(correction_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / cfg.rope_theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0.0, 1.0)
    return (extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(cfg: ModelConfig, qk_dim: int) -> float:
    """qk_dim^-0.5, times YaRN's mscale(factor, mscale_all_dim)^2 where set."""
    scale = qk_dim**-0.5
    if cfg.rope_type == "yarn" and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _rotate(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (..., dim); cos/sin (..., dim//2) broadcastable (rotate-half pairs)."""
    x1, x2 = jnp.split(x.astype(f32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def apply_rope(
    cfg: ModelConfig, x: jax.Array, positions: jax.Array, head_axis: int = 2
) -> jax.Array:
    """Apply the config's RoPE variant.

    x: (B, S, H, hd); positions: (3, B, S) (t/h/w streams; stream 0 is the
    standard sequential position).
    """
    if cfg.rope_type == "none":
        return x
    hd = x.shape[-1]
    if cfg.rope_type == "mrope":
        # M-RoPE [arXiv:2409.12191]: split the rotary half-dims into
        # (t, h, w) sections, each driven by its own position stream.
        secs = cfg.mrope_sections
        assert sum(secs) == hd // 2, (secs, hd)
        cos_parts, sin_parts = [], []
        for stream, sec in enumerate(secs):
            pos = positions[stream]  # (B, S)
            inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, 2 * sec, 2, dtype=f32) / hd))
            ang = pos.astype(f32)[..., None] * inv  # (B, S, sec)
            cos_parts.append(jnp.cos(ang))
            sin_parts.append(jnp.sin(ang))
        cos = jnp.concatenate(cos_parts, -1)[:, :, None, :]  # (B,S,1,hd/2)
        sin = jnp.concatenate(sin_parts, -1)[:, :, None, :]
        return _rotate(x, cos, sin)
    pos = positions[0]  # (B, S)
    if cfg.rope_type == "yarn":
        ang = pos.astype(f32)[..., None] * jnp.asarray(yarn_inv_freq(cfg, hd))
        m = (yarn_mscale(cfg.rope_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim))
        return _rotate(x, (jnp.cos(ang) * m)[:, :, None, :], (jnp.sin(ang) * m)[:, :, None, :])
    if cfg.rope_type == "partial" and cfg.rope_fraction < 1.0:
        rot = int(hd * cfg.rope_fraction)
        rot -= rot % 2
        cos, sin = _rope_cos_sin(pos, rot, cfg.rope_theta)
        x_rot = _rotate(x[..., :rot], cos[:, :, None, :], sin[:, :, None, :])
        return jnp.concatenate([x_rot, x[..., rot:]], axis=-1)
    cos, sin = _rope_cos_sin(pos, hd, cfg.rope_theta)
    return _rotate(x, cos[:, :, None, :], sin[:, :, None, :])


# ---------------------------------------------------------------------------
# Dense (SwiGLU) FFN — Megatron column/row parallel.
# ---------------------------------------------------------------------------


def mlp_defs(d: int, dff: int) -> dict[str, ParamDef]:
    return {
        "wi": ParamDef((d, dff), P(None, "model")),
        "wg": ParamDef((d, dff), P(None, "model")),
        "wo": ParamDef((dff, d), P("model", None)),
    }


def mlp(p: dict[str, jax.Array], x: jax.Array, ax: AxisCtx, *, reduce: bool = True) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    g = jnp.einsum("bsd,df->bsf", x, p["wg"])
    h = jax.nn.silu(g) * h
    out = jnp.einsum("bsf,fd->bsd", h, p["wo"])
    if reduce:
        out = psum(out, ax.model)  # row-parallel reduction
    return out


# ---------------------------------------------------------------------------
# Mixture of Experts — expert-parallel over the model axis.
# ---------------------------------------------------------------------------


def moe_defs(cfg: ModelConfig, plan: ShapePlan) -> dict[str, Any]:
    d, E, dff = plan.d, plan.E, plan.Dff_e
    defs: dict[str, Any] = {
        "router": ParamDef((d, cfg.router_width), P(None, None), init="small"),
        "wi": ParamDef((E, d, dff), P("model", None, None)),
        "wg": ParamDef((E, d, dff), P("model", None, None)),
        "wo": ParamDef((E, dff, d), P("model", None, None)),
    }
    if plan.Dff_shared:
        defs["shared"] = mlp_defs(d, plan.Dff_shared)
    return defs


def balance_loss(cfg: ModelConfig, probs: jax.Array, top_i: jax.Array, batch: int) -> jax.Array:
    """sum_e f_e * P_e, P_e the mean router probability of expert e and f_e
    its count of top-k picks over the tokens, times E: per sequence with
    DeepSeek's 1/k (f_e = E / (k S) count_e) and averaged over the sequences
    where ``seq_aux``, else over the whole batch (Switch: f_e = E count_e / T)."""
    E, k = probs.shape[-1], top_i.shape[-1]
    groups = batch if cfg.seq_aux else 1
    counts = jnp.sum(jax.nn.one_hot(top_i, E, dtype=f32), axis=1).reshape(groups, -1, E)
    f = jnp.mean(counts, axis=1) * (E / k if cfg.seq_aux else E)
    return jnp.mean(jnp.sum(f * jnp.mean(probs.reshape(groups, -1, E), axis=1), axis=-1))


def grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    """x (R, a) whose first rows lie in consecutive groups of ``sizes`` (G,)
    rows, w (G, a, b) -> (R, b): each group's rows times its matrix, zero
    for the rows past the groups.  On a TPU the grouped-matmul kernel
    (``kernels/ops.py``), whose work follows the grouped rows, not R;
    elsewhere ``jax.lax.ragged_dot``.  (On a TPU v5e ``ragged_dot`` leaves
    the rows past the groups unset in the rows' gradient: PERF.md.)"""
    if jax.default_backend() == "tpu":
        return ops.grouped_matmul(x, w, sizes)
    return jax.lax.ragged_dot(x, w, sizes)


def moe_ffn(
    cfg: ModelConfig,
    p: dict[str, Any],
    x: jax.Array,
    ax: AxisCtx,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Dropless top-k MoE over the experts held here, expert-parallel over
    the model axis.

    The router scores each token over all ``cfg.router_width`` experts (f32
    logits, softmax) and picks the greedy top-k; a pick's gate is its score,
    renormalized over the k where ``norm_topk_prob`` (DeepSeek-V2-Lite's
    ``routed_scaling_factor`` is 1, so there is none).  Tokens are
    replicated over the model axis and each shard holds experts
    [lo, lo + E_l) of the router's: the (token, pick) rows routed to them are
    sorted by expert and run through grouped matmuls whose work follows
    those rows.  No capacity: no row is dropped,
    whatever the routing.  Experts the router has and no shard holds (they
    would lie on other chips) add nothing here.  The shared experts are
    added once (row-parallel) and one ``psum`` combines the shards.

    Returns (out, stats): ``aux``, the balance loss without its coefficient,
    and the counters ``moe_routed_rows`` (rows computed here) and
    ``moe_max_expert_rows`` (those of the busiest expert held here)."""
    B, S, d = x.shape
    T = B * S
    k = cfg.experts_per_token
    E_l = p["wi"].shape[0]
    xt = x.reshape(T, d)
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            logits = jnp.einsum("td,de->te", xt.astype(f32), p["router"].astype(f32))
            probs = jax.nn.softmax(logits, axis=-1)
            top_p, top_i = jax.lax.top_k(probs, k)  # (T, k)
            if cfg.norm_topk_prob:
                top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
            aux = balance_loss(cfg, probs, top_i, B)

        with jax.named_scope("dispatch"):
            local = top_i.reshape(-1) - jax.lax.axis_index(ax.model) * E_l  # (T*k,)
            held = (local >= 0) & (local < E_l)
            group = jnp.where(held, local, E_l)  # rows held elsewhere sort last
            order = jnp.argsort(group, stable=True)
            sizes = jnp.sum(group[:, None] == jnp.arange(E_l), axis=0, dtype=jnp.int32)
            rows = jnp.take(xt, order // k, axis=0)

        with jax.named_scope("experts"):
            h = grouped_matmul(rows, p["wi"], sizes)
            g = grouped_matmul(rows, p["wg"], sizes)
            out = grouped_matmul(jax.nn.silu(g) * h, p["wo"], sizes)

        with jax.named_scope("combine"):
            unsort = jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=order.dtype))
            gate = jnp.where(held, top_p.reshape(-1), 0.0).astype(out.dtype)
            y = (jnp.take(out, unsort, axis=0) * gate[:, None]).reshape(T, k, d).sum(1)

        if "shared" in p:
            with jax.named_scope("shared"):
                y = y + mlp(p["shared"], x, ax, reduce=False).reshape(T, d)
        y = psum(y, ax.model)  # combine expert shards (+ shared row-parallel)
    stats = {"aux": aux, "moe_routed_rows": jnp.sum(sizes).astype(f32),
             "moe_max_expert_rows": jnp.max(sizes).astype(f32)}
    return y.reshape(B, S, d), stats


# ---------------------------------------------------------------------------
# Attention (GQA / MLA), train/prefill path.
# ---------------------------------------------------------------------------


def attn_defs(cfg: ModelConfig, plan: ShapePlan) -> dict[str, Any]:
    d, H, KV, hd = plan.d, plan.H, plan.KV, plan.hd
    if cfg.seq_par:
        # sequence-parallel mode: attention weights replicated (no head
        # sharding, no padding); the sequence dim carries the parallelism
        assert cfg.attn_kind == "gqa" and not cfg.kv_lora and not cfg.moe, cfg.name
        H, KV = cfg.n_heads, cfg.n_kv_heads
        rep = P(None, None, None)
        defs = {
            "wq": ParamDef((d, H, hd), rep),
            "wk": ParamDef((d, KV, hd), rep),
            "wv": ParamDef((d, KV, hd), rep),
            "wo": ParamDef((H, hd, d), P(None, None, None)),
        }
        if cfg.qkv_bias:
            defs["bq"] = ParamDef((H, hd), P(None, None), init="zeros")
            defs["bk"] = ParamDef((KV, hd), P(None, None), init="zeros")
            defs["bv"] = ParamDef((KV, hd), P(None, None), init="zeros")
        if cfg.qk_norm:
            defs["q_norm"] = rmsnorm_def(hd)
            defs["k_norm"] = rmsnorm_def(hd)
        return defs
    kv_spec = P(None, "model", None) if plan.kv_sharded else P(None, None, None)
    if cfg.kv_lora:  # MLA (deepseek-v2) [arXiv:2405.04434]
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        defs = {
            "wq": ParamDef((d, H, qk), P(None, "model", None)),
            "w_dkv": ParamDef((d, cfg.kv_lora + cfg.qk_rope_dim), P(None, None)),
            "kv_norm": rmsnorm_def(cfg.kv_lora),
            "w_uk": ParamDef((cfg.kv_lora, H, cfg.qk_nope_dim), P(None, "model", None)),
            "w_uv": ParamDef((cfg.kv_lora, H, cfg.v_head_dim), P(None, "model", None)),
            "wo": ParamDef((H, cfg.v_head_dim, d), P("model", None, None)),
        }
        return defs
    defs = {
        "wq": ParamDef((d, H, hd), P(None, "model", None)),
        "wk": ParamDef((d, KV, hd), kv_spec),
        "wv": ParamDef((d, KV, hd), kv_spec),
        "wo": ParamDef((H, hd, d), P("model", None, None)),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H, hd), P("model", None), init="zeros")
        defs["bk"] = ParamDef((KV, hd), P("model", None) if plan.kv_sharded else P(None, None), init="zeros")
        defs["bv"] = ParamDef((KV, hd), P("model", None) if plan.kv_sharded else P(None, None), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = rmsnorm_def(hd)
        defs["k_norm"] = rmsnorm_def(hd)
    return defs


def _window_mask(q_pos: jax.Array, k_pos: jax.Array, window: int, causal: bool) -> jax.Array:
    """(Q, K) boolean mask. window counts tokens attended to (incl. self)."""
    diff = q_pos[:, None] - k_pos[None, :]
    ok = diff < window
    if causal:
        ok &= diff >= 0
    return ok


def flash_blocks(
    backend: str, q_shape: tuple, k_shape: tuple, v_shape: tuple, causal: bool
) -> tuple[int, int] | None:
    """(block_q, block_k) of the fused attention kernel where it runs these
    shapes, else None: on a TPU, causal, head dims multiples of 128, query
    heads a multiple of the KV heads, sequence lengths that blocks divide."""
    (_, Sq, H, hd), (_, Sk, KV, hd_k), hd_v = q_shape, k_shape, v_shape[-1]
    if backend != "tpu" or not causal or hd != hd_k or H % KV:
        return None
    if hd % flash_attention.LANES or hd_v % flash_attention.LANES:
        return None
    return flash_attention.block_sizes(Sq, Sk)


def _is_grouping(kv_map, H: int, KV: int) -> bool:
    """A static map that is sdpa's own grouping: q head h -> kv head h // (H/KV)."""
    return isinstance(kv_map, np.ndarray) and np.array_equal(kv_map, np.arange(H) // (H // KV))


def sdpa_chunked(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,
    *,
    window: int,
    causal: bool = True,
    q_offset: int | jax.Array = 0,  # position of query row 0; keys sit at 0..Sk-1
    kv_map: np.ndarray | jax.Array | None = None,  # (H,) kv head of each q head
    q_chunk: int = 1024,
    scale: float | None = None,  # of the scores; None: hd^-0.5
) -> jax.Array:
    """Exact attention under the name scope ``attention``.

    GQA: H must be a multiple of KV (after padding); each group of
    H/KV query heads shares one KV head, unless ``kv_map`` names each q
    head's KV head, which then gathers K/V to H heads (no gather where the
    map is static and is that grouping, and the kernel runs).

    On a TPU, where :func:`flash_blocks` fits the shapes, one fused kernel
    (``kernels/flash_attention.py``) computes it with no score matrix in HBM;
    elsewhere the jnp path below, scanned over query chunks to bound the
    score buffer.
    """
    with jax.named_scope("attention"):
        _, Sq, H, _ = q.shape
        blocks = flash_blocks(jax.default_backend(), q.shape, k.shape, v.shape, causal)
        if kv_map is not None and not (blocks and _is_grouping(kv_map, H, k.shape[2])):
            k = jnp.take(k, kv_map, axis=2)
            v = jnp.take(v, kv_map, axis=2)
        if blocks:
            return ops.flash_attention(q, k, v, q_offset, window=window, block_q=blocks[0],
                                       block_k=blocks[1], scale=scale)
        return _sdpa_jnp(q, k, v, q_pos=q_offset + jnp.arange(Sq),
                         k_pos=jnp.arange(k.shape[1]), window=window, causal=causal,
                         q_chunk=q_chunk, scale=scale)


def _sdpa_jnp(q, k, v, *, q_pos, k_pos, window, causal, q_chunk, scale=None):
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    KV = k.shape[2]
    hd_v = v.shape[-1]
    group = H // KV
    scale = hd**-0.5 if scale is None else scale
    qg = q.reshape(B, Sq, KV, group, hd)

    n_chunks = max(1, Sq // q_chunk)
    qc = min(q_chunk, Sq)
    assert Sq % qc == 0, (Sq, qc)
    # sliding-window layers only ever need K/V in [q - window + 1, q]: slice
    # the KV block per q-chunk instead of masking the full row (cuts the
    # score buffer and its HBM traffic by ~Sk/(window+qc))
    kv_len = min(Sk, window + qc) if (causal and window < Sk) else Sk

    def one_chunk(i):
        qs = jax.lax.dynamic_slice_in_dim(qg, i * qc, qc, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, i * qc, qc, axis=0)
        if kv_len < Sk:
            start = jnp.clip(i * qc + qc - kv_len, 0, Sk - kv_len)
            ks = jax.lax.dynamic_slice_in_dim(k, start, kv_len, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, kv_len, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(k_pos, start, kv_len, axis=0)
        else:
            ks, vs, kp = k, v, k_pos
        s = jnp.einsum("bqkgh,bskh->bkgqs", qs.astype(f32) * scale, ks.astype(f32))
        mask = _window_mask(qp, kp, window, causal)
        s = jnp.where(mask[None, None, None], s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskh->bqkgh", a, vs.astype(f32))
        return o.astype(q.dtype)

    if n_chunks == 1:
        out = one_chunk(0)
    else:
        out = jax.lax.map(one_chunk, jnp.arange(n_chunks))
        out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, KV, group, hd_v)
    return out.reshape(B, Sq, H, hd_v)


def attention(
    cfg: ModelConfig,
    p: dict[str, Any],
    x: jax.Array,
    ax: AxisCtx,
    *,
    positions: jax.Array,  # (3, B, S)
    window: int,
    causal: bool = True,
    kv_source: jax.Array | None = None,  # cross-attention memory (B, Sk, d)
) -> jax.Array:
    """Train/prefill attention (full sequence). Returns (B, S, d)."""
    if "w_dkv" in p:
        return _mla_attention(cfg, p, x, ax, positions=positions, window=window)
    B, S, _ = x.shape
    src = x if kv_source is None else kv_source
    Sk = src.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    kk = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    vv = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        kk = kk + p["bk"]
        vv = vv + p["bv"]
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        kk = rmsnorm(p["k_norm"], kk)
    if kv_source is None:
        q = apply_rope(cfg, q, positions)
        kk = apply_rope(cfg, kk, positions)
    H_l, KV_l = q.shape[2], kk.shape[2]
    i = jax.lax.axis_index(ax.model)
    gheads = i * H_l + jnp.arange(H_l)  # global (padded) q-head ids
    sel = None
    if KV_l == cfg.n_kv_heads and cfg.n_kv_heads != cfg.n_heads:
        # KV replicated: each local q head reads kv head h * KV / H (padded
        # dummy heads -> the last real head's); static where this shard holds
        # every head
        if H_l == cfg.n_heads:
            sel = np.arange(H_l) * cfg.n_kv_heads // cfg.n_heads
        else:
            sel = jnp.clip(gheads, 0, cfg.n_heads - 1) * cfg.n_kv_heads // cfg.n_heads
    # else: KV sharded with aligned contiguous groups — reshape grouping works
    out = sdpa_chunked(
        q, kk, vv, window=window, causal=causal and kv_source is None, kv_map=sel
    )
    # zero padded dummy heads so their (random-weight) outputs never leak
    out = out * (gheads < cfg.n_heads)[None, None, :, None].astype(out.dtype)
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return psum(o, ax.model)


def _mla_attention(cfg, p, x, ax, *, positions, window):
    """Multi-head Latent Attention (training path, decompressed K/V).  The
    q/k head dim (qk_nope + qk_rope, 192 for DeepSeek-V2) is zero-padded to
    the lanes' multiple the fused kernel takes: zero columns add nothing to
    q.k, and the scale stays that of the unpadded dim."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope = q[..., : cfg.qk_nope_dim]
    q_rope = apply_rope(cfg, q[..., cfg.qk_nope_dim :], positions)
    latent = jnp.einsum("bsd,dc->bsc", x, p["w_dkv"])
    kv_lat = rmsnorm(p["kv_norm"], latent[..., : cfg.kv_lora])
    k_rope = apply_rope(cfg, latent[..., None, cfg.kv_lora :], positions)  # (B,S,1,rope)
    k_nope = jnp.einsum("bsc,chk->bshk", kv_lat, p["w_uk"])
    v = jnp.einsum("bsc,chk->bshk", kv_lat, p["w_uv"])
    H_l = q.shape[2]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H_l, cfg.qk_rope_dim))], -1)
    qq = jnp.concatenate([q_nope, q_rope], -1)
    qk = qq.shape[-1]
    pad = [(0, 0)] * 3 + [(0, -qk % flash_attention.LANES)]
    out = sdpa_chunked(jnp.pad(qq, pad), jnp.pad(k, pad), v, window=window, causal=True,
                       scale=softmax_scale(cfg, qk))
    i = jax.lax.axis_index(ax.model)
    gheads = i * H_l + jnp.arange(H_l)
    out = out * (gheads < cfg.n_heads)[None, None, :, None].astype(out.dtype)
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return psum(o, ax.model)


def attention_seqpar(
    cfg: ModelConfig,
    p: dict[str, Any],
    x_l: jax.Array,  # (B, S_l, d) — sequence-sharded over the model axis
    ax: AxisCtx,
    *,
    positions_l: jax.Array,  # (3, B, S_l) local absolute positions
    window: int,
) -> jax.Array:
    """Sequence-parallel attention (beyond-paper; DeepSpeed-Ulysses-flavored,
    simplified for GQA): queries stay local to the sequence shard, the small
    GQA K/V are all-gathered.  No psum on the output projection — the only
    per-layer TP collective left is the FFN's (B, S_l, d) psum."""
    B, S_l, _ = x_l.shape
    i = jax.lax.axis_index(ax.model)
    q = jnp.einsum("bsd,dhk->bshk", x_l, p["wq"])
    kk = jnp.einsum("bsd,dhk->bshk", x_l, p["wk"])
    vv = jnp.einsum("bsd,dhk->bshk", x_l, p["wv"])
    if cfg.qkv_bias:
        q, kk, vv = q + p["bq"], kk + p["bk"], vv + p["bv"]
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        kk = rmsnorm(p["k_norm"], kk)
    q = apply_rope(cfg, q, positions_l)
    kk = apply_rope(cfg, kk, positions_l)
    with jax.named_scope("kv_allgather"):
        kk = all_gather(kk, ax.model, axis=1, tiled=True)  # (B, S, KV, hd)
        vv = all_gather(vv, ax.model, axis=1, tiled=True)
    # all heads are local here (16x the baseline's per-shard head count), so
    # bound the f32 score buffer with a smaller q chunk
    out = sdpa_chunked(q, kk, vv, window=window, causal=True, q_offset=i * S_l, q_chunk=128)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])  # no psum: wo replicated


# ---------------------------------------------------------------------------
# Decode attention: context-parallel over the model axis (LSE combine).
# ---------------------------------------------------------------------------


def decode_attention(
    cfg: ModelConfig,
    p: dict[str, Any],
    x: jax.Array,  # (B, 1, d)
    cache: dict[str, jax.Array],
    ax: AxisCtx,
    *,
    pos: jax.Array,  # scalar current position
    window: int,
    seq_axes: tuple[str, ...],  # axes the cache seq dim is sharded over
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One-token attention with a sequence-sharded KV cache.

    cache: {"k": (B,S_l,KV,hd), "v": ..., "pos": (S_l,) int32 absolute
    positions (-1 = empty)} ; for MLA {"lat": (B,S_l,c), "rope": ...}.
    Every shard computes partial attention over its cache slice; partials
    are combined with pmax/psum over ``seq_axes``.
    """
    if "w_dkv" in p:
        return _mla_decode(cfg, p, x, cache, ax, pos=pos, window=window, seq_axes=seq_axes)
    B = x.shape[0]
    pos3 = jnp.broadcast_to(pos, (3, B, 1))
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    kk = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    vv = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, kk, vv = q + p["bq"], kk + p["bk"], vv + p["bv"]
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        kk = rmsnorm(p["k_norm"], kk)
    q = apply_rope(cfg, q, pos3)
    kk = apply_rope(cfg, kk, pos3)
    if not cfg.seq_par:
        # gather all heads to every shard (tiny tensors)
        q = all_gather(q, ax.model, axis=2, tiled=True)  # (B,1,H,hd)
    if _kv_is_sharded(p, cache):
        kk = all_gather(kk, ax.model, axis=2, tiled=True)
        vv = all_gather(vv, ax.model, axis=2, tiled=True)
    cache = _cache_write(cache, {"k": kk[:, 0], "v": vv[:, 0]}, pos, window, seq_axes)
    valid = _cache_valid(cache["pos"], pos, window)  # (S_l,)
    q = q[:, 0]  # (B, H_pad, hd)
    H_pad, hd = q.shape[1], q.shape[2]
    KV = cache["k"].shape[2]
    if KV == cfg.n_kv_heads and cfg.n_kv_heads != cfg.n_heads:
        eff = cfg.n_heads  # drop padded dummy heads (real heads come first)
    else:
        eff = H_pad  # KV sharded/MHA-padded: aligned 1:1 groups
    qg = q[:, :eff].reshape(B, KV, eff // KV, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(f32) * hd**-0.5, cache["k"].astype(f32))
    s = jnp.where(valid[None, None, None], s, -1e30)
    o, l, m = _partial_softmax_combine(s, cache["v"], seq_axes)
    ctx = (o / jnp.maximum(l, 1e-30)).reshape(B, 1, eff, hd).astype(x.dtype)
    if cfg.seq_par:  # replicated wo: output already complete, no psum
        out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])
        return out, cache
    # mask dummy heads (their random-weight outputs must not leak), restore
    # the padded head count, then apply the local wo slice
    ctx = ctx * (jnp.arange(eff) < cfg.n_heads)[None, None, :, None].astype(ctx.dtype)
    if eff < H_pad:
        ctx = jnp.pad(ctx, ((0, 0), (0, 0), (0, H_pad - eff), (0, 0)))
    ctx_local = _local_head_slice(ctx, p["wo"].shape[0], ax)
    out = jnp.einsum("bshk,hkd->bsd", ctx_local, p["wo"])
    return psum(out, ax.model), cache


def _partial_softmax_combine(s, v, seq_axes):
    """s: (B,KV,G,S_l) masked scores; v: (B,S_l,KV,hd). LSE-combine over shards."""
    m_loc = jnp.max(s, axis=-1, keepdims=True)
    m = m_loc
    for axn in seq_axes:
        m = pmax(m, axn)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bkgs,bskh->bkgh", e, v.astype(f32))
    l = psum(l, seq_axes)
    o = psum(o, seq_axes)
    return o, l[..., 0][..., None], m


def _local_head_slice(ctx, H_l, ax):
    i = jax.lax.axis_index(ax.model)
    return jax.lax.dynamic_slice_in_dim(ctx, i * H_l, H_l, axis=2)


def _kv_is_sharded(p, cache):
    return p["wk"].shape[1] != cache["k"].shape[2]


def _cache_write(cache, new, pos, window, seq_axes):
    """Masked ring-buffer write of the new token into the local cache slice."""
    S_l = cache["pos"].shape[0]
    n_shards = 1
    for axn in seq_axes:
        n_shards *= jax.lax.axis_size(axn)
    shard = 0
    for axn in seq_axes:
        shard = shard * jax.lax.axis_size(axn) + jax.lax.axis_index(axn)
    S_alloc = S_l * n_shards
    slot_global = pos % S_alloc
    owner = slot_global // S_l
    slot = slot_global % S_l
    any_key = next(k for k in ("k", "lat") if k in cache)
    mine = (owner == shard).astype(cache[any_key].dtype)
    out = dict(cache)
    for name in new:
        upd = new[name][:, None] * mine  # (B,1,...)
        cur = jax.lax.dynamic_slice_in_dim(cache[name], slot, 1, axis=1)
        out[name] = jax.lax.dynamic_update_slice_in_dim(
            cache[name], cur * (1 - mine) + upd, slot, axis=1
        )
    newpos = jnp.where(owner == shard, pos, cache["pos"][slot]).astype(jnp.int32)
    out["pos"] = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], newpos[None], slot, axis=0
    )
    return out


def _cache_valid(cache_pos, pos, window):
    return (cache_pos >= 0) & (cache_pos <= pos) & (cache_pos > pos - window)


def _mla_decode(cfg, p, x, cache, ax, *, pos, window, seq_axes):
    B = x.shape[0]
    pos3 = jnp.broadcast_to(pos, (3, B, 1))
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope = q[..., : cfg.qk_nope_dim]
    q_rope = apply_rope(cfg, q[..., cfg.qk_nope_dim :], pos3)
    latent = jnp.einsum("bsd,dc->bsc", x, p["w_dkv"])
    kv_lat = rmsnorm(p["kv_norm"], latent[..., : cfg.kv_lora])
    k_rope = apply_rope(cfg, latent[..., None, cfg.kv_lora :], pos3)[:, :, 0]
    # absorb W_uk into q (local heads), then gather all heads
    q_lat = jnp.einsum("bshn,chn->bshc", q_nope, p["w_uk"])  # (B,1,H_l,c)
    q_lat = all_gather(q_lat, ax.model, axis=2, tiled=True)
    q_rope = all_gather(q_rope, ax.model, axis=2, tiled=True)
    cache = _cache_write(cache, {"lat": kv_lat[:, 0], "rope": k_rope[:, 0]}, pos, window, seq_axes)
    valid = _cache_valid(cache["pos"], pos, window)
    H_pad = q_lat.shape[2]
    q_lat = q_lat[:, :, : cfg.n_heads]
    q_rope = q_rope[:, :, : cfg.n_heads]
    scale = softmax_scale(cfg, cfg.qk_nope_dim + cfg.qk_rope_dim)
    s = jnp.einsum("bhc,btc->bht", q_lat[:, 0].astype(f32), cache["lat"].astype(f32))
    s = s + jnp.einsum("bhr,btr->bht", q_rope[:, 0].astype(f32), cache["rope"].astype(f32))
    s = s * scale
    s = jnp.where(valid[None, None], s, -1e30)
    m = jnp.max(s, -1, keepdims=True)
    for axn in seq_axes:
        m = pmax(m, axn)
    e = jnp.exp(s - m)
    l = psum(jnp.sum(e, -1, keepdims=True), seq_axes)
    ctx_lat = psum(jnp.einsum("bht,btc->bhc", e, cache["lat"].astype(f32)), seq_axes)
    ctx_lat = ctx_lat / jnp.maximum(l, 1e-30)
    if cfg.n_heads < H_pad:
        ctx_lat = jnp.pad(ctx_lat, ((0, 0), (0, H_pad - cfg.n_heads), (0, 0)))
    H_l = p["w_uv"].shape[1]
    i = jax.lax.axis_index(ax.model)
    ctx_local = jax.lax.dynamic_slice_in_dim(ctx_lat, i * H_l, H_l, axis=1)
    v_ctx = jnp.einsum("bhc,chn->bhn", ctx_local.astype(f32), p["w_uv"].astype(f32)).astype(x.dtype)
    out = jnp.einsum("bhn,hnd->bd", v_ctx, p["wo"])[:, None]
    return psum(out, ax.model), cache


# ---------------------------------------------------------------------------
# Embedding / logits / loss (vocab-parallel).
# ---------------------------------------------------------------------------


def embed_defs(plan: ShapePlan) -> dict[str, ParamDef]:
    return {"embedding": ParamDef((plan.V, plan.d), P("model", None), init="small")}


def embed(p: dict[str, jax.Array], ids: jax.Array, ax: AxisCtx) -> jax.Array:
    """Vocab-parallel embedding lookup: local gather + psum."""
    V_l = p["embedding"].shape[0]
    lo = jax.lax.axis_index(ax.model) * V_l
    local = ids - lo
    ok = (local >= 0) & (local < V_l)
    vec = jnp.take(p["embedding"], jnp.clip(local, 0, V_l - 1), axis=0)
    vec = vec * ok[..., None].astype(vec.dtype)
    return psum(vec, ax.model)


def logits_and_loss(
    p: dict[str, jax.Array],
    h: jax.Array,  # (B,S,d)
    labels: jax.Array,  # (B,S) int32; -1 = masked
    ax: AxisCtx,
    *,
    softcap: float = 0.0,
    s_chunk: int = 1024,
) -> jax.Array:
    """Vocab-parallel cross-entropy (Megatron-style): never materializes the
    full logits across shards, and chunks the sequence (checkpointed) so the
    (B, S, V_local) f32 logits buffer never exists either."""
    V_l = p["embedding"].shape[0]
    lo = jax.lax.axis_index(ax.model) * V_l

    def chunk_loss(h_c, labels_c):
        logits = jnp.einsum("bsd,vd->bsv", h_c.astype(f32), p["embedding"].astype(f32))
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        m = pmax(jax.lax.stop_gradient(jnp.max(logits, -1)), ax.model)  # (B,c)
        lse = jnp.log(psum(jnp.sum(jnp.exp(logits - m[..., None]), -1), ax.model)) + m
        local = labels_c - lo
        ok = (local >= 0) & (local < V_l)
        y = jnp.take_along_axis(
            logits, jnp.clip(local, 0, V_l - 1)[..., None], axis=-1
        )[..., 0]
        y = psum(y * ok.astype(f32), ax.model)
        mask = (labels_c >= 0).astype(f32)
        return jnp.sum((lse - y) * mask), jnp.sum(mask)

    B, S = labels.shape
    if S <= s_chunk:
        tot, cnt = chunk_loss(h, labels)
        return tot / jnp.maximum(cnt, 1.0)
    assert S % s_chunk == 0, (S, s_chunk)
    n = S // s_chunk
    hc = h.reshape(B, n, s_chunk, -1).swapaxes(0, 1)
    lc = labels.reshape(B, n, s_chunk).swapaxes(0, 1)

    def body(carry, xs):
        t, c = jax.checkpoint(chunk_loss)(*xs)
        return (carry[0] + t, carry[1] + c), ()

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), f32), jnp.zeros((), f32)), (hc, lc))
    return tot / jnp.maximum(cnt, 1.0)


def logits_local(p, h, ax, *, softcap: float = 0.0) -> jax.Array:
    """Decode-time logits: (B, S, V_local) vocab shard (argmax needs a
    global reduce done by the caller, or gather)."""
    logits = jnp.einsum("bsd,vd->bsv", h.astype(f32), p["embedding"].astype(f32))
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits
