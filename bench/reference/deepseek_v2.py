"""DeepSeek-V2's reference model: one chip's share of its MLA and
mixture-of-experts decoder, in plain float32 for
``bench/reference/training.py``, and its work for the metrics.

Written from the published description (arXiv:2405.04434) and the
model's modelling code (``modeling_deepseek.py`` of
hf:deepseek-ai/DeepSeek-V2-Lite); it imports nothing of the program under
test.  Each layer: pre-norm RMSNorm, then

* multi-head latent attention without q-LoRA: q = h Wq split into a
  ``qk_nope`` part and a ``qk_rope`` part; a latent [c | k_pe] = h W_dkv,
  c RMS-normed; k_nope = c W_uk and v = c W_uv per head; the rope part of k
  is one head shared by all; RoPE with YaRN frequencies on the rope parts
  (cos and sin times mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)); causal softmax at (qk_nope + qk_rope)^-0.5 times
  mscale(factor, mscale_all_dim)^2; out = o Wo;
* the first ``first_dense_layers`` layers a SwiGLU MLP, the others a
  router over ``router_experts`` (softmax of f32 logits, greedy top-k;
  gates the picked scores, renormalized over the k where
  ``norm_topk_prob``, else times ``routed_scaling_factor``) and the shared
  experts, one SwiGLU MLP of their summed width.

This chip's share (``model-configs`` guide, section 4): of the router's
experts it holds experts 0..n_experts-1, and each of them is computed on
every token and weighted by its gate there, zero where the token did not
pick it: no sort and no capacity.  What the experts held elsewhere would add
is left out, as in the program.  The loss is the mean cross-entropy over the
vocabulary slice (the head tied to the embedding) plus ``router_aux_coef``
times the sum over the expert layers of the balance loss: per sequence,
sum_e f_e P_e with f_e = E / (k S) times the picks of expert e and P_e its
mean probability (E the router's width), averaged over the sequences where
``seq_aux``, else over the batch with f_e = E / T times the picks.

Departure, as in the program: RoPE rotates halves of the rope part where
DeepSeek-V2 rotates interleaved pairs; with seeded weights that is a fixed
permutation of the rope columns.

Memory: layers are rematerialized one at a time, attention runs in blocks
of queries, the held experts one at a time, and the loss in blocks of
positions, so the reference fits one chip at the timed sizes once the
program's own state is freed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
Q_CHUNK = 512  # queries per attention block
S_CHUNK = 1024  # positions per loss block


# ------------------------------------------------------------------ layout


def _attn_layout(cfg: dict, lead: tuple) -> dict:
    d, H, c = cfg["d_model"], cfg["n_heads"], cfg["kv_lora"]
    nope, rope, vd = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_head_dim"]
    return {"wq": lead + (d, H, nope + rope), "w_dkv": lead + (d, c + rope),
            "kv_norm": lead + (c,), "w_uk": lead + (c, H, nope), "w_uv": lead + (c, H, vd),
            "wo": lead + (H, vd, d)}


def _mlp_layout(d: int, width: int, lead: tuple) -> dict:
    return {"wi": lead + (d, width), "wg": lead + (d, width), "wo": lead + (width, d)}


def param_layout(cfg: dict) -> dict:
    """Nested dict of the shape of every parameter, in the program's layout:
    the leading dense layers one by one under ``prefix``, the expert layers
    stacked on a leading layer axis under ``blocks``."""
    d, V = cfg["d_model"], cfg["vocab"]
    n_dense = cfg["first_dense_layers"]
    L = cfg["n_layers"] - n_dense
    E, ff = cfg["n_experts"], cfg["d_ff_expert"]
    prefix = [{"attn": _attn_layout(cfg, ()), "ln1": (d,), "ln2": (d,),
               "mlp": _mlp_layout(d, cfg["d_ff"], ())} for _ in range(n_dense)]
    moe = {"router": (L, d, cfg["router_experts"]), "wi": (L, E, d, ff), "wg": (L, E, d, ff),
           "wo": (L, E, ff, d), "shared": _mlp_layout(d, cfg["n_shared_experts"] * ff, (L,))}
    block = {"attn": _attn_layout(cfg, (L,)), "ln1": (L, d), "ln2": (L, d), "moe": moe}
    return {"blocks": {"0": block}, "embed": {"embedding": (V, d)}, "ln_f": (d,),
            "prefix": prefix}


# ------------------------------------------------------------------ YaRN


def _yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _yarn_inv_freq(cfg: dict, dim: int) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s inv_freq over ``dim`` rotated dims."""
    base, factor, orig = cfg["rope_theta"], cfg["rope_factor"], cfg["rope_original_len"]

    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(cfg["yarn_beta_fast"])), 0)
    high = min(math.ceil(find_correction_dim(cfg["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return (freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask).astype(np.float32)


def _rope(cfg, x, pos):
    """YaRN RoPE on x (B, S, heads, dim), rotating halves."""
    dim = x.shape[-1]
    ang = pos.astype(f32)[:, None] * jnp.asarray(_yarn_inv_freq(cfg, dim))  # (S, dim/2)
    m = (_yarn_get_mscale(cfg["rope_factor"], cfg["yarn_mscale"])
         / _yarn_get_mscale(cfg["rope_factor"], cfg["yarn_mscale_all_dim"]))
    cos = (jnp.cos(ang) * m)[None, :, None, :]
    sin = (jnp.sin(ang) * m)[None, :, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ------------------------------------------------------------------ model


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mla(cfg, ein, p, h):
    B, S, _ = h.shape
    H, c = cfg["n_heads"], cfg["kv_lora"]
    nope, rope = cfg["qk_nope_dim"], cfg["qk_rope_dim"]
    pos = jnp.arange(S)
    q = ein("bsd,dhk->bshk", h, p["wq"])
    q = jnp.concatenate([q[..., :nope], _rope(cfg, q[..., nope:], pos)], axis=-1)
    latent = ein("bsd,dc->bsc", h, p["w_dkv"])
    kv = _rmsnorm(latent[..., :c], p["kv_norm"], cfg["rms_norm_eps"])
    k_pe = _rope(cfg, latent[..., None, c:], pos)  # (B, S, 1, rope): one head for all
    k = jnp.concatenate([ein("bsc,chk->bshk", kv, p["w_uk"]),
                         jnp.broadcast_to(k_pe, (B, S, H, rope))], axis=-1)
    v = ein("bsc,chk->bshk", kv, p["w_uv"])
    scale = (nope + rope) ** -0.5
    if cfg["yarn_mscale_all_dim"]:
        scale *= _yarn_get_mscale(cfg["rope_factor"], cfg["yarn_mscale_all_dim"]) ** 2
    qc = min(Q_CHUNK, S)

    @jax.checkpoint
    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1) * scale
        s = ein("bqhk,bshk->bhqs", qs, k)
        causal = (i * qc + jnp.arange(qc))[:, None] >= pos[None, :]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return ein("bhqs,bshk->bqhk", a, v)

    o = jax.lax.map(block, jnp.arange(S // qc))  # (n, B, qc, H, vd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, -1)
    return ein("bshk,hkd->bsd", o, p["wo"])


def _swiglu(ein, m, x):
    """x (..., d) through a SwiGLU MLP {wi, wg, wo}."""
    return ein("tf,fd->td", jax.nn.silu(ein("td,df->tf", x, m["wg"])) * ein("td,df->tf", x, m["wi"]),
               m["wo"])


def _gates(cfg, ein, router, x, batch):
    """(gates (T, n_experts) of the experts held here, balance loss) for the
    tokens x (T, d) of ``batch`` sequences."""
    E, k, held = cfg["router_experts"], cfg["experts_per_token"], cfg["n_experts"]
    probs = jax.nn.softmax(ein("td,de->te", x, router), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    else:
        top_p = top_p * cfg["routed_scaling_factor"]  # 1 for DeepSeek-V2-Lite
    picked = (top_i[:, :, None] == jnp.arange(E)).astype(f32)  # (T, k, E)
    gates = jnp.einsum("tk,tke->te", top_p, picked)[:, :held]
    groups = batch if cfg["seq_aux"] else 1
    counts = jnp.sum(picked, axis=1).reshape(groups, -1, E)
    f = jnp.mean(counts, axis=1) * (E / k if cfg["seq_aux"] else E)
    P = jnp.mean(probs.reshape(groups, -1, E), axis=1)
    return gates, jnp.mean(jnp.sum(f * P, axis=-1))


def _moe(cfg, ein, p, h):
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    gates, aux = _gates(cfg, ein, p["router"], x, B)

    @jax.checkpoint
    def expert(y, e):
        w = {n: p[n][e] for n in ("wi", "wg", "wo")}
        return y + gates[:, e, None] * _swiglu(ein, w, x), None

    y, _ = jax.lax.scan(expert, _swiglu(ein, p["shared"], x), jnp.arange(cfg["n_experts"]))
    return y.reshape(B, S, d), aux


def _layer(cfg, ein, x, p):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(cfg, ein, p["attn"], _rmsnorm(x, p["ln1"], eps))
    h = _rmsnorm(x, p["ln2"], eps)
    if "moe" in p:
        y, aux = _moe(cfg, ein, p["moe"], h)
    else:
        B, S, d = h.shape
        y, aux = _swiglu(ein, p["mlp"], h.reshape(B * S, d)).reshape(B, S, d), jnp.zeros((), f32)
    return x + y, aux


def loss_fn(cfg, ein, params, tokens, labels):
    """Mean cross-entropy over the positions whose label is >= 0, plus
    ``router_aux_coef`` times the expert layers' summed balance loss."""
    emb = params["embed"]["embedding"]
    x = emb[tokens]
    step = jax.checkpoint(lambda x_, p: _layer(cfg, ein, x_, p))
    aux = jnp.zeros((), f32)
    for p in params["prefix"]:
        x, a = step(x, p)
        aux = aux + a
    x, auxs = jax.lax.scan(step, x, params["blocks"]["0"])
    aux = aux + jnp.sum(auxs)
    h = _rmsnorm(x, params["ln_f"], cfg["rms_norm_eps"])
    B, S, d = h.shape
    sc = min(S_CHUNK, S)

    @jax.checkpoint
    def chunk(carry, xs):
        hc, lc = xs
        logits = ein("bsd,vd->bsv", hc, emb)
        lse = jax.nn.logsumexp(logits, axis=-1)
        y = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        m = (lc >= 0).astype(f32)
        return (carry[0] + jnp.sum((lse - y) * m), carry[1] + jnp.sum(m)), None

    hs = h.reshape(B, S // sc, sc, d).swapaxes(0, 1)
    ls = labels.reshape(B, S // sc, sc).swapaxes(0, 1)
    (tot, cnt), _ = jax.lax.scan(chunk, (jnp.zeros((), f32), jnp.zeros((), f32)), (hs, ls))
    return tot / jnp.maximum(cnt, 1.0) + cfg["router_aux_coef"] * aux


# ------------------------------------------------------------------ work


def n_params(cfg: dict) -> int:
    shapes = jax.tree.leaves(param_layout(cfg), is_leaf=lambda x: isinstance(x, tuple))
    return int(sum(np.prod(s) for s in shapes))


def matmul_params(cfg: dict) -> float:
    """Parameters a token goes through in matrix products: every layer's
    MLA projections, the dense layers' MLP, each expert layer's router,
    shared experts and the held experts' expected share of its k routed
    experts (k x n_experts / router_experts experts a token), and the head."""
    d, H, c = cfg["d_model"], cfg["n_heads"], cfg["kv_lora"]
    nope, rope, vd = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_head_dim"]
    attn = d * H * (nope + rope) + d * (c + rope) + c * H * (nope + vd) + H * vd * d
    expert = 3 * d * cfg["d_ff_expert"]
    share = cfg["experts_per_token"] * cfg["n_experts"] / cfg["router_experts"]
    moe = d * cfg["router_experts"] + (cfg["n_shared_experts"] + share) * expert
    n_dense = cfg["first_dense_layers"]
    return (cfg["n_layers"] * attn + n_dense * 3 * d * cfg["d_ff"]
            + (cfg["n_layers"] - n_dense) * moe + cfg["vocab"] * d)


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs per trained token, by the convention of
    ``bench/reference/model.py`` (PaLM, remat not counted):

        6 * N_matmul  +  6 * n_layers * n_heads * (qk dim + v dim) * seq_len

    with ``N_matmul`` from :func:`matmul_params` (the held experts'
    expected share, not k experts) and the attention term at the unpadded
    head dims (qk_nope + qk_rope for the scores, v_head_dim for the sum)."""
    qk = cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
    attn = 6 * cfg["n_layers"] * cfg["n_heads"] * (qk + cfg["v_head_dim"]) * seq_len
    return 6.0 * matmul_params(cfg) + attn
