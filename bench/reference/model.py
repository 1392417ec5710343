"""The dense reference model: a configuration's loss in plain float32, for
``bench/reference/training.py`` to train, and its work for the metrics.

A configuration file names its reference model with ``"reference"``
(``bench/reference/<name>.py``); without the key it is this file.  A model
file supplies four functions and nothing else:

* ``param_layout(cfg)``: the shape of every parameter, as a nested dict
  whose leaf paths are the program's (``param_abstract``);
* ``loss_fn(cfg, einsum, params, tokens, labels)``: the mean token
  cross-entropy, with every matrix product through ``einsum(spec, a, b)``
  (the control runs the same loss with fp8 products);
* ``n_params(cfg)``: the elements of every leaf, the gradient's size;
* ``flops_per_token(cfg, seq_len)``: the model FLOPs per trained token.

This one is a decoder of dense blocks: pre-norm RMSNorm (eps from the file),
grouped-query attention with rotate-half RoPE on the first ``rope_fraction``
of each head (Qwen3: RMSNorm of q and k per head first; GLM-4: q/k/v
biases), causal softmax, a SwiGLU MLP, a final RMSNorm and a head tied to
the embedding.  It imports nothing of the program under test.

Memory: layers are rematerialized one at a time, attention runs in blocks of
queries, and the loss in blocks of positions, so the reference fits one chip
at the timed sizes once the program's own state is freed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
Q_CHUNK = 512  # queries per attention block
S_CHUNK = 1024  # positions per loss block


# ------------------------------------------------------------------ layout


def param_layout(cfg: dict) -> dict:
    """Nested dict of the shape of every parameter, in the program's layout:
    the layers' parameters stacked on a leading layer axis."""
    L, d, H, KV = cfg["n_layers"], cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, dff, V = cfg["head_dim"], cfg["d_ff"], cfg["vocab"]
    attn = {"wq": (L, d, H, hd), "wk": (L, d, KV, hd), "wv": (L, d, KV, hd),
            "wo": (L, H, hd, d)}
    if cfg.get("qkv_bias"):
        attn.update(bq=(L, H, hd), bk=(L, KV, hd), bv=(L, KV, hd))
    if cfg.get("qk_norm"):
        attn.update(q_norm=(L, hd), k_norm=(L, hd))
    block = {"attn": attn, "ln1": (L, d), "ln2": (L, d),
             "mlp": {"wi": (L, d, dff), "wg": (L, d, dff), "wo": (L, dff, d)}}
    return {"blocks": {"0": block}, "embed": {"embedding": (V, d)}, "ln_f": (d,)}


# ------------------------------------------------------------------ model


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(cfg, x, pos):
    """Rotate-half RoPE on the first rope_fraction of the head dim."""
    hd = x.shape[-1]
    rot = int(hd * cfg.get("rope_fraction", 1.0)) if cfg["rope_type"] == "partial" else hd
    rot -= rot % 2
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, rot, 2, dtype=f32) / rot))
    ang = pos.astype(f32)[:, None] * inv  # (S, rot/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out, x[..., rot:]], axis=-1) if rot < hd else out


def _attention(cfg, ein, p, h):
    B, S, _ = h.shape
    H, KV = cfg["n_heads"], cfg["n_kv_heads"]
    eps = cfg["rms_norm_eps"]
    q = ein("bsd,dhk->bshk", h, p["wq"])
    k = ein("bsd,dhk->bshk", h, p["wk"])
    v = ein("bsd,dhk->bshk", h, p["wv"])
    if cfg.get("qkv_bias"):
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.get("qk_norm"):
        q, k = _rmsnorm(q, p["q_norm"], eps), _rmsnorm(k, p["k_norm"], eps)
    pos = jnp.arange(S)
    q, k = _rope(cfg, q, pos), _rope(cfg, k, pos)
    k = jnp.repeat(k, H // KV, axis=2)  # query head h reads kv head h // (H/KV)
    v = jnp.repeat(v, H // KV, axis=2)
    qc = min(Q_CHUNK, S)
    scale = q.shape[-1] ** -0.5

    @jax.checkpoint
    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1) * scale
        s = ein("bqhk,bshk->bhqs", qs, k)
        causal = (i * qc + jnp.arange(qc))[:, None] >= pos[None, :]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return ein("bhqs,bshk->bqhk", a, v)

    o = jax.lax.map(block, jnp.arange(S // qc))  # (n, B, qc, H, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, -1)
    return ein("bshk,hkd->bsd", o, p["wo"])


def _layer(cfg, ein, x, p):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, ein, p["attn"], _rmsnorm(x, p["ln1"], eps))
    h = _rmsnorm(x, p["ln2"], eps)
    m = p["mlp"]
    ff = jax.nn.silu(ein("bsd,df->bsf", h, m["wg"])) * ein("bsd,df->bsf", h, m["wi"])
    return x + ein("bsf,fd->bsd", ff, m["wo"])


def loss_fn(cfg, ein, params, tokens, labels):
    """Mean cross-entropy over the positions whose label is >= 0."""
    emb = params["embed"]["embedding"]
    x = emb[tokens]
    step = jax.checkpoint(lambda x_, p: (_layer(cfg, ein, x_, p), None))
    x, _ = jax.lax.scan(step, x, params["blocks"]["0"])
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
    return tot / jnp.maximum(cnt, 1.0)


# ------------------------------------------------------------------ work


def n_params(cfg: dict) -> int:
    shapes = jax.tree.leaves(param_layout(cfg), is_leaf=lambda x: isinstance(x, tuple))
    return int(sum(np.prod(s) for s in shapes))


def matmul_params(cfg: dict) -> int:
    d, H, KV, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = 3 * d * cfg["d_ff"]
    head = cfg["vocab"] * d
    return cfg["n_layers"] * (attn + mlp) + head


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs per trained token, by the convention of the PaLM paper
    (Chowdhery et al. 2022, appendix B):

        6 * N_matmul  +  12 * n_layers * n_heads * head_dim * seq_len

    * ``N_matmul`` counts every parameter that enters a matrix product once
      per token: the attention and MLP projections of each layer and the
      output head (tied to the embedding in these configurations, so the
      table counts once, as the head).  The embedding gather, norms and
      biases are not products.
    * The second term is the attention scores and the weighted sum, forward
      and backward, over the whole sequence (causal masking not discounted).
    * Recomputation (remat) is not counted: this is the work the model
      needs, not the work the program chose to do.

    A model file with sparse experts counts, in ``N_matmul``, the products a
    token goes through: its router, its ``k`` routed experts and the shared
    experts, not every expert held."""
    attn = 12 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * seq_len
    return 6.0 * matmul_params(cfg) + attn
