"""Model assembly for all assigned architectures.

One composable decoder/encoder-decoder stack covering:
dense GQA (qwen*, glm4, gemma3), MLA+MoE (deepseek-v2-lite), routed MoE
(qwen3-moe), RWKV6 (attention-free), Hymba (parallel attention+SSM heads),
encoder–decoder (seamless-m4t) and VLM token streams (qwen2-vl, M-RoPE).

Layer stacking uses ``lax.scan`` over *pattern groups*: the per-layer
attention-type pattern (e.g. gemma3's LLLLLG) is unrolled inside the scanned
super-block, so heterogeneous window sizes stay static while compile time
stays O(pattern), not O(n_layers).

All functions run inside shard_map (manual mesh axes); see layers.py.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core import comms
from repro.models import layers as L
from repro.models import rwkv as RW
from repro.models import ssm as SM
from repro.models.sharding import (
    AxisCtx,
    ParamDef,
    ShapePlan,
    make_plan,
    materialize,
    stack_defs,
    tree_abstract,
    tree_specs,
)

f32 = jnp.float32


# ---------------------------------------------------------------------------
# Parameter definitions.
# ---------------------------------------------------------------------------


def _block_defs(cfg: ModelConfig, plan: ShapePlan, *, moe_layer: bool, cross: bool) -> dict:
    d = plan.d
    defs: dict[str, Any] = {"ln1": L.rmsnorm_def(d), "ln2": L.rmsnorm_def(d)}
    if cfg.family == "ssm":  # rwkv6: time-mix + channel-mix
        defs.update(RW.rwkv_defs(cfg, plan))
        return defs
    defs["attn"] = L.attn_defs(cfg, plan)
    if cfg.family == "hybrid":
        defs["ssm"] = SM.ssm_defs(cfg, plan)
    if cross:
        defs["ln_x"] = L.rmsnorm_def(d)
        defs["xattn"] = L.attn_defs(cfg.with_updates(kv_lora=0, qk_norm=False, qkv_bias=False), plan)
    if moe_layer:
        defs["moe"] = L.moe_defs(cfg, plan)
    else:
        defs["mlp"] = L.mlp_defs(d, plan.Dff)
    return defs


def build_defs(cfg: ModelConfig, plan: ShapePlan) -> dict[str, Any]:
    pat = cfg.attn_pattern
    repeats = cfg.pattern_repeats
    n_prefix = cfg.first_dense_layers
    defs: dict[str, Any] = {"embed": L.embed_defs(plan), "ln_f": L.rmsnorm_def(plan.d)}
    # prefix layers (unstacked; e.g. deepseek-v2 layer 0 is dense-FFN)
    defs["prefix"] = [
        _block_defs(cfg, plan, moe_layer=False, cross=cfg.is_encoder_decoder)
        for _ in range(n_prefix)
    ]
    # main pattern groups, each stacked over scan repeats
    n_rest = cfg.n_layers - n_prefix
    assert n_rest % len(pat) == 0, (cfg.name, n_rest, pat)
    repeats = n_rest // len(pat)
    group = {
        str(i): _block_defs(cfg, plan, moe_layer=cfg.moe, cross=cfg.is_encoder_decoder)
        for i in range(len(pat))
    }
    defs["blocks"] = stack_defs(group, repeats) if cfg.scan_layers else [
        {str(i): _block_defs(cfg, plan, moe_layer=cfg.moe, cross=cfg.is_encoder_decoder) for i in range(len(pat))}
        for _ in range(repeats)
    ]
    if cfg.is_encoder_decoder:
        enc_block = _block_defs(
            cfg.with_updates(moe=False, family="dense", kv_lora=0), plan, moe_layer=False, cross=False
        )
        defs["encoder"] = stack_defs(enc_block, cfg.encoder_layers) if cfg.scan_layers else [
            _block_defs(cfg.with_updates(moe=False, family="dense", kv_lora=0), plan, moe_layer=False, cross=False)
            for _ in range(cfg.encoder_layers)
        ]
        defs["enc_ln_f"] = L.rmsnorm_def(plan.d)
    if cfg.modality in ("vision", "audio"):
        defs["frontend_proj"] = ParamDef((plan.d, plan.d), P(None, None), init="small")
    return defs


def abstract_params(cfg: ModelConfig, msize: int):
    plan = make_plan(cfg, msize)
    defs = build_defs(cfg, plan)
    return tree_abstract(defs, cfg.pdtype), tree_specs(defs), plan


def init_params(cfg: ModelConfig, key: jax.Array, msize: int = 1):
    plan = make_plan(cfg, msize)
    defs = build_defs(cfg, plan)
    return materialize(defs, key, cfg.pdtype)


# ---------------------------------------------------------------------------
# Positions (synthetic, deterministic; M-RoPE grid for VLM).
# ---------------------------------------------------------------------------


def make_positions(cfg: ModelConfig, B: int, S: int, offset: int = 0) -> jax.Array:
    seq = jnp.arange(S) + offset
    pos = jnp.broadcast_to(seq, (3, B, S))
    if cfg.rope_type == "mrope" and cfg.modality == "vision":
        n_vis = int(S * cfg.vision_fraction)
        side = max(1, int(n_vis**0.5))
        idx = jnp.arange(S)
        h = jnp.where(idx < n_vis, idx // side, idx - n_vis + side)
        w = jnp.where(idx < n_vis, idx % side, idx - n_vis + side)
        t = jnp.where(idx < n_vis, 0, idx - n_vis + side)
        pos = jnp.stack([
            jnp.broadcast_to(t, (B, S)),
            jnp.broadcast_to(h, (B, S)),
            jnp.broadcast_to(w, (B, S)),
        ])
    return pos


# ---------------------------------------------------------------------------
# Forward blocks (training / prefill).
# ---------------------------------------------------------------------------


def _run_block(
    cfg: ModelConfig,
    p: dict[str, Any],
    x: jax.Array,
    ax: AxisCtx,
    *,
    attn_type: str,
    seq_len: int,
    positions: jax.Array,
    enc_out: jax.Array | None,
    collect_cache: bool,
    causal: bool = True,
    max_seq: int = 0,  # decode-cache capacity (collect_cache only)
) -> tuple[jax.Array, Any, dict[str, jax.Array]]:
    """Returns (x, cache_or_state, stats): the MoE layer's balance loss and
    counters (:func:`layer_stats`), zero for other layers."""
    stats = layer_stats(cfg)
    cache: Any = ()
    if cfg.family == "ssm":
        h, tm_state = RW.rwkv_block(cfg, p, L.rmsnorm(p["ln1"], x), ax)
        x = x + h
        h, cm_last = RW.rwkv_channel_mix(cfg, p, L.rmsnorm(p["ln2"], x), ax)
        x = x + h
        if collect_cache:
            cache = {"tm": tm_state, "cm_last": cm_last}
        return x, cache, stats

    window = cfg.layer_window(attn_type, seq_len)
    h_in = L.rmsnorm(p["ln1"], x)
    attn_out = L.attention(cfg, p["attn"], h_in, ax, positions=positions, window=window, causal=causal)
    if cfg.family == "hybrid":
        ssm_out, ssm_state = SM.ssm_block(cfg, p["ssm"], h_in, ax)
        x = x + 0.5 * (attn_out + ssm_out)
    else:
        ssm_state = None
        x = x + attn_out
    if enc_out is not None and "xattn" in p:
        xa = L.attention(
            cfg, p["xattn"], L.rmsnorm(p["ln_x"], x), ax,
            positions=positions, window=seq_len, causal=False, kv_source=enc_out,
        )
        x = x + xa
    h2 = L.rmsnorm(p["ln2"], x)
    if "moe" in p:
        ff, stats = L.moe_ffn(cfg, p["moe"], h2, ax)
    else:
        ff = L.mlp(p["mlp"], h2, ax)
    x = x + ff
    if collect_cache:
        cache = {"attn": _build_cache_from_prefill(cfg, p, h_in, positions, attn_type, ax, max_seq or seq_len)}
        if ssm_state is not None:
            cache["ssm"] = ssm_state
    return x, cache, stats


def layer_stats(cfg: ModelConfig) -> dict[str, jax.Array]:
    """Zeros for what a layer reports: the balance loss ``aux``, and in a MoE
    model the rows computed by its held experts and by the busiest of them
    (device counters, summed over the layers into the step's metrics)."""
    names = ("aux", "moe_routed_rows", "moe_max_expert_rows") if cfg.moe else ("aux",)
    return {n: jnp.zeros((), f32) for n in names}


def metric_names(cfg: ModelConfig) -> tuple[str, ...]:
    """The keys of :func:`forward_loss`'s metrics."""
    return ("ce", *layer_stats(cfg))


def _add(a: dict, b: dict) -> dict:
    return jax.tree.map(jnp.add, a, b)


def _build_cache_from_prefill(cfg, p, h_in, positions, attn_type, ax, max_seq):
    """Recompute K/V (cheap vs. attention itself) and lay them out in the
    decode cache format: ring buffer of capacity
    ``min(layer_window(max_seq), max_seq)`` (position p at slot p % W,
    unfilled slots pos=-1), sequence-sharded over the model axis
    (context-parallel decode)."""
    msize = comms.axis_size(ax.model)
    S = h_in.shape[1]
    W = min(cfg.layer_window(attn_type, max_seq), max_seq)
    assert W % msize == 0, (W, msize)
    fill = min(S, W)
    slots = (jnp.arange(S - fill, S)) % W  # ring slots for the last `fill`

    def ring(t):
        seg = jax.lax.dynamic_slice_in_dim(t, S - fill, fill, axis=1)
        buf = jnp.zeros((t.shape[0], W, *t.shape[2:]), t.dtype)
        return buf.at[:, slots].set(seg)

    pos_full = jnp.full((W,), -1, jnp.int32).at[slots].set(
        jnp.arange(S - fill, S, dtype=jnp.int32)
    )

    if "w_dkv" in p["attn"]:
        latent = jnp.einsum("bsd,dc->bsc", h_in, p["attn"]["w_dkv"])
        kv_lat = L.rmsnorm(p["attn"]["kv_norm"], latent[..., : cfg.kv_lora])
        k_rope = L.apply_rope(cfg, latent[..., None, cfg.kv_lora :], positions)[:, :, 0]
        full = {"lat": ring(kv_lat), "rope": ring(k_rope)}
    else:
        kk = jnp.einsum("bsd,dhk->bshk", h_in, p["attn"]["wk"])
        vv = jnp.einsum("bsd,dhk->bshk", h_in, p["attn"]["wv"])
        if cfg.qkv_bias:
            kk, vv = kk + p["attn"]["bk"], vv + p["attn"]["bv"]
        if cfg.qk_norm:
            kk = L.rmsnorm(p["attn"]["k_norm"], kk)
        kk = L.apply_rope(cfg, kk, positions)
        if kk.shape[2] != plan_kv_heads(cfg, msize):
            # kv heads sharded in prefill -> seq-sharded cache via all_to_all
            kk, vv = ring(kk), ring(vv)
            kk = comms.all_to_all(kk, ax.model, split_axis=1, concat_axis=2)
            vv = comms.all_to_all(vv, ax.model, split_axis=1, concat_axis=2)
            S_l = kk.shape[1]
            i = comms.axis_index(ax.model)
            pos_slice = jax.lax.dynamic_slice_in_dim(pos_full, i * S_l, S_l)
            return {"k": kk, "v": vv, "pos": pos_slice}
        full = {"k": ring(kk), "v": ring(vv)}
    S_l = W // msize
    i = comms.axis_index(ax.model)
    out = {
        k: jax.lax.dynamic_slice_in_dim(v, i * S_l, S_l, axis=1) for k, v in full.items()
    }
    out["pos"] = jax.lax.dynamic_slice_in_dim(pos_full, i * S_l, S_l)
    return out


def plan_kv_heads(cfg: ModelConfig, msize: int) -> int:
    """Global KV head count in the decode cache (padded for MHA)."""
    from repro.models.sharding import make_plan

    return make_plan(cfg, msize).KV


# ---------------------------------------------------------------------------
# Full forward.
# ---------------------------------------------------------------------------


def _embed_inputs(cfg, params, batch, ax):
    """Token / patch / frame embedding -> (B, S, d)."""
    x = L.embed(params["embed"], batch["tokens"], ax)
    if cfg.modality == "vision":
        patches = jnp.einsum("bsd,de->bse", batch["patches"].astype(x.dtype), params["frontend_proj"])
        x = jnp.concatenate([patches, x], axis=1)
    return x.astype(cfg.dtype)


def _encode(cfg, params, batch, ax):
    frames = jnp.einsum("bsd,de->bse", batch["frames"].astype(cfg.dtype), params["frontend_proj"])
    x = frames
    B, S_enc, _ = x.shape
    pos = make_positions(cfg, B, S_enc)

    def enc_block(x, p):
        x, _, _ = _run_block(
            cfg.with_updates(moe=False, family="dense", kv_lora=0), p, x, ax,
            attn_type="global", seq_len=S_enc, positions=pos, enc_out=None,
            collect_cache=False, causal=False,
        )
        return x, ()

    if cfg.scan_layers:
        with comms.loop(cfg.encoder_layers):
            x, _ = jax.lax.scan(lambda c, p: enc_block(c, p), x, params["encoder"])
    else:
        for p in params["encoder"]:
            x, _ = enc_block(x, p)
    return L.rmsnorm(params["enc_ln_f"], x)


def forward_loss(
    cfg: ModelConfig,
    params: dict[str, Any],
    batch: dict[str, jax.Array],
    ax: AxisCtx,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Training forward: returns (loss, metrics)."""
    x = _embed_inputs(cfg, params, batch, ax)
    B, S, _ = x.shape
    positions = make_positions(cfg, B, S)
    enc_out = _encode(cfg, params, batch, ax) if cfg.is_encoder_decoder else None
    pat = cfg.attn_pattern
    total = layer_stats(cfg)

    for p in params["prefix"]:
        x, _, st = _run_block(
            cfg, p, x, ax, attn_type=pat[0], seq_len=S, positions=positions,
            enc_out=enc_out, collect_cache=False,
        )
        total = _add(total, st)

    def super_block(x, pgroup):
        stats = layer_stats(cfg)
        for i, attn_type in enumerate(pat):
            blk = functools.partial(
                _run_block, cfg, pgroup[str(i)], ax=ax, attn_type=attn_type,
                seq_len=S, positions=positions, enc_out=enc_out, collect_cache=False,
            )
            if cfg.remat != "none":
                blk = jax.checkpoint(
                    blk,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                    if cfg.remat == "dots_saveable"
                    else None,
                )
            x, _, st = blk(x)
            stats = _add(stats, st)
        return x, stats

    repeats = (cfg.n_layers - cfg.first_dense_layers) // len(pat)
    if cfg.scan_layers:
        with comms.loop(repeats):
            x, stats = jax.lax.scan(super_block, x, params["blocks"])
        total = _add(total, jax.tree.map(jnp.sum, stats))
    else:
        for pgroup in params["blocks"]:
            x, st = super_block(x, pgroup)
            total = _add(total, st)

    x = L.rmsnorm(params["ln_f"], x)
    if cfg.modality == "vision":  # only text positions carry labels
        x = x[:, -batch["labels"].shape[1] :]
    ce = L.logits_and_loss(params["embed"], x, batch["labels"], ax, softcap=cfg.logits_softcap)
    # The aux loss is fully-replicated compute: under check_vma=False AD its
    # per-shard gradient is already complete, so scale by 1/msize so that the
    # replicated-grad psum fix-up (train.steps._fix_model_grads) is exact.
    msize = comms.axis_size(ax.model)
    loss = ce + cfg.router_aux_coef * total["aux"] / msize
    return loss, {"ce": ce, **total}


# ---------------------------------------------------------------------------
# Prefill (build decode cache) and decode.
# ---------------------------------------------------------------------------


def prefill_seqpar(
    cfg: ModelConfig,
    params: dict[str, Any],
    batch: dict[str, jax.Array],
    ax: AxisCtx,
    max_seq: int = 0,
) -> tuple[jax.Array, Any]:
    """Sequence-parallel prefill (cfg.seq_par; EXPERIMENTS.md §Perf pair 2).

    Activations are sequence-sharded over the model axis end-to-end; the
    decode cache comes out in exactly the context-parallel layout
    ``decode_step`` consumes (full-window layers only)."""
    assert cfg.family == "dense" and cfg.attn_pattern == ("global",), cfg.name
    msize = comms.axis_size(ax.model)
    i = comms.axis_index(ax.model)
    x = _embed_inputs(cfg, params, batch, ax)  # (B, S, d) replicated
    B, S, _ = x.shape
    max_seq = max_seq or S
    assert S % msize == 0 and max_seq == S, "seq_par prefill: capacity == S"
    S_l = S // msize
    x = jax.lax.dynamic_slice_in_dim(x, i * S_l, S_l, axis=1)
    positions = make_positions(cfg, B, S)
    pos_l = jax.lax.dynamic_slice_in_dim(positions, i * S_l, S_l, axis=2)

    def block(x, p):
        h_in = L.rmsnorm(p["ln1"], x)
        x = x + L.attention_seqpar(cfg, p["attn"], h_in, ax, positions_l=pos_l,
                                   window=cfg.layer_window("global", S))
        # FFN on sequence shards: tokens stay local, so each shard needs the
        # FULL dff — gather the (column/row-sharded) weights per layer
        # (ZeRO-3-style transient gather; a psum here would wrongly mix
        # different token positions across shards)
        h2 = L.rmsnorm(p["ln2"], x)
        with comms.tag("ffn_weight_gather"):
            wi = comms.all_gather(p["mlp"]["wi"], ax.model, axis=1, tiled=True)
            wg = comms.all_gather(p["mlp"]["wg"], ax.model, axis=1, tiled=True)
            wo = comms.all_gather(p["mlp"]["wo"], ax.model, axis=0, tiled=True)
        ff = jnp.einsum("bsf,fd->bsd",
                        jax.nn.silu(jnp.einsum("bsd,df->bsf", h2, wg))
                        * jnp.einsum("bsd,df->bsf", h2, wi), wo)
        x = x + ff
        # cache: the local sequence slice IS this shard's ring block (W == S)
        kk = jnp.einsum("bsd,dhk->bshk", h_in, p["attn"]["wk"])
        vv = jnp.einsum("bsd,dhk->bshk", h_in, p["attn"]["wv"])
        if cfg.qkv_bias:
            kk, vv = kk + p["attn"]["bk"], vv + p["attn"]["bv"]
        if cfg.qk_norm:
            kk = L.rmsnorm(p["attn"]["k_norm"], kk)
        kk = L.apply_rope(cfg, kk, pos_l)
        cache = {"k": kk, "v": vv, "pos": (i * S_l + jnp.arange(S_l)).astype(jnp.int32)}
        return x, {"0": {"attn": cache}}

    caches: dict[str, Any] = {"prefix": [], "pos": jnp.array(S, jnp.int32)}
    repeats = cfg.n_layers
    if cfg.scan_layers:
        def super_block(x, pgroup):
            return block(x, pgroup["0"])

        with comms.loop(repeats):
            x, blk_caches = jax.lax.scan(super_block, x, params["blocks"])
        caches["blocks"] = blk_caches
    else:
        blk_list = []
        for pgroup in params["blocks"]:
            x, c = block(x, pgroup["0"])
            blk_list.append(c)
        caches["blocks"] = blk_list
    x = L.rmsnorm(params["ln_f"], x)
    # the global last position lives on the last shard
    last = jnp.where(i == msize - 1, x[:, -1], jnp.zeros_like(x[:, -1]))
    return comms.psum(last, ax.model), caches


def prefill(
    cfg: ModelConfig,
    params: dict[str, Any],
    batch: dict[str, jax.Array],
    ax: AxisCtx,
    max_seq: int = 0,
) -> tuple[jax.Array, Any]:
    """Runs the full sequence, returns (last_hidden (B,d), cache pytree).
    ``max_seq``: decode-cache capacity (defaults to the prompt length)."""
    if cfg.seq_par:
        return prefill_seqpar(cfg, params, batch, ax, max_seq)
    x = _embed_inputs(cfg, params, batch, ax)
    B, S, _ = x.shape
    max_seq = max_seq or S
    positions = make_positions(cfg, B, S)
    enc_out = _encode(cfg, params, batch, ax) if cfg.is_encoder_decoder else None
    pat = cfg.attn_pattern
    caches: dict[str, Any] = {"prefix": [], "pos": jnp.array(S, jnp.int32)}

    for p in params["prefix"]:
        x, c, _ = _run_block(
            cfg, p, x, ax, attn_type=pat[0], seq_len=S, positions=positions,
            enc_out=enc_out, collect_cache=True, max_seq=max_seq,
        )
        caches["prefix"].append(c)

    def super_block(x, pgroup):
        cs = {}
        for i, attn_type in enumerate(pat):
            x, c, _ = _run_block(
                cfg, pgroup[str(i)], x, ax, attn_type=attn_type, seq_len=S,
                positions=positions, enc_out=enc_out, collect_cache=True,
                max_seq=max_seq,
            )
            cs[str(i)] = c
        return x, cs

    repeats = (cfg.n_layers - cfg.first_dense_layers) // len(pat)
    if cfg.scan_layers:
        with comms.loop(repeats):
            x, blk_caches = jax.lax.scan(super_block, x, params["blocks"])
    else:
        blk_list = []
        for pgroup in params["blocks"]:
            x, cs = super_block(x, pgroup)
            blk_list.append(cs)
        blk_caches = blk_list
    caches["blocks"] = blk_caches
    if enc_out is not None:
        caches["enc_out"] = enc_out
    x = L.rmsnorm(params["ln_f"], x)
    return x[:, -1], caches


def decode_step(
    cfg: ModelConfig,
    params: dict[str, Any],
    cache: Any,
    tokens: jax.Array,  # (B, 1) int32
    ax: AxisCtx,
    *,
    seq_axes: tuple[str, ...],
    max_seq: int,
) -> tuple[jax.Array, Any]:
    """One decode step. Returns (next_token (B,1), new cache)."""
    x = L.embed(params["embed"], tokens, ax).astype(cfg.dtype)
    pos = cache["pos"]
    enc_out = cache.get("enc_out") if isinstance(cache, dict) else None
    pat = cfg.attn_pattern
    new_cache = dict(cache)
    new_cache["prefix"] = []

    def dec_block(x, p, c, attn_type):
        if cfg.family == "ssm":
            return _rwkv_decode_block(cfg, p, x, c, ax)
        window = cfg.layer_window(attn_type, max_seq)
        h_in = L.rmsnorm(p["ln1"], x)
        attn_out, ac = L.decode_attention(
            cfg, p["attn"], h_in, c["attn"], ax, pos=pos, window=window, seq_axes=seq_axes
        )
        nc = {"attn": ac}
        if cfg.family == "hybrid":
            ssm_out, sc = SM.ssm_block(cfg, p["ssm"], h_in, ax, state=c["ssm"])
            nc["ssm"] = sc
            x = x + 0.5 * (attn_out + ssm_out)
        else:
            x = x + attn_out
        if enc_out is not None and "xattn" in p:
            xa = L.attention(
                cfg, p["xattn"], L.rmsnorm(p["ln_x"], x), ax,
                positions=jnp.broadcast_to(pos, (3, x.shape[0], 1)),
                window=enc_out.shape[1], causal=False, kv_source=enc_out,
            )
            x = x + xa
        h2 = L.rmsnorm(p["ln2"], x)
        if "moe" in p:
            ff, _ = L.moe_ffn(cfg, p["moe"], h2, ax)
        else:
            ff = L.mlp(p["mlp"], h2, ax)
        return x + ff, nc

    for p, c in zip(params["prefix"], cache["prefix"]):
        x, nc = dec_block(x, p, c, pat[0])
        new_cache["prefix"].append(nc)

    def super_block(x, pc):
        pgroup, cgroup = pc
        ncs = {}
        for i, attn_type in enumerate(pat):
            x, nc = dec_block(x, pgroup[str(i)], cgroup[str(i)], attn_type)
            ncs[str(i)] = nc
        return x, ncs

    repeats = (cfg.n_layers - cfg.first_dense_layers) // len(pat)
    if cfg.scan_layers:
        with comms.loop(repeats):
            x, blk_caches = _scan_decode(super_block, x, params["blocks"], cache["blocks"])
    else:
        blk_caches = []
        for pgroup, cgroup in zip(params["blocks"], cache["blocks"]):
            x, ncs = super_block(x, (pgroup, cgroup))
            blk_caches.append(ncs)
    new_cache["blocks"] = blk_caches

    x = L.rmsnorm(params["ln_f"], x)
    logits = L.logits_local(params["embed"], x, ax, softcap=cfg.logits_softcap)
    next_tok = _distributed_argmax(logits, ax)
    new_cache["pos"] = pos + 1
    return next_tok, new_cache


def _scan_decode(super_block, x, pblocks, cblocks):
    def body(carry, pc):
        x = carry
        x, ncs = super_block(x, pc)
        return x, ncs

    x, ncs = jax.lax.scan(body, x, (pblocks, cblocks))
    return x, ncs


def _rwkv_decode_block(cfg, p, x, c, ax):
    h = L.rmsnorm(p["ln1"], x)
    # single-token time-mix: token shift comes from the stored state
    out, tm_state = RW.rwkv_block(cfg, p, h, ax, state=c["tm"])
    x = x + out
    h2 = L.rmsnorm(p["ln2"], x)
    out2, cm_last = RW.rwkv_channel_mix(cfg, p, h2, ax, last=c["cm_last"])
    x = x + out2
    return x, {"tm": tm_state, "cm_last": cm_last}


def _distributed_argmax(logits_local: jax.Array, ax: AxisCtx) -> jax.Array:
    """Argmax over the vocab-sharded logits: encode (value, global idx) and
    pmax the pair."""
    B = logits_local.shape[0]
    V_l = logits_local.shape[-1]
    i = comms.axis_index(ax.model)
    loc = jnp.argmax(logits_local, axis=-1)  # (B,1)
    val = jnp.take_along_axis(logits_local, loc[..., None], axis=-1)[..., 0]
    # pack: value determines winner; break ties by shard index
    packed = val.astype(f32) * 1e6 - i.astype(f32)
    best = comms.pmax(packed, ax.model)
    win = packed == best
    gidx = jnp.where(win, loc + i * V_l, 0)
    return comms.psum(gidx, ax.model).astype(jnp.int32)
