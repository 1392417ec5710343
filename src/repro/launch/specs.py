"""Input / cache ShapeDtypeStructs and PartitionSpecs for every
(architecture × input shape × mesh) combination.

``input_specs`` returns weak-type-correct ShapeDtypeStruct stand-ins (no
device allocation) for the step functions; ``batch_pspecs`` / ``cache_pspecs``
give the matching PartitionSpecs used both as shard_map in/out_specs and as
jit in/out_shardings.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core.types import CommConfig
from repro.models.sharding import AxisCtx
from repro.utils.tree import tree_map_with_name

f32 = jnp.float32

# Named comm presets of the launchers (paper-faithful baseline and the
# compressed variants).
COMM_PRESETS = {
    "dense_bsp": CommConfig(),
    "topk_ef": CommConfig(
        compressor="topk", compressor_kwargs={"ratio": 0.01},
        error_feedback=True, momentum_correction=0.9, bucket_mb=32,
    ),
    "qsgd": CommConfig(compressor="qsgd", compressor_kwargs={"levels": 16}, bucket_mb=32),
    "signsgd_mv": CommConfig(compressor="signsgd", bucket_mb=32),
    # the Pallas-kernel compressors: 8-bit QSGD codes and 1-bit packed signs
    # (pair with --wire-format compressed for the compressed-domain reduce)
    "qsgd_int8": CommConfig(compressor="qsgd_kernel",
                            compressor_kwargs={"levels": 127}, bucket_mb=32),
    "signsgd_packed": CommConfig(compressor="signsgd_packed", bucket_mb=32),
    "local_sgd": CommConfig(sync="local", local_steps=8),
    "ring_manual": CommConfig(collective="ring", bucket_mb=32),
    # multi-pod: BSP on ICI inside each pod, Local-SGD across the DCN
    # boundary every 8 steps (survey §III-D at pod scale)
    "pod_local_sgd": CommConfig(pod_local=True, local_steps=8),
}


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_axis_ctx(mesh) -> AxisCtx:
    return AxisCtx(data=data_axes(mesh), model="model")


def batch_sharding_plan(mesh, shape: InputShape) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """Returns (batch_axes, seq_axes) for decode-cache sharding.

    The KV cache sequence dim is always sharded over the model axis; when the
    global batch cannot cover the data axes (long_500k has batch=1), the
    sequence is additionally sharded over them (context-parallel decode).
    """
    daxes = data_axes(mesh)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    if shape.global_batch % dsize == 0 and shape.global_batch >= dsize:
        return daxes, ("model",)
    return (), daxes + ("model",)


def train_inputs(cfg: ModelConfig, shape: InputShape, mesh) -> tuple[dict, dict]:
    """(ShapeDtypeStruct dict, PartitionSpec dict) for train/prefill."""
    B, S = shape.global_batch, shape.seq_len
    daxes = data_axes(mesh)
    bspec = P(daxes)
    specs: dict[str, Any] = {}
    pspecs: dict[str, Any] = {}
    S_text = S
    if cfg.modality == "vision":
        S_vis = int(S * cfg.vision_fraction)
        S_text = S - S_vis
        specs["patches"] = jax.ShapeDtypeStruct((B, S_vis, cfg.d_model), jnp.bfloat16)
        pspecs["patches"] = P(daxes, None, None)
    if cfg.is_encoder_decoder:
        S_enc = max(1, S // cfg.encoder_ratio)
        specs["frames"] = jax.ShapeDtypeStruct((B, S_enc, cfg.d_model), jnp.bfloat16)
        pspecs["frames"] = P(daxes, None, None)
    specs["tokens"] = jax.ShapeDtypeStruct((B, S_text), jnp.int32)
    pspecs["tokens"] = P(daxes, None)
    if shape.kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct((B, S_text), jnp.int32)
        pspecs["labels"] = P(daxes, None)
    return specs, pspecs


def serve_cache_specs(cfg: ModelConfig, mesh, shape: InputShape) -> tuple[Any, Any]:
    """Analytic (ShapeDtypeStruct tree, PartitionSpec tree) for the decode
    cache of one architecture at one input shape.  Must mirror exactly what
    ``repro.models.transformer.prefill`` emits / ``decode_step`` consumes.
    """
    from repro.models.sharding import make_plan

    B, S = shape.global_batch, shape.seq_len
    msize = mesh.shape["model"]
    plan = make_plan(cfg, msize)
    baxes, saxes = batch_sharding_plan(mesh, shape)
    pat = cfg.attn_pattern
    kvd = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else f32

    def attn_cache(attn_type: str) -> dict:
        W = min(cfg.layer_window(attn_type, S), S)
        if cfg.kv_lora:
            return {
                "lat": jax.ShapeDtypeStruct((B, W, cfg.kv_lora), kvd),
                "rope": jax.ShapeDtypeStruct((B, W, cfg.qk_rope_dim), kvd),
                "pos": jax.ShapeDtypeStruct((W,), jnp.int32),
            }
        hd = cfg.resolved_head_dim
        # plan.KV: MHA caches are padded together with the q heads
        # (seq_par mode keeps weights replicated and unpadded)
        KV = cfg.n_kv_heads if cfg.seq_par else plan.KV
        return {
            "k": jax.ShapeDtypeStruct((B, W, KV, hd), kvd),
            "v": jax.ShapeDtypeStruct((B, W, KV, hd), kvd),
            "pos": jax.ShapeDtypeStruct((W,), jnp.int32),
        }

    def block_cache(attn_type: str) -> dict:
        if cfg.family == "ssm":
            from repro.models.sharding import make_plan

            plan = make_plan(cfg, msize)
            return {
                "tm": {
                    "shift": jax.ShapeDtypeStruct((B, cfg.d_model), kvd),
                    "wkv": jax.ShapeDtypeStruct(
                        (B, plan.rwkv_heads, plan.rwkv_hd, plan.rwkv_hd), f32
                    ),
                },
                "cm_last": jax.ShapeDtypeStruct((B, cfg.d_model), kvd),
            }
        out: dict[str, Any] = {"attn": attn_cache(attn_type)}
        if cfg.family == "hybrid":
            from repro.models.sharding import make_plan

            plan = make_plan(cfg, msize)
            out["ssm"] = {
                "conv": jax.ShapeDtypeStruct((B, cfg.ssm_conv - 1, plan.d_inner), kvd),
                "h": jax.ShapeDtypeStruct((B, plan.d_inner, cfg.ssm_state), f32),
            }
        return out

    repeats = (cfg.n_layers - cfg.first_dense_layers) // len(pat)
    group = {str(i): block_cache(t) for i, t in enumerate(pat)}
    if cfg.scan_layers:
        blocks = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((repeats, *x.shape), x.dtype), group
        )
    else:
        blocks = [
            {str(i): block_cache(t) for i, t in enumerate(pat)} for _ in range(repeats)
        ]
    cache: dict[str, Any] = {
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
        "prefix": [block_cache(pat[0]) for _ in range(cfg.first_dense_layers)],
        "blocks": blocks,
    }
    if cfg.is_encoder_decoder:
        S_enc = max(1, S // cfg.encoder_ratio)
        cache["enc_out"] = jax.ShapeDtypeStruct((B, S_enc, cfg.d_model), kvd)
    pspecs = cache_pspecs(cfg, cache, mesh, shape)
    return cache, pspecs


def cache_pspecs(cfg: ModelConfig, cache_abstract: Any, mesh, shape: InputShape) -> Any:
    """PartitionSpec tree for a decode cache, keyed on leaf path names."""
    baxes, saxes = batch_sharding_plan(mesh, shape)

    def rule(name: str, leaf) -> P:
        key = name.rsplit("/", 1)[-1]
        nd = len(leaf.shape)
        # stacked scan-over-layers leaves carry a leading (repeats,) dim
        lead = (None,) if (name.startswith("blocks") and cfg.scan_layers) else ()
        nd -= len(lead)
        if key == "pos":
            return P(*lead, saxes) if nd >= 1 else P(*lead)
        if key in ("k", "v", "lat", "rope"):  # (B, S_l, ...) seq-sharded
            return P(*lead, baxes, saxes, *(None,) * (nd - 2))
        if key == "enc_out":
            return P(*lead, baxes, None, None)
        if key in ("shift", "cm_last"):
            return P(*lead, baxes, None)
        if key == "wkv":
            return P(*lead, baxes, "model", None, None)
        if key == "conv":
            return P(*lead, baxes, None, "model")
        if key == "h":
            return P(*lead, baxes, "model", None)
        raise ValueError(f"no cache pspec rule for {name} shape={leaf.shape}")

    return tree_map_with_name(rule, cache_abstract)
