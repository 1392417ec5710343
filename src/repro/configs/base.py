"""Model/config schema for all assigned architectures.

Every architecture in ``repro.configs`` instantiates :class:`ModelConfig`.
The config fully determines the model built by ``repro.models.transformer``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Input shapes (assigned; see the task brief).
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Model config.
# ---------------------------------------------------------------------------


@dataclass
class ModelConfig:
    # identity ---------------------------------------------------------------
    name: str = "tiny"
    family: str = "dense"  # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""  # citation for the configuration

    # trunk ------------------------------------------------------------------
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0  # 0 -> d_model // n_heads
    d_ff: int = 512
    vocab: int = 1024
    tie_embeddings: bool = False

    # attention --------------------------------------------------------------
    attn_kind: str = "gqa"  # gqa | mla | none (rwkv) | hybrid (attn+ssm)
    qkv_bias: bool = False
    qk_norm: bool = False
    # Repeating per-layer pattern of attention types, e.g. 5*("local",)+("global",)
    attn_pattern: tuple[str, ...] = ("global",)
    window: int = 1024  # sliding window for "local" layers
    rope_type: str = "rope"  # rope | mrope | partial | yarn | none
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0  # "partial": fraction of head_dim rotated
    mrope_sections: tuple[int, int, int] = (16, 24, 24)  # half-dims (t, h, w)
    # "yarn" [arXiv:2309.00071], as DeepSeek-V2's rope_scaling names them:
    # context extension factor, pretraining length, the ramp's rotation
    # bounds and the two attention-temperature coefficients
    rope_factor: float = 1.0
    rope_original_len: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # MLA (deepseek) ---------------------------------------------------------
    kv_lora: int = 0  # latent dim; >0 enables MLA
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128

    # MoE --------------------------------------------------------------------
    moe: bool = False
    #: routed experts held (the router's experts 0..n_experts-1); every token
    #: routed to one of them is computed: no capacity, no token dropped
    n_experts: int = 0
    #: the router's width; 0 means n_experts.  Wider where this program holds
    #: its share of an expert-parallel layer, whose other experts lie on
    #: chips that are not here
    router_experts: int = 0
    experts_per_token: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0  # expert hidden size (d_ff used for dense layers)
    first_dense_layers: int = 0  # leading layers with dense FFN (deepseek)
    norm_topk_prob: bool = True  # renormalize the top-k gates to sum to 1
    router_aux_coef: float = 0.001
    #: balance loss per sequence with DeepSeek's 1/k (seq_aux), else
    #: Switch-style over the whole batch
    seq_aux: bool = False

    # SSM / hybrid (rwkv6, hymba) ---------------------------------------------
    ssm_state: int = 16
    ssm_conv: int = 3
    ssm_expand: float = 1.0  # d_inner = expand * d_model
    rwkv_head_dim: int = 64  # rwkv6 head size
    rwkv_decay_lora: int = 64
    rwkv_mix_lora: int = 32

    # encoder-decoder (seamless) ----------------------------------------------
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    encoder_ratio: int = 4  # encoder_seq = seq_len // encoder_ratio

    # modality frontend stub --------------------------------------------------
    modality: str = "text"  # text | vision | audio
    vision_fraction: float = 0.25  # fraction of seq positions that are patches

    # numerics / implementation ------------------------------------------------
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    scan_layers: bool = True
    remat: str = "none"  # none | full | dots_saveable
    logits_softcap: float = 0.0

    # runtime overrides (set by launcher) ---------------------------------------
    swa_override: int = 0  # >0: force all "global" layers to this window (long ctx)
    #: sequence-parallel prefill (beyond-paper; EXPERIMENTS.md §Perf): shard
    #: the sequence over the model axis, replicate attention weights,
    #: all-gather the (small, GQA) K/V — slashes prefill TP collectives.
    #: Dense single-pattern attention archs only.
    seq_par: bool = False

    # ------------------------------------------------------------------ helpers
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def router_width(self) -> int:
        return self.router_experts or self.n_experts

    @property
    def pattern_repeats(self) -> int:
        assert self.n_layers % len(self.attn_pattern) == 0, (
            f"{self.name}: n_layers={self.n_layers} not divisible by "
            f"pattern {self.attn_pattern}"
        )
        return self.n_layers // len(self.attn_pattern)

    def layer_window(self, attn_type: str, seq_len: int) -> int:
        """Effective attention window for a layer type at a given seq_len."""
        if attn_type == "local":
            return self.window
        if self.swa_override:
            return self.swa_override
        return seq_len

    def with_updates(self, **kw: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant of the same family (<=2 pattern repeats,
        d_model<=256, <=4 experts)."""
        if len(self.attn_pattern) > 1:
            pattern = (self.attn_pattern[0], self.attn_pattern[-1])
        else:
            pattern = self.attn_pattern
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        while n_heads % n_kv:
            n_kv -= 1
        upd = dict(
            attn_pattern=pattern,
            window=min(self.window, 16),
            n_layers=2,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=min(self.resolved_head_dim, 64),
            d_ff=min(self.d_ff, 512),
            vocab=min(self.vocab, 512),
            scan_layers=False,
            param_dtype="float32",
            compute_dtype="float32",
        )
        if self.moe:
            upd.update(
                n_experts=min(self.n_experts, 4),
                router_experts=min(self.router_width, 4),
                experts_per_token=min(self.experts_per_token, 2),
                d_ff_expert=min(self.d_ff_expert or self.d_ff, 256),
                first_dense_layers=min(self.first_dense_layers, 1),
            )
        if self.rope_type == "mrope":
            s = min(self.resolved_head_dim, 64) // 2
            upd.update(mrope_sections=(s - 2 * (s // 3), s // 3, s // 3))
        if self.kv_lora:
            upd.update(kv_lora=64, qk_nope_dim=32, qk_rope_dim=16, v_head_dim=32)
        if self.is_encoder_decoder:
            upd.update(encoder_layers=2)
        if self.family in ("ssm", "hybrid"):
            upd.update(rwkv_head_dim=32, rwkv_decay_lora=16, rwkv_mix_lora=8)
        return self.with_updates(**upd)

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)


def n_params(cfg: ModelConfig) -> int:
    """Analytic parameter count (approximate; used for roofline MODEL_FLOPS)."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    # attention
    if cfg.kv_lora:
        attn = d * (cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim))
        attn += d * (cfg.kv_lora + cfg.qk_rope_dim)
        attn += cfg.kv_lora * cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim)
        attn += cfg.n_heads * cfg.v_head_dim * d
    elif cfg.attn_kind == "none":
        attn = 0
    else:
        attn = d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd + cfg.n_heads * hd * d
    # ffn
    if cfg.moe:
        dff = cfg.d_ff_expert or cfg.d_ff
        moe_ffn = 3 * d * dff * (cfg.n_experts + cfg.n_shared_experts) + d * cfg.router_width
        dense_ffn = 3 * d * cfg.d_ff
        n_moe = cfg.n_layers - cfg.first_dense_layers
        ffn_total = n_moe * moe_ffn + cfg.first_dense_layers * dense_ffn
    else:
        ffn_total = cfg.n_layers * 3 * d * cfg.d_ff
    if cfg.family == "ssm":  # rwkv6: time-mix + channel-mix
        att_dim = cfg.d_model
        tm = 4 * d * att_dim + att_dim * d + 2 * d * cfg.d_ff  # rwkv ffn is 2-proj
        ffn_total = 0
        attn = tm
    total = cfg.n_layers * attn + ffn_total + cfg.vocab * d * (1 if cfg.tie_embeddings else 2)
    if cfg.is_encoder_decoder:
        # encoder self-attn + ffn and decoder cross-attn
        enc = cfg.encoder_layers * (attn + 3 * d * cfg.d_ff)
        total += enc + cfg.n_layers * attn  # cross-attn approx
    if cfg.family == "hybrid":
        d_inner = int(cfg.ssm_expand * d)
        ssm = cfg.n_layers * (2 * d * d_inner + d_inner * cfg.ssm_conv + 3 * d_inner * cfg.ssm_state + d_inner * d)
        total += ssm
    return int(total)


def active_params(cfg: ModelConfig) -> int:
    """Active parameters per token (MoE: only routed-in experts)."""
    if not cfg.moe:
        return n_params(cfg)
    full = n_params(cfg)
    dff = cfg.d_ff_expert or cfg.d_ff
    d = cfg.d_model
    n_moe_layers = cfg.n_layers - cfg.first_dense_layers
    inactive = n_moe_layers * 3 * d * dff * (cfg.n_experts - cfg.experts_per_token)
    return int(full - inactive)
