"""DeepSeek-V2-Lite (16B) [arXiv:2405.04434], as published in
hf:deepseek-ai/DeepSeek-V2-Lite config.json: MLA (kv_lora_rank 512, no
q-LoRA, qk 128 + 64 rope, v 128) with YaRN rope scaling (factor 40 over
4096 positions), layer 0 dense, then 64 routed experts (softmax, greedy
top-6, not renormalized) and 2 shared experts, the sequence-wise balance
loss (aux_loss_alpha 0.001)."""

from repro.configs.base import ModelConfig


def get_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b",
        family="moe",
        source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=10944,  # dense first layer
        vocab=102400,
        attn_kind="mla",
        kv_lora=512,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_head_dim=128,
        rope_type="yarn",
        rope_theta=10000.0,
        rope_factor=40.0,
        rope_original_len=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        moe=True,
        n_experts=64,
        router_experts=64,
        experts_per_token=6,
        n_shared_experts=2,
        d_ff_expert=1408,
        first_dense_layers=1,
        norm_topk_prob=False,
        router_aux_coef=0.001,
        seq_aux=True,
        param_dtype="bfloat16",
        compute_dtype="bfloat16",
        remat="full",
    )
