"""Model FLOPs per trained token, by the convention of the PaLM paper
(Chowdhery et al. 2022, appendix B):

    6 * N_matmul  +  12 * n_layers * n_heads * head_dim * seq_len

* ``N_matmul`` counts every parameter that enters a matrix product once per
  token: the attention and MLP projections of each layer and the output head
  (tied to the embedding in these configurations, so the table counts once,
  as the head).  The embedding gather, norms and biases are not products.
* The second term is the attention scores and the weighted sum, forward and
  backward, over the whole sequence (causal masking not discounted).
* Recomputation (remat) is not counted: this is the work the model needs,
  not the work the program chose to do.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d, H, KV, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = 3 * d * cfg["d_ff"]
    head = cfg["vocab"] * d
    return cfg["n_layers"] * (attn + mlp) + head


def flops_per_token(cfg: dict, seq_len: int) -> float:
    attn = 12 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * seq_len
    return 6.0 * matmul_params(cfg) + attn
