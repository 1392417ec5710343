"""The held experts' grouped matmuls (``models/layers.py:moe_ffn`` under
the scopes ``moe`` / ``experts``): for each expert layer, the rows routed to
the experts held here through a SwiGLU expert, x Wi and x Wg (d -> d_ff
each) and then (silu(g) h) Wo (d_ff -> d).

Logical work per step and chip, whatever implements it (a grouped-matmul
kernel, ``ragged_dot``, or dense products over a padded buffer alike):

* rows = tokens x k x n_experts / router_experts, the held experts'
  expected share of the (token, pick) rows;
* 3 products of 2 x rows x d_model x d_ff_expert FLOPs, 4 times over: the
  forward, the remat recompute and the backward's two products (the rows'
  and the weights' gradients);
* HBM bytes: each product reads its rows and the held experts' weights and
  writes its result once, in bf16.

Bound: FLOPs at the cell's sizes.
"""

from __future__ import annotations

PASSES = 4  # forward, remat, backward (rows), backward (weights)
BYTES = 2  # bf16


def rows(cfg: dict, tokens: int) -> float:
    return tokens * cfg["experts_per_token"] * cfg["n_experts"] / cfg["router_experts"]


def work(cfg: dict, tokens: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of a step's held-expert matmuls for ``tokens``
    tokens on one chip."""
    r = rows(cfg, tokens)
    d, f, E = cfg["d_model"], cfg["d_ff_expert"], cfg["n_experts"]
    layers = cfg["n_layers"] - cfg["first_dense_layers"]
    flops = 3 * 2.0 * r * d * f
    nbytes = BYTES * (3 * E * d * f + 2 * (r * d + r * f) + r * (f + d))
    return layers * PASSES * flops, layers * PASSES * nbytes
