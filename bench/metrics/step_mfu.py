"""step_mfu: the whole step's share of the chips' peak: model FLOPs per token
(``bench/flops.py``, remat not counted) times the tokens per second of the
traced window, over chips times the peak bf16 FLOP/s of ``bench/peaks.json``."""

from __future__ import annotations


def read(tr, run):
    if run["steps"] <= 0:
        return None
    achieved = run["flops_per_token"] * run["tokens_per_s"]
    return 100.0 * achieved / (run["chips"] * run["peak"]["bf16_flops"])
