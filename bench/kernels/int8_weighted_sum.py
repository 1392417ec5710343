"""Widening weighted sum of gathered int8 codes (``repro.kernels.wire_reduce``
``int8_acc``): out[i] = sum_w weight[w] * codes[w, i] in f32.

Logical work per output element: the W int8 codes in and the f32 sum out
(W + 4 bytes of HBM traffic), and a multiply and an add per worker.
Bound: HBM bytes.
"""

from __future__ import annotations

# the kernel's custom-call in a v5e trace carries the pallas_call's name,
# which is its wrapper's (repro.kernels.ops.int8_weighted_sum)
PATTERN = r"^%int8_weighted_sum\."


def work(elements: int, workers: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) for reducing ``elements`` outputs of ``workers``
    gathered code rows on one chip."""
    return 2.0 * workers * elements, (workers + 4.0) * elements
