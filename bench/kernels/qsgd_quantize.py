"""QSGD stochastic quantization (``repro.kernels.qsgd``): each gradient
element becomes an int8 code, sign(x) * (floor(y) + [u < frac(y)]) with
y = |x| / ||x|| * levels.

Logical work per gradient element, whatever the implementation reads:
the f32 gradient in and the int8 code out (5 bytes of HBM traffic), and
about six element operations.  The uniform noise and the norm pass the
kernel reads today are not counted, so a kernel that fuses them away reads
against the same work.  Bound: HBM bytes.
"""

from __future__ import annotations

# the kernel's custom-call in a v5e trace carries the pallas_call's name,
# which is its wrapper's (repro.kernels.ops.qsgd_quantize)
PATTERN = r"^%qsgd_quantize\."


def work(elements: int, workers: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) for quantizing ``elements`` gradient elements on
    one chip."""
    return 6.0 * elements, 5.0 * elements
