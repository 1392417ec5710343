"""roofline.int8_weighted_sum: the int8 widening-sum kernel's share of its
HBM roofline (``bench/kernels/int8_weighted_sum.py``), over the whole
gradient each step.  None where the trace holds no such kernel."""

from __future__ import annotations

from bench import roofline


def read(tr, run):
    return roofline.share(tr, run, "int8_weighted_sum")
