"""roofline.qsgd_quantize: the QSGD quantize kernel's share of its HBM
roofline (``bench/kernels/qsgd_quantize.py``), over the whole gradient each
step.  None where the trace holds no such kernel."""

from __future__ import annotations

from bench import roofline


def read(tr, run):
    return roofline.share(tr, run, "qsgd_quantize")
