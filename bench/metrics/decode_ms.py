"""decode_ms: device ms per step and chip under ``grad_agg``'s ``decode``
stage: the per-worker weights, the decoding reduction kernels and the
residual update after compression (``bench/program_trace.py``).  None where
the trace holds none."""

from __future__ import annotations

from bench import program_trace


def read(tr, run):
    return program_trace.scope_ms(tr, run, program_trace.decode)
