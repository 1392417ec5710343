"""encode_ms: device ms per step and chip under ``grad_agg``'s ``encode``
stage: error feedback and momentum correction before compression, the
compressor with its norm and noise draw, and packing
(``bench/program_trace.py``).  None where the trace holds none."""

from __future__ import annotations

from bench import program_trace


def read(tr, run):
    return program_trace.scope_ms(tr, run, program_trace.encode)
