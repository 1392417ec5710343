"""remat_ms: the part of ``backward_ms`` that recomputes the forward pass,
the operations under ``rematted_computation`` inside the backward scope
(``bench/program_trace.py``).  None where the trace holds none."""

from __future__ import annotations

from bench import program_trace


def read(tr, run):
    return program_trace.scope_ms(tr, run, program_trace.remat)
