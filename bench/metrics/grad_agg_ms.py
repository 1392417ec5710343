"""grad_agg_ms: device ms per step and chip under the program's ``grad_agg``
scope: bucket packing, encode, the collectives' own events, decode and
unpacking (``bench/program_trace.py``).  At one chip it is what the bypassed
exchange still costs.  None where the trace holds no such operation."""

from __future__ import annotations

from bench import program_trace


def read(tr, run):
    return program_trace.scope_ms(tr, run, program_trace.grad_agg)
