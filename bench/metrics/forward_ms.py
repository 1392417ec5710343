"""forward_ms: device ms per step and chip of the forward pass, the
operations under the program's ``forward`` scope outside any ``transpose(``
(``bench/program_trace.py``).  None where the program opens no such scope."""

from __future__ import annotations

from bench import program_trace


def read(tr, run):
    return program_trace.scope_ms(tr, run, program_trace.forward)
