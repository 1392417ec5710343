"""optimizer_ms: device ms per step and chip under the program's
``optimizer`` scope: clipping and the optimizer's update
(``bench/program_trace.py``).  None where the program opens no such scope."""

from __future__ import annotations

from bench import program_trace


def read(tr, run):
    return program_trace.scope_ms(tr, run, program_trace.optimizer)
