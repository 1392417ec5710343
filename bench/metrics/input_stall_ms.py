"""input_stall_ms: device idle ms per step and chip while the host was in
the trainer's ``trainer.batch`` or ``trainer.put`` span: the time a step
waited for its data (``bench/program_trace.py``).  None where the program
opens no such span."""

from __future__ import annotations

from bench import program_trace


def read(tr, run):
    return program_trace.input_stall_ms(tr, run)
