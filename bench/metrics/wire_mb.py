"""wire_mb: the per-chip wire bytes of a step, in MB (1e6 bytes), as the
program counts them (``StepBundle.wire``) and reports them in the
``wire_bytes`` stat of its ``trainer.step`` spans (``bench/program_trace.py``).
None where the program opens no such span."""

from __future__ import annotations

from bench import program_trace


def read(tr, run):
    return program_trace.wire_mb(tr, run)
