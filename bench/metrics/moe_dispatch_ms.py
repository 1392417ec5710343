"""moe_dispatch_ms: device ms per step and chip of the held-experts layer's
row movement, which a dense layer does not have: the operations under the
program's ``moe`` scope and, inside it, ``dispatch`` (sort of the (token,
pick) rows by expert and their gather) or ``combine`` (un-sort, gates and
the sum over the picks), in forward, remat and backward alike
(``bench/program_trace.py``).  None where the program opens no such scope."""

from __future__ import annotations

from bench import program_trace

STAGES = ("dispatch", "combine")


def row_movement(path: tuple[str, ...]) -> bool:
    for i, c in enumerate(path):
        if program_trace._in((c,), "moe"):
            return any(program_trace._in(path[i + 1:], s) for s in STAGES)
    return False


def read(tr, run):
    return program_trace.scope_ms(tr, run, row_movement)
