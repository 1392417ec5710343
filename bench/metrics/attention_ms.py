"""attention_ms: device ms per step and chip of the attention core, the
operations under the program's ``attention`` scope (``sdpa_chunked``) in the
forward, the remat recompute and the backward alike
(``bench/program_trace.py``).  None where the program opens no such scope."""

from __future__ import annotations

from bench import program_trace


def attention(path: tuple[str, ...]) -> bool:
    """Some component names the scope ``attention``, bare or under AD's
    transformations (``jvp(attention)``, ``transpose(jvp(attention))``)."""
    return program_trace._in(path, "attention")


def read(tr, run):
    return program_trace.scope_ms(tr, run, attention)
