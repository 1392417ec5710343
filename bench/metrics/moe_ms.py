"""moe_ms: device ms per step and chip of the held-experts layer, the
operations under the program's ``moe`` scope (``models/layers.py:moe_ffn``:
router, dispatch, grouped matmuls, combine and shared experts) in the
forward, the remat recompute and the backward alike
(``bench/program_trace.py``).  None where the program opens no such scope."""

from __future__ import annotations

from bench import program_trace


def moe(path: tuple[str, ...]) -> bool:
    """Some component names the scope ``moe``, bare or under AD's
    transformations (``jvp(moe)``, ``transpose(jvp(moe))``)."""
    return program_trace._in(path, "moe")


def read(tr, run):
    return program_trace.scope_ms(tr, run, moe)
