"""roofline.expert_matmul: the held experts' grouped matmuls' share of their
roofline: the least time of their logical work (``bench/kernels/
expert_matmul.py``, from the cell's configuration and tokens) over the
device time of the operations under the program's ``moe`` / ``experts``
scopes, per step and chip, whatever implements them.

``bench/roofline.share`` counts a kernel's work from the gradient's size,
which says nothing of routed rows, so this reader computes the share
itself.  The cell is the one whose traced run left the trace it reads
(``bench_out/trace/<cell>/``).  None where the program opens no such scope."""

from __future__ import annotations

import os

from bench import cell as cells
from bench import program_trace


def experts(path: tuple[str, ...]) -> bool:
    for i, c in enumerate(path):
        if program_trace._in((c,), "moe"):
            return program_trace._in(path[i + 1:], "experts")
    return False


def traced_cell(run) -> str:
    """The cell whose traced run wrote the newest trace under the checkout."""
    top = os.path.join(run["root"], "bench_out", "trace")
    newest = max((os.path.join(d, f) for d, _, fs in os.walk(top) for f in fs
                  if f.endswith(".xplane.pb")), key=os.path.getmtime)
    return os.path.relpath(newest, top).split(os.sep)[0]


def read(tr, run):
    ms = program_trace.scope_ms(tr, run, experts)
    if ms is None:
        return None
    c = cells.resolve(traced_cell(run), run["root"])
    t = c.traffic
    k = cells.load_module("kernels", "expert_matmul", run["root"])
    flops, nbytes = k.work(c.config, t["seq_len"] * t["batch_per_chip"])
    least = max(flops / run["peak"]["bf16_flops"], nbytes / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
