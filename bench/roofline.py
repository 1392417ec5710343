"""A kernel's share of its roofline from the device trace: the least time
the chip could take for the kernel's logical work (the larger of FLOPs over
peak FLOP/s and bytes over peak HBM bytes/s, ``bench/kernels/<kernel>.py``)
over the summed time of the kernel's events, per step and chip."""

from __future__ import annotations

import re

from bench import cell as cells


def share(tr, run: dict, kernel: str) -> float | None:
    k = cells.load_module("kernels", kernel, run.get("root"))
    pat = re.compile(k.PATTERN)
    total, hits = 0.0, 0
    for d in tr.devices:
        for o in tr.ops(d):
            if o.kind == "custom-call" and pat.match(o.name):
                total += o.end - o.start
                hits += 1
    if not hits or run["steps"] <= 0:
        return None
    seconds = total * 1e-9 / len(tr.devices) / run["steps"]  # per chip and step
    flops, nbytes = k.work(run["grad_elements"], run["workers"])
    least = max(flops / run["peak"]["bf16_flops"], nbytes / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
