"""collective_ms: milliseconds per step in which a collective operation
(all-gather, all-reduce, reduce-scatter, collective-permute, all-to-all;
an asynchronous start/done pair counts from start to done) was in flight on
the device, averaged over the cell's chips.  None where the trace has none."""

from __future__ import annotations

from bench import trace


def read(tr, run):
    per = [trace.length(trace.collectives(tr, d)) for d in tr.devices]
    if not any(per) or run["steps"] <= 0:
        return None
    return sum(per) / len(per) / run["steps"] * 1e-6
