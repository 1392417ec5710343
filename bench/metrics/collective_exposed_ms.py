"""collective_exposed_ms: the part of ``collective_ms`` during which no
other operation ran on that device, per step, averaged over the chips."""

from __future__ import annotations

from bench import trace


def read(tr, run):
    per = []
    for d in tr.devices:
        coll = trace.collectives(tr, d)
        if coll:
            per.append(trace.length(trace.subtract(coll, trace.compute(tr, d))))
    if not per or run["steps"] <= 0:
        return None
    return sum(per) / len(tr.devices) / run["steps"] * 1e-6
