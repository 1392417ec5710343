"""gen_ms: host milliseconds per step spent in the traffic generator
(``bench/data``), from the benchmark's own ``bench.gen`` spans inside the
traced window.  Moves ``tokens_per_s`` once it outlasts the device's step."""

from __future__ import annotations


def read(tr, run):
    spans = [s for s in tr.spans if s.name == "bench.gen"
             and s.start >= tr.window[0] and s.end <= tr.window[1]]
    if not spans:
        return None
    return sum(s.end - s.start for s in spans) / len(spans) * 1e-6
