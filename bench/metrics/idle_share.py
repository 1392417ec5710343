"""idle_share: the share of the traced window in which no operation ran on
the device, averaged over the cell's chips: 1 - union(op intervals)/window."""

from __future__ import annotations

from bench import trace


def read(tr, run):
    if not tr.devices:
        return None
    return 100.0 * (1.0 - trace.busy_s(tr) / tr.window_s)
