"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer metric
readers read: per-device operation intervals, the benchmark's own host spans
and the traced window, all on the profiler's one clock (nanoseconds).

Device planes are ``/device:TPU:<n>``; their operations are the events of the
``XLA Ops`` line, each named by its HLO instruction (``%fusion.717 = ...``):
the reduction keeps the instruction's name and its opcode.  A ``while`` or
``conditional`` event spans the operations of its body, so it counts towards
busy time but is no operation of its own elsewhere.  Host spans are the
``jax.profiler.TraceAnnotation`` events whose names start with ``bench.``;
the window is the ``bench.window`` span.  An asynchronous collective shows as
a ``*-start`` and a ``*-done`` event; the reduction joins each pair into one
interval from the start of the one to the end of the other, the time its
transfer is in flight.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HLO = re.compile(r"^(%?[\w.-]+) = .*? ([a-z][\w-]*)\(")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Op:
    name: str  # the HLO instruction's name, e.g. "%fusion.717"
    start: float  # ns
    end: float  # ns
    kind: str = ""  # the HLO opcode, e.g. "fusion", "all-gather-start"


@dataclass
class Trace:
    devices: dict[int, list[Op]] = field(default_factory=dict)
    spans: list[Op] = field(default_factory=list)  # host spans "bench.*"
    window: tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self, device: int) -> list[Op]:
        """The device's operations clipped to the window."""
        lo, hi = self.window
        return [Op(o.name, max(o.start, lo), min(o.end, hi), o.kind)
                for o in self.devices[device] if o.end > lo and o.start < hi]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    b = union(b)
    for s, e in union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def is_collective(op: Op) -> bool:
    return bool(COLLECTIVE.match(op.kind))


def _join_async(ops: list[Op]) -> list[Op]:
    """Each ``X-start`` / ``X-done`` pair as one interval, start to done."""
    out, open_ = [], {}
    for o in sorted(ops, key=lambda o: o.start):
        m = re.match(r"^(.*?)-(start|done)(\..*)?$", o.name)
        if not m:
            out.append(o)
            continue
        base = m.group(1) + (m.group(3) or "")
        if m.group(2) == "start":
            open_[base] = o
        elif base in open_:
            s = open_.pop(base)
            out.append(Op(base, s.start, o.end, s.kind.replace("-start", "")))
        else:
            out.append(o)
    out.extend(open_.values())
    return out


def _op(event) -> Op:
    m = HLO.match(event.name)
    name, kind = (m.group(1), m.group(2)) if m else (event.name, event.name)
    return Op(name, event.start_ns, event.end_ns, kind)


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or the newest under a directory)."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    window = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [_op(e) for e in line.events]
            tr.devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        tr.spans.append(Op(e.name, e.start_ns, e.end_ns))
                        if e.name == "bench.window":
                            window = (e.start_ns, e.end_ns)
    if window is None:
        raise ValueError(f"{path}: no bench.window span")
    tr.window = window
    tr.spans.sort(key=lambda o: o.start)
    return tr


# ------------------------------------------------------------------ shared reductions


def busy(tr: Trace, device: int) -> list[tuple[float, float]]:
    return union([(o.start, o.end) for o in tr.ops(device)])


def busy_s(tr: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    return sum(length(busy(tr, d)) for d in tr.devices) / len(tr.devices) * 1e-9


def collectives(tr: Trace, device: int) -> list[tuple[float, float]]:
    """Intervals in which a collective was in flight on the device."""
    coll = [o for o in tr.ops(device) if is_collective(o)]
    return union([(o.start, o.end) for o in _join_async(coll)])


def compute(tr: Trace, device: int) -> list[tuple[float, float]]:
    """Intervals in which some other operation ran (loop bodies' operations,
    not the loops that hold them)."""
    return union([(o.start, o.end) for o in tr.ops(device)
                  if not is_collective(o) and o.kind not in CONTAINERS])


def host_label(tr: Trace, s: float, e: float) -> str:
    """The ``bench.*`` host span that covers most of [s, e] (the one that
    starts later, the inner one, on a tie)."""
    best, best_cover = "none", 0.0
    for sp in tr.spans:
        if sp.name == "bench.window":
            continue
        cover = min(e, sp.end) - max(s, sp.start)
        if cover > best_cover or (cover == best_cover and cover > 0):
            best, best_cover = sp.name, cover
    return best


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time (seconds per device,
    averaged over the devices) and the longest idle gaps of device 0, each
    labelled by the host span that covered it."""
    totals: dict[str, float] = {}
    for d in tr.devices:
        for o in tr.ops(d):
            if o.kind not in CONTAINERS:
                key = f"{o.kind} {o.name}"
                totals[key] = totals.get(key, 0.0) + (o.end - o.start)
    n = len(tr.devices)
    ops = sorted(((k, v / n * 1e-9) for k, v in totals.items()), key=lambda kv: -kv[1])
    d0 = min(tr.devices)
    idle = subtract([tr.window], busy(tr, d0))
    gaps = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": [[host_label(tr, s, e), (e - s) * 1e-9] for s, e in gaps]}
