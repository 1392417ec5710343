"""The program's own names in a traced run: the name scope of every device
operation and the trainer's host spans with their stats.

The program scopes its step (``forward``, whose backward AD names
``transpose(jvp(forward))`` with the remat recompute under
``rematted_computation``; ``optimizer``; ``grad_agg`` with its ``encode``
and ``decode`` stages) and opens ``trainer.step`` / ``trainer.batch`` /
``trainer.put`` host spans around each step of ``Trainer.fit``.

A device operation's scope path is the ``tf_op`` stat of its *event
metadata* in the ``.xplane.pb``, which ``jax.profiler.ProfileData`` does not
expose (it returns event stats only), so this module decodes the planes'
metadata maps with a small protobuf wire reader of its own: the standard
library only, since the protobuf and xprof modules may be missing where the
benchmark runs.  Events come from ``ProfileData``, and are joined to their
metadata by the full metadata name (the HLO instruction's text), not by the
instruction name alone, since two programs in one trace can both hold
``%fusion.1``.

Every duration here is the union of operation intervals, an asynchronous
``*-start`` / ``*-done`` event counting as itself and not as the time its
transfer is in flight; loops and calls, which span their bodies, are left
out.  The run's trace holds no such names where the program opens none: each
reader then returns None.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from dataclasses import dataclass, field

from bench import trace

SPANS = "trainer."
STALL_SPANS = ("trainer.batch", "trainer.put")


@dataclass
class ScopedOp:
    start: float  # ns
    end: float  # ns
    path: tuple[str, ...]  # the tf_op scope path, e.g. ("jit(_step)", "optimizer", "mul:")


@dataclass
class Span:
    name: str
    start: float  # ns
    end: float  # ns
    stats: dict = field(default_factory=dict)


@dataclass
class ProgramTrace:
    devices: dict[int, list[ScopedOp]] = field(default_factory=dict)
    spans: list[Span] = field(default_factory=list)  # host spans "trainer.*"
    window: tuple[float, float] = (0.0, 0.0)

    def ops(self, device: int) -> list[ScopedOp]:
        """The device's scoped operations clipped to the window."""
        lo, hi = self.window
        return [ScopedOp(max(o.start, lo), min(o.end, hi), o.path)
                for o in self.devices[device] if o.end > lo and o.start < hi]


# ------------------------------------------------------------------ protobuf wire reader


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, wire type, value) of one message in buf[lo:hi]; a
    length-delimited value is its (start, end) span in ``buf``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire} at byte {i}")
        yield num, wire, value


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, entry: tuple[int, int]):
    """The value message span of one protobuf map entry (key 1, value 2)."""
    for num, _, value in _fields(buf, *entry):
        if num == 2:
            yield value


def _scope_paths(buf: bytes) -> dict[str, dict[str, tuple[str, ...] | None]]:
    """For each device plane, the tf_op path of each event metadata name
    (None where one name carries two different paths).

    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4, stat_metadata =
    5 (both maps); XEventMetadata: name = 2, stats = 5; XStatMetadata: id =
    1, name = 2; XStat: metadata_id = 1, str_value = 5, ref_value = 7 (the id
    of a stat metadata whose name is the string)."""
    out = {}
    for num, _, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, events, stat_names = None, [], {}
        for pnum, _, value in _fields(buf, *plane):
            if pnum == 2:
                name = _text(buf, value)
            elif pnum == 4:
                events.extend(_map_values(buf, value))
            elif pnum == 5:
                for meta in _map_values(buf, value):
                    sid, sname = 0, None  # proto3 leaves a zero id out
                    for snum, _, sv in _fields(buf, *meta):
                        if snum == 1:
                            sid = sv
                        elif snum == 2:
                            sname = _text(buf, sv)
                    stat_names[sid] = sname
        if name is None or not trace.DEVICE_PLANE.match(name):
            continue
        tf_op_id = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        paths: dict[str, tuple[str, ...] | None] = {}
        out[name] = paths
        if tf_op_id is None:
            continue
        for meta in events:
            ename, tf_op = None, None
            for enum, _, ev in _fields(buf, *meta):
                if enum == 2:
                    ename = _text(buf, ev)
                elif enum == 5:
                    sid, sval = 0, None
                    for snum, _, sv in _fields(buf, *ev):
                        if snum == 1:
                            sid = sv
                        elif snum == 5:
                            sval = _text(buf, sv)
                        elif snum == 7:
                            sval = stat_names.get(sv)
                    if sid == tf_op_id and sval:
                        tf_op = sval
            if ename is None:
                continue
            path = tuple(tf_op.split("/")) if tf_op else None
            paths[ename] = path if paths.get(ename, path) == path else None
    return out


# ------------------------------------------------------------------ loading


@functools.lru_cache(maxsize=2)
def _parse(path: str, mtime: float) -> ProgramTrace:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        buf = f.read()
    scopes = _scope_paths(buf)
    data = ProfileData.from_serialized_xspace(buf)
    pt = ProgramTrace()
    window = None
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            paths = scopes.get(plane.name, {})
            ops = []
            for line in plane.lines:
                if line.name != trace.OPS_LINE:
                    continue
                for e in line.events:
                    p = paths.get(e.name)
                    hlo = trace.HLO.match(e.name)
                    if p and not (hlo and hlo.group(2) in trace.CONTAINERS):
                        ops.append(ScopedOp(e.start_ns, e.end_ns, p))
            pt.devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPANS):
                        pt.spans.append(Span(e.name, e.start_ns, e.end_ns, dict(e.stats)))
                    elif e.name == "bench.window":
                        window = (e.start_ns, e.end_ns)
    if window is None:
        raise ValueError(f"{path}: no bench.window span")
    pt.window = window
    pt.spans.sort(key=lambda s: s.start)
    return pt


def load(path: str) -> ProgramTrace:
    """The program's names in one ``.xplane.pb``; parsed once per process."""
    return _parse(path, os.path.getmtime(path))


def of(tr: trace.Trace, run: dict) -> ProgramTrace:
    """The traced run that ``tr`` reduces: the newest ``.xplane.pb`` under
    ``<root>/bench_out/trace/``, which must hold the same window."""
    found = sorted(glob.glob(os.path.join(run["root"], "bench_out", "trace", "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {run['root']}/bench_out/trace")
    pt = load(found[-1])
    if pt.window != tr.window:
        raise ValueError(f"{found[-1]} holds the window {pt.window}, the run's "
                         f"trace {tr.window}")
    return pt


# ------------------------------------------------------------------ scopes


@functools.lru_cache(maxsize=None)
def _pattern(scope: str) -> re.Pattern:
    return re.compile(rf"(?:[\w-]+\()*{re.escape(scope)}\)*")


def _in(path: tuple[str, ...], scope: str) -> bool:
    """Some component names ``scope``, bare or under transformations such as
    ``jvp(scope)`` or ``transpose(jvp(scope))``."""
    pat = _pattern(scope)
    return any(pat.fullmatch(c) for c in path)


def backward(path: tuple[str, ...]) -> bool:
    return any(c.startswith("transpose(") and _in((c,), "forward") for c in path)


def forward(path: tuple[str, ...]) -> bool:
    return _in(path, "forward") and not any(c.startswith("transpose(") for c in path)


def remat(path: tuple[str, ...]) -> bool:
    return backward(path) and "rematted_computation" in path


def optimizer(path: tuple[str, ...]) -> bool:
    return _in(path, "optimizer")


def grad_agg(path: tuple[str, ...]) -> bool:
    return _in(path, "grad_agg")


def _stage(path: tuple[str, ...], stage: str) -> bool:
    for i, c in enumerate(path):
        if _in((c,), "grad_agg"):
            return _in(path[i + 1:], stage)
    return False


def encode(path: tuple[str, ...]) -> bool:
    return _stage(path, "encode")


def decode(path: tuple[str, ...]) -> bool:
    return _stage(path, "decode")


def scope_ms(tr: trace.Trace, run: dict, keep) -> float | None:
    """Device milliseconds per step and chip of the operations whose scope
    path ``keep`` accepts; None where the trace holds none."""
    pt = of(tr, run)
    total, hit, kept = 0.0, False, {}
    for d in pt.devices:
        iv = []
        for o in pt.ops(d):
            if o.path not in kept:
                kept[o.path] = keep(o.path)
            if kept[o.path]:
                iv.append((o.start, o.end))
        hit = hit or bool(iv)
        total += trace.length(trace.union(iv))
    if not hit or run["steps"] <= 0:
        return None
    return total / len(pt.devices) / run["steps"] * 1e-6


# ------------------------------------------------------------------ host spans


def wire_mb(tr: trace.Trace, run: dict) -> float | None:
    """The mean ``wire_bytes`` stat of the ``trainer.step`` spans inside the
    window, in MB (1e6 bytes): the per-chip bytes the step's programs put on
    the wire, as the program counts them.  None where no span carries it."""
    pt = of(tr, run)
    lo, hi = pt.window
    counts = [s.stats["wire_bytes"] for s in pt.spans
              if s.name == "trainer.step" and s.start >= lo and s.end <= hi
              and "wire_bytes" in s.stats]
    if not counts:
        return None
    return sum(counts) / len(counts) / 1e6


def intersect(a: list[tuple[float, float]], b: list[tuple[float, float]]):
    return trace.subtract(a, trace.subtract(a, b))


def input_stall_ms(tr: trace.Trace, run: dict) -> float | None:
    """Device idle milliseconds per step and chip inside the window while the
    host was in ``trainer.batch`` or ``trainer.put``: the time a step waited
    for its data.  None where the program opens no such span."""
    pt = of(tr, run)
    feed = trace.union([(s.start, s.end) for s in pt.spans if s.name in STALL_SPANS])
    if not feed or not tr.devices or run["steps"] <= 0:
        return None
    total = 0.0
    for d in tr.devices:
        idle = trace.subtract([tr.window], trace.busy(tr, d))
        total += trace.length(intersect(idle, feed))
    return total / len(tr.devices) / run["steps"] * 1e-6
