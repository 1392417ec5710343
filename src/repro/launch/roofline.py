"""Roofline-term extraction from a compiled dry-run artifact.

Three terms per (arch x shape x mesh), in seconds, priced with the peaks of
the named device kind (:data:`PEAKS`):

    compute    = HLO_FLOPs / peak_FLOPs
    memory     = HLO_bytes / HBM_bw
    collective = collective_bytes / (links x ICI bw per link)

``HLO_FLOPs``/``HLO_bytes`` come from ``compiled.cost_analysis()`` (the
per-device SPMD module).  ``collective_bytes`` has two independent sources:
  * primary: the comms-wrapper capture (exact, loop-aware, design-coupled);
  * cross-check: summing operand bytes of collective ops in the optimized
    HLO text (upper-bounds loop bodies by their trip count where the
    enclosing while can be matched; reported raw otherwise).
"""

from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class DevicePeaks:
    flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM B/s per chip
    ici_bw: float  # B/s per ICI link
    ici_links: int  # links engaged per collective phase (2D torus ring dims)


#: Published peaks by ``jax.Device.device_kind``.  "TPU v5 lite" is the TPU
#: v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16, 819 GB/s
#: HBM, 1,600 Gbit/s ICI per chip = 4 links x 50 GB/s.
PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                               ici_links=2),
}

#: The device kind the dry-run and the analytic roofline rows are priced for.
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> DevicePeaks:
    """Peaks of ``device_kind``; a kind missing from the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    flops: float  # per device
    hbm_bytes: float
    coll_bytes: float  # per device, from comms capture
    coll_bytes_hlo: float  # cross-check (static HLO text, no loop multiplicity)
    coll_by_kind: dict
    device_kind: str
    backward_factor: float = 1.0

    @property
    def t_compute(self) -> float:
        return self.flops / peaks(self.device_kind).flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / peaks(self.device_kind).hbm_bw

    @property
    def t_collective(self) -> float:
        pk = peaks(self.device_kind)
        return self.coll_bytes * self.backward_factor / (pk.ici_bw * pk.ici_links)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "device_kind": self.device_kind,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
            "coll_bytes_hlo": self.coll_bytes_hlo,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "coll_by_kind": self.coll_by_kind,
        }


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLL_RE = re.compile(
    r"=\s*(\w[\w\d]*\[[^\]]*\])(?:\{[^}]*\})?\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(sig: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def hlo_collective_bytes(hlo_text: str) -> tuple[float, dict]:
    """Sum output-shape bytes of collective ops in optimized HLO text.
    Static count — ops inside while bodies counted once (cross-check only).
    """
    total = 0.0
    by_kind: dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        nbytes = _shape_bytes(m.group(1))
        kind = m.group(2)
        total += nbytes
        by_kind[kind] = by_kind.get(kind, 0.0) + nbytes
    return total, by_kind


def extract(
    arch: str,
    shape: str,
    mesh_name: str,
    compiled,
    comm_log,
    *,
    device_kind: str,
    backward_factor: float = 1.0,
) -> Roofline:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):  # older API returned [dict]
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    hlo_bytes, _ = hlo_collective_bytes(compiled.as_text())
    # the AD-transpose collective twins only exist for the forward-pass TP
    # collectives (untagged); gradient aggregation / zero1 / sync run outside
    # AD and are counted once
    weighted = sum(
        r.wire_bytes * r.mult * (backward_factor if not r.tag else 1.0)
        for r in comm_log.records
    )
    return Roofline(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        flops=flops,
        hbm_bytes=hbm,
        coll_bytes=weighted,
        coll_bytes_hlo=hlo_bytes,
        coll_by_kind=comm_log.by_kind(),
        device_kind=device_kind,
        backward_factor=1.0,
    )
