"""Instrumented collectives.

Every collective the framework issues goes through these wrappers.  At trace
time (inside ``capture()``) each call records its *local payload bytes*, the
mesh axes involved, and the enclosing loop multiplicity (``loop(n)`` wraps
``lax.scan`` bodies).  This gives an exact, design-coupled account of the
bytes each collective moves — the quantity the paper's communication-cost
tables (III, IV) are about — without fragile HLO while-loop parsing.
(The optimized-HLO text is still parsed as a cross-check; see
``repro.launch.roofline``.)

Backward passes: JAX AD inserts the transposed collectives (psum↔pbroadcast,
all_gather↔reduce_scatter) which do not pass through these wrappers; train
steps therefore scale forward collective bytes by ``backward_factor`` (≈2 for
Megatron-style TP, exact for the gradient aggregation itself which happens
outside AD).
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np


_STATE = threading.local()


@dataclass
class CollRecord:
    kind: str  # psum | pmax | all_gather | ppermute | all_to_all | reduce_scatter
    axes: tuple[str, ...]
    payload_bytes: int  # local operand bytes per call
    mult: float  # loop multiplicity
    n_workers: int = 1  # product of the collective's axis sizes
    tag: str = ""
    wire_format: str = "f32"  # actual on-wire encoding: f32|bf16|int8|packed1|packed2|...

    @property
    def wire_bytes(self) -> float:
        """Per-device ICI bytes implied by the (bandwidth-optimal) algorithm:
        all-reduce 2p(n-1)/n; all-gather p(n-1) [p = local shard];
        reduce-scatter / all-to-all p(n-1)/n; ppermute p."""
        p, n = self.payload_bytes, max(self.n_workers, 1)
        if n == 1:
            return 0.0
        if self.kind in ("psum", "pmax"):
            return 2.0 * p * (n - 1) / n
        if self.kind == "all_gather":
            return float(p * (n - 1))
        if self.kind in ("reduce_scatter", "all_to_all"):
            return p * (n - 1) / n
        return float(p)  # ppermute


@dataclass
class CommLog:
    records: list[CollRecord] = field(default_factory=list)

    def total_bytes(self, kinds: tuple[str, ...] | None = None) -> float:
        """Total per-device wire bytes."""
        return sum(
            r.wire_bytes * r.mult
            for r in self.records
            if kinds is None or r.kind in kinds
        )

    def payload_bytes(self) -> float:
        return sum(r.payload_bytes * r.mult for r in self.records)

    def by_kind(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for r in self.records:
            out[r.kind] = out.get(r.kind, 0.0) + r.wire_bytes * r.mult
        return out

    def by_tag(self, *, with_format: bool = False) -> dict[str, float]:
        """Wire bytes per tag; ``with_format=True`` splits each tag by the
        payload's actual on-wire encoding (``"grad_agg[packed1]"``)."""
        out: dict[str, float] = {}
        for r in self.records:
            key = r.tag or "untagged"
            if with_format:
                key = f"{key}[{r.wire_format}]"
            out[key] = out.get(key, 0.0) + r.wire_bytes * r.mult
        return out

    def by_wire_format(self, *, payload: bool = False,
                       exclude_tags: tuple[str, ...] = ()) -> dict[str, float]:
        """Bytes per on-wire encoding — wire bytes by default, raw local
        payload bytes with ``payload=True`` (mesh-size independent, what the
        32x packed-vs-dense claims are stated in).  ``exclude_tags`` drops
        whole channels (e.g. the dense ``churn_resync`` rejoin channel) so
        a breakdown can describe the payload wire alone."""
        out: dict[str, float] = {}
        for r in self.records:
            if r.tag in exclude_tags:
                continue
            b = r.payload_bytes if payload else r.wire_bytes
            out[r.wire_format] = out.get(r.wire_format, 0.0) + b * r.mult
        return out


def _log() -> CommLog | None:
    return getattr(_STATE, "log", None)


def _mult() -> float:
    return getattr(_STATE, "mult", 1.0)


def _tag() -> str:
    return getattr(_STATE, "tag", "")


def _wire_fmt() -> str:
    return getattr(_STATE, "wire_fmt", "")


def capturing() -> bool:
    """True while some ``capture()`` is open on this thread.  Cached program
    paths that would skip tracing entirely (the persistent executable cache)
    consult this to keep the contract that a capture held open around a
    step's first call observes that step's collectives."""
    return _log() is not None


@contextlib.contextmanager
def capture():
    """Collect collective records issued while tracing under this context."""
    prev = _log()
    _STATE.log = CommLog()
    try:
        yield _STATE.log
    finally:
        _STATE.log = prev


@contextlib.contextmanager
def loop(n: int):
    """Multiply records inside (e.g. around a ``lax.scan`` over layers)."""
    prev = _mult()
    _STATE.mult = prev * n
    try:
        yield
    finally:
        _STATE.mult = prev


@contextlib.contextmanager
def tag(name: str):
    prev = _tag()
    _STATE.tag = name
    try:
        yield
    finally:
        _STATE.tag = prev


@contextlib.contextmanager
def wire_format(name: str):
    """Override the recorded on-wire encoding for collectives issued inside.
    Needed where the array dtype under-describes the packing (a uint32 sign
    bitmap is 1 bit/element -> ``packed1``, a 2-bit ternary payload ->
    ``packed2``); plain narrow dtypes (int8/bf16) are derived automatically
    from the payload leaves."""
    prev = _wire_fmt()
    _STATE.wire_fmt = name
    try:
        yield
    finally:
        _STATE.wire_fmt = prev


def _bytes(x) -> int:
    return int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize


_DTYPE_FMT = {
    "float32": "f32", "bfloat16": "bf16", "float16": "f16",
    "int8": "int8", "uint8": "int8", "int32": "int32", "uint32": "int32",
}


def _fmt_of(x) -> str:
    """Derive the wire format from the payload's dominant (largest) leaf."""
    leaves = jax.tree.leaves(x)
    if not leaves:
        return "f32"
    big = max(leaves, key=_bytes)
    name = jnp.dtype(big.dtype).name
    return _DTYPE_FMT.get(name, name)


def _record(kind: str, axes, x) -> None:
    log = _log()
    if log is None:
        return
    if isinstance(axes, str):
        axes = (axes,)
    total = sum(_bytes(leaf) for leaf in jax.tree.leaves(x))
    n = 1
    try:
        for a in axes:
            n *= jax.lax.axis_size(a)
    except Exception:  # outside shard_map (e.g. unit tests): size unknown
        n = 1
    fmt = _wire_fmt() or _fmt_of(x)
    log.records.append(
        CollRecord(kind, tuple(axes), total, _mult(), n, _tag(), fmt))


# ---------------------------------------------------------------------------
# Wrappers.
# ---------------------------------------------------------------------------


def psum(x, axes, *, tag_: str = ""):
    if isinstance(axes, (list, tuple)) and not axes:
        return x
    _record("psum", axes, x)
    return jax.lax.psum(x, axes)


def pmax(x, axes):
    if isinstance(axes, (list, tuple)) and not axes:
        return x
    _record("pmax", axes, x)
    return jax.lax.pmax(x, axes)


def pmean(x, axes):
    if isinstance(axes, (list, tuple)) and not axes:
        return x
    _record("psum", axes, x)
    return jax.lax.pmean(x, axes)


def all_gather(x, axes, *, axis: int = 0, tiled: bool = False):
    _record("all_gather", axes, x)
    if axis == 0 and not tiled and jnp.issubdtype(x.dtype, jnp.integer):
        return _gather_words(x, axes)
    return jax.lax.all_gather(x, axes, axis=axis, tiled=tiled)


def _gather_words(x, axes):
    """Untiled axis-0 all-gather of a sub-32-bit payload, carried as 32-bit
    words (bit-identical result, same bytes).  The TPU compiler's time for
    a narrow all-gather grows with its size: compiled for a described v5e,
    32M int8 elements take 58 s as int8 and 2.3 s as int32 words, which
    made full-width int8-wire train steps take minutes to compile."""
    bits = 8 * x.dtype.itemsize
    k = 32 // bits  # narrow values per word
    if k <= 1 or x.ndim == 0:
        return jax.lax.all_gather(x, axes, axis=0)
    # value j*m + i of the last axis sits in bits [bits*j, bits*(j+1)) of
    # word i: whole planes, so no (m, k)-shaped array is ever laid out
    n = x.shape[-1]
    m = -(-n // k)
    uint = jnp.dtype(f"uint{bits}")
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, m * k - n)])
    u = jax.lax.bitcast_convert_type(xp, uint).astype(jnp.uint32)
    words = u[..., :m]
    for j in range(1, k):
        words = words | (u[..., j * m:(j + 1) * m] << (bits * j))
    g = jax.lax.all_gather(words, axes, axis=0)
    planes = [((g >> (bits * j)) & ((1 << bits) - 1)).astype(uint)
              for j in range(k)]
    out = jnp.concatenate(planes, axis=-1)[..., :n]
    return jax.lax.bitcast_convert_type(out, x.dtype)


def ppermute(x, axis_name, perm):
    _record("ppermute", axis_name, x)
    return jax.lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name, split_axis, concat_axis, *, tiled: bool = True):
    _record("all_to_all", axis_name, x)
    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=tiled)


def psum_scatter(x, axis_name, *, scatter_dimension: int = 0, tiled: bool = True):
    _record("reduce_scatter", axis_name, x)
    return jax.lax.psum_scatter(
        x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled
    )


def all_gather_compressed(payload: dict, axes, *, axis: int = 0) -> dict:
    """All-gather a compressed wire payload dict (codes + per-tensor scales)
    leaf by leaf.  Each leaf is recorded at its ACTUAL dtype bytes — an int8
    code array logs N bytes, not the 4N of its dense decode — so `CommLog`
    accounting reflects what the wire carries.  Use ``wire_format(...)``
    around the call when the dtype under-describes the packing."""
    return {k: all_gather(v, axes, axis=axis) for k, v in payload.items()}


def widening_psum(x, axes):
    """All-reduce with a narrow wire dtype but f32 accumulation: gather the
    narrow payload (recorded at its actual byte width) and sum widened, so
    e.g. a bf16 wire format never rounds partial sums to bf16.  Costs
    p(n-1) wire vs psum's 2p(n-1)/n — cheaper than a dense-f32 psum for
    any sub-f32 payload at moderate n."""
    if isinstance(axes, (list, tuple)) and not axes:
        return x.astype(jnp.float32)
    _record("all_gather", axes, x)
    g = _gather_words(x, axes)
    return jnp.sum(g.astype(jnp.float32), axis=0)


def varying(x, axes):
    """Mark a (constant-created) value as varying over the given mesh axes —
    needed for scan carries initialized with jnp.zeros inside shard_map."""
    if isinstance(axes, str):
        axes = (axes,)
    return jax.lax.pcast(x, tuple(axes), to="varying")


def axis_index(axis_name):
    return jax.lax.axis_index(axis_name)


def axis_size(axis_name) -> int:
    return jax.lax.axis_size(axis_name)
