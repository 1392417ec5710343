"""Manual all-reduce schedules built from ``ppermute`` (paper §IV-B,
Table III).

XLA does not expose collective-algorithm selection the way NCCL does, so the
TPU-native analogue is to *write the schedule* as explicit ICI neighbor
exchanges inside shard_map.  Both schedules are numerically identical to
``psum`` (tested) and move the Table III bandwidth term exactly:

    ring: 2 N (n-1)/n   per device        (bandwidth-optimal, latency O(n))
    rhd (recursive halving-doubling): 2 N (n-1)/n, latency O(log n)

The comms wrappers record each hop, so the roofline's collective term sees
the real wire traffic of the chosen schedule.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


from repro.core import comms


def _pad_to(x: jax.Array, m: int) -> jax.Array:
    r = (-x.size) % m
    return jnp.pad(x, (0, r)) if r else x


def ring_allreduce(x: jax.Array, axis: str) -> jax.Array:
    """Bandwidth-optimal ring: reduce-scatter then all-gather [145,146]."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    orig = x.size
    xp = _pad_to(x, n)
    chunk = xp.size // n
    chunks = xp.reshape(n, chunk)
    i = jax.lax.axis_index(axis)
    fwd = [(j, (j + 1) % n) for j in range(n)]

    # reduce-scatter: after n-1 hops rank j holds the full sum of chunk (j+1)%n
    def take(c):
        return jax.lax.dynamic_slice_in_dim(chunks, c % n, 1, axis=0)[0]

    val = take(i + 1)
    for s in range(1, n):
        val = comms.ppermute(val, axis, fwd)
        val = val + take(i + 1 - s)
    my_chunk = (i + 1 - (n - 1)) % n  # == (i + 2) % n

    # all-gather: circulate completed chunks
    out = jnp.zeros_like(chunks)
    idx = my_chunk
    cur = val
    out = jax.lax.dynamic_update_slice_in_dim(out, cur[None], idx, axis=0)
    for s in range(n - 1):
        cur = comms.ppermute(cur, axis, fwd)
        idx = (idx - 1) % n
        out = jax.lax.dynamic_update_slice_in_dim(out, cur[None], idx, axis=0)
    return out.reshape(-1)[:orig]


def rhd_allreduce(x: jax.Array, axis: str) -> jax.Array:
    """Recursive halving-doubling [146]: log2(n) exchange steps."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    assert n & (n - 1) == 0, f"rhd requires power-of-two workers, got {n}"
    orig = x.size
    xp = _pad_to(x, n)
    i = jax.lax.axis_index(axis)

    # reduce-scatter by recursive halving
    segs = []  # (offset, size) of the live segment, tracked per-branch via where
    size = xp.size
    offset = jnp.zeros((), jnp.int32)
    buf = xp
    bit = n >> 1
    while bit:
        pairs = [(j, j ^ bit) for j in range(n)]
        half = size // 2
        upper = (i & bit) > 0
        lo = jax.lax.dynamic_slice_in_dim(buf, offset, half)
        hi = jax.lax.dynamic_slice_in_dim(buf, offset + half, half)
        send = jnp.where(upper, lo, hi)
        recv = comms.ppermute(send, axis, pairs)
        keep = jnp.where(upper, hi, lo)
        summed = keep + recv
        offset = offset + jnp.where(upper, half, 0).astype(jnp.int32)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, summed, offset, axis=0)
        size = half
        bit >>= 1

    # all-gather by recursive doubling (reverse order)
    bit = 1
    while bit < n:
        pairs = [(j, j ^ bit) for j in range(n)]
        upper = (i & bit) > 0
        seg = jax.lax.dynamic_slice_in_dim(buf, offset, size)
        recv = comms.ppermute(seg, axis, pairs)
        new_off = offset - jnp.where(upper, size, 0).astype(jnp.int32)
        other_off = jnp.where(upper, new_off, new_off + size).astype(jnp.int32)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, recv, other_off, axis=0)
        offset = new_off
        size *= 2
        bit <<= 1
    return buf[:orig]


def allreduce(x: jax.Array, axes: tuple[str, ...], impl: str = "xla") -> jax.Array:
    """Dense all-reduce over (possibly multiple) mesh axes with a selectable
    schedule.  Multi-axis manual schedules run hierarchically (axis by axis),
    which is itself the paper's 'hierarchical all-reduce' [21,150]."""
    if impl == "xla":
        return comms.psum(x, axes)
    fn = {"ring": ring_allreduce, "rhd": rhd_allreduce}[impl]
    shape = x.shape
    flat = x.reshape(-1)
    for axis in axes:
        flat = fn(flat, axis)
    return flat.reshape(shape)
