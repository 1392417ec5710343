"""Gossip (decentralized) training (paper §IV-C).

Runtime path (inside shard_map over the data axes): neighbor mixing via
``ppermute`` on the mesh ring — D-PSGD [51], plus the compressed variants
DCD-PSGD [54] and CHOCO-SGD [164].  The mixing matrix is the symmetric ring
W = I(1-2w) + w(L+R), doubly stochastic with spectral gap rho < 1 (property
tested).  Asynchronous gossip (SGP [53]) and arbitrary graphs live in the
discrete-event simulator (`repro.core.simulate`) because SPMD programs are
bulk-synchronous (DESIGN.md §2).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


from repro.core import comms
from repro.core.compression.base import Compressed
from repro.core.types import CommConfig

f32 = jnp.float32


def ring_mixing_matrix(n: int, w: float = 1.0 / 3.0) -> np.ndarray:
    """Symmetric doubly-stochastic ring weights (benchmark/consensus use).
    Keep element-wise equal to :func:`ring_mixing_matrix_traced` — the
    engine-vs-reference equivalence tests of the training simulator rely on
    the two definitions agreeing (including n=2, where both neighbors
    coincide and the off-diagonal weight doubles)."""
    W = np.eye(n) * (1 - 2 * w)
    for j in range(n):
        W[j, (j + 1) % n] += w
        W[j, (j - 1) % n] += w
    return W


def ring_mixing_matrix_traced(n: int, w) -> jax.Array:
    """:func:`ring_mixing_matrix` with a *traced* weight ``w`` — the form the
    batched sweep engine builds inside jit so the mixing weight can vary per
    cell without retracing."""
    eye = jnp.eye(n, dtype=f32)
    ring = jnp.roll(eye, 1, axis=0) + jnp.roll(eye, -1, axis=0)
    return eye * (1 - 2 * w) + w * ring


def masked_mixing_matrix(W: jax.Array, m: jax.Array) -> jax.Array:
    """Renormalize a mixing matrix over the live peer set ``m`` (1 = alive,
    0 = dropped; both entries may be traced).

    A dead peer's column weight folds back into each live row's SELF weight
    (instead of dividing the row), so row sums are preserved EXACTLY and an
    all-ones mask reproduces ``W`` bitwise; dead rows become identity (their
    parameters freeze until rejoin).  For symmetric ``W`` the result stays
    symmetric — mass is conserved among the live workers."""
    n = W.shape[0]
    eye = jnp.eye(n, dtype=W.dtype)
    off = W * (1.0 - eye)
    dead_w = jnp.sum(off * (1.0 - m[None, :]), axis=1)  # weight lost per row
    Wm = off * m[None, :] + jnp.diag(jnp.diag(W) + dead_w)
    return m[:, None] * Wm + (1.0 - m[:, None]) * eye


def exp_mixing_matrix(n: int) -> np.ndarray:
    """One-peer exponential graph (powers of two), averaged over rounds."""
    import math

    rounds = max(1, int(math.log2(n)))
    W = np.zeros((n, n))
    for s in range(rounds):
        stride = 2**s
        Ws = np.eye(n) * 0.5
        for j in range(n):
            Ws[j, (j + stride) % n] += 0.5
        W += Ws / rounds
    return W


def spectral_gap(W: np.ndarray) -> float:
    ev = np.sort(np.abs(np.linalg.eigvals(W)))[::-1]
    return float(ev[1])


def _neighbor_sum(x: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """left+right neighbors on the ring formed by the (flattened) data axes.
    For multi-axis (pod,data) the ring runs within the innermost axis and
    wraps across the pod axis boundary via the same ppermute on that axis."""
    total = x
    axis = axes[-1]  # ring within the innermost data axis
    n = jax.lax.axis_size(axis)
    right = [(j, (j + 1) % n) for j in range(n)]
    left = [(j, (j - 1) % n) for j in range(n)]
    return comms.ppermute(x, axis, right) + comms.ppermute(x, axis, left)


def dpsgd_mix(params_flat: list[jax.Array], axes: tuple[str, ...], w=1.0 / 3.0,
              alive: jax.Array | None = None,
              rejoined: jax.Array | None = None):
    """D-PSGD [51]: x_i <- (1-2w) x_i + w (x_left + x_right).  ``w`` may be a
    *traced* scalar (the ``gossip_w`` knob) — the wire cost is w-independent,
    so every mixing weight shares one compiled program.

    ``alive`` (churn participation bit, traced scalar per shard): a dead
    peer's weight folds back into the live shard's self weight — each row of
    the effective mixing matrix keeps summing to 1 — and a dead shard keeps
    its own parameters untouched (frozen until rejoin).

    ``rejoined`` (the ``pull_avg`` rejoin policy): a shard re-entering this
    round replaces the partial mixing step with a full pull of its live
    neighbors' average — its stale parameters jump to the local consensus
    instead of dragging it.  Uses the values already on the wire; no extra
    transfer."""
    if alive is None:
        return [(1 - 2 * w) * p + w * _neighbor_sum(p, axes) for p in params_flat]
    axis = axes[-1]
    n = jax.lax.axis_size(axis)
    right = [(j, (j + 1) % n) for j in range(n)]
    left = [(j, (j - 1) % n) for j in range(n)]
    live_nbrs = (comms.ppermute(alive, axis, right)
                 + comms.ppermute(alive, axis, left))
    out = []
    for p in params_flat:
        ap = alive * p
        nbr = comms.ppermute(ap, axis, right) + comms.ppermute(ap, axis, left)
        mixed = (1 - w * live_nbrs) * p + w * nbr
        res = jnp.where(alive > 0, mixed, p)
        if rejoined is not None:
            pulled = nbr / jnp.maximum(live_nbrs, 1.0)
            res = jnp.where((rejoined > 0) & (live_nbrs > 0), pulled, res)
        out.append(res)
    return out


@dataclass
class ChocoState:
    """CHOCO-SGD [164] per-worker state: x_hat copies of self and the
    neighbor-average of x_hat."""

    x_hat: list[jax.Array]
    x_hat_nbr: list[jax.Array]  # sum of neighbors' x_hat


def choco_init(params_flat: list[jax.Array]) -> ChocoState:
    return ChocoState(
        [jnp.zeros_like(p) for p in params_flat],
        [jnp.zeros_like(p) for p in params_flat],
    )


def choco_mix(
    comm: CommConfig,
    compressor,
    key: jax.Array,
    params_flat: list[jax.Array],
    st: ChocoState,
    axes: tuple[str, ...],
    w=1.0 / 3.0,
    *,
    gamma=None,
    comp_knobs: tuple[dict, ...] | None = None,
    alive: jax.Array | None = None,
    rejoined: jax.Array | None = None,
) -> tuple[list[jax.Array], ChocoState]:
    """One CHOCO-SGD communication round: exchange q = C(x - x_hat) with ring
    neighbors; supports *biased* compressors (the method's point).

    ``gamma`` (CHOCO step size), ``w`` (ring weight) and ``comp_knobs`` (one
    traced knob dict per bucket) may all be traced scalars — cells differing
    only in these values share one compiled gossip step.

    Churn (``alive``/``rejoined``, traced scalars per shard) preserves the
    mirror-drift invariant ``x_hat_nbr_i == sum_j∈nbr(i) x_hat_j``:

    * a DEAD shard freezes (params, mirrors) and its payload is weighted 0
      by receivers — both sides of the invariant stop moving together;
    * a REJOINING shard snaps its mirror to its fresh params
      (``x_hat := x``) and broadcasts the EXACT delta ``x - x_hat`` on a
      dense resync channel (tagged ``churn_resync``) so every neighbor's
      mirror-sum absorbs the snap consistently, and rebuilds its own
      ``x_hat_nbr`` from the neighbors' dense ``x_hat`` exchange.

    At dropout 0 every selection reduces to the churn-free value (the
    resync channel carries zeros), so the round reproduces the plain one."""
    from repro.core.compression.base import compress_p, decompress_p

    gamma = comm.gossip_step_size if gamma is None else gamma
    new_x, new_hat, new_nbr = [], [], []
    if alive is None:
        for i, (p, xh, xn) in enumerate(zip(params_flat, st.x_hat, st.x_hat_nbr)):
            kn = comp_knobs[i] if comp_knobs is not None else None
            c = compress_p(compressor, jax.random.fold_in(key, i), (p - xh).reshape(-1), kn)
            q_self = decompress_p(compressor, c, kn).reshape(p.shape)
            # send the *payload* to both neighbors (wire = compressed)
            q_nbr = _neighbor_sum_payload(compressor, c, axes, kn).reshape(p.shape)
            xh2 = xh + q_self
            xn2 = xn + q_nbr
            # x <- x + gamma * (sum_j w_ij xhat_j - xhat_i); ring: w on each nbr
            p2 = p + gamma * (w * xn2 - 2 * w * xh2)
            new_x.append(p2)
            new_hat.append(xh2)
            new_nbr.append(xn2)
        return new_x, ChocoState(new_hat, new_nbr)

    r = jnp.zeros((), f32) if rejoined is None else rejoined
    axis = axes[-1]
    n = jax.lax.axis_size(axis)
    right = [(j, (j + 1) % n) for j in range(n)]
    left = [(j, (j - 1) % n) for j in range(n)]
    a_nb = [comms.ppermute(alive, axis, perm) for perm in (right, left)]
    r_nb = [comms.ppermute(r, axis, perm) for perm in (right, left)]
    for i, (p, xh, xn) in enumerate(zip(params_flat, st.x_hat, st.x_hat_nbr)):
        kn = comp_knobs[i] if comp_knobs is not None else None
        c = compress_p(compressor, jax.random.fold_in(key, i), (p - xh).reshape(-1), kn)
        q_self = decompress_p(compressor, c, kn).reshape(p.shape)
        # compressed channel: peer contribution weighted by its alive bit;
        # zeroed on the peer's rejoin round (the exact delta replaces it)
        q_nbr = jnp.zeros_like(p)
        for perm, a_p, r_p in zip((right, left), a_nb, r_nb):
            payload = {k: comms.ppermute(v, axis, perm) for k, v in c.payload.items()}
            dec = decompress_p(compressor, Compressed(payload, c.n), kn).reshape(p.shape)
            q_nbr = q_nbr + a_p * (1.0 - r_p) * dec
        # mirror snap + exact-delta broadcast + dense mirror rebuild
        xh2 = jnp.where(alive > 0, jnp.where(r > 0, p, xh + q_self), xh)
        with comms.tag("churn_resync"):
            rd = r * (p - xh)
            rd_nbr = (comms.ppermute(rd, axis, right)
                      + comms.ppermute(rd, axis, left))
            xh2_nbr = (comms.ppermute(xh2, axis, right)
                       + comms.ppermute(xh2, axis, left))
        xn2 = jnp.where(alive > 0,
                        jnp.where(r > 0, xh2_nbr, xn + q_nbr + rd_nbr),
                        xn)
        p2 = jnp.where(alive > 0, p + gamma * (w * xn2 - 2 * w * xh2), p)
        new_x.append(p2)
        new_hat.append(xh2)
        new_nbr.append(xn2)
    return new_x, ChocoState(new_hat, new_nbr)


def _neighbor_sum_payload(
    compressor, c: Compressed, axes: tuple[str, ...],
    comp_knobs: dict | None = None,
) -> jax.Array:
    """Sum of both neighbors' decompressed payloads, exchanging only the
    compressed wire format."""
    from repro.core.compression.base import decompress_p

    axis = axes[-1]
    n = jax.lax.axis_size(axis)
    right = [(j, (j + 1) % n) for j in range(n)]
    left = [(j, (j - 1) % n) for j in range(n)]
    total = None
    for perm in (right, left):
        payload = {k: comms.ppermute(v, axis, perm) for k, v in c.payload.items()}
        dec = decompress_p(compressor, Compressed(payload, c.n), comp_knobs)
        total = dec if total is None else total + dec
    return total
