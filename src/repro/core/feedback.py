"""Auxiliary technologies (paper §IX): error accumulation / feedback,
momentum correction, global momentum compression, local gradient clipping,
and warm-up sparsity scheduling.

All functions operate on *flat per-bucket vectors* (the aggregation layer
flattens tensors/buckets) and on explicit state pytrees, so they compose
with any compressor and live inside the jitted train step.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.types import CommConfig

f32 = jnp.float32


def local_clip(g: jax.Array, thr, n_workers: int) -> jax.Array:
    """Local Gradient Clipping [25] (§IX-C): each worker clips at
    thr / sqrt(N) so the aggregated gradient keeps the global threshold.
    ``thr`` may be a traced scalar (the bundle-cache knob path); only a
    *static* zero short-circuits."""
    if isinstance(thr, (int, float)) and not thr:
        return g
    local_thr = thr * (n_workers ** -0.5)
    norm = jnp.linalg.norm(g)
    return g * jnp.minimum(1.0, local_thr / jnp.maximum(norm, 1e-30))


def warmup_ratio(base_ratio: float, step: jax.Array, warmup_steps: int) -> jax.Array:
    """DGC warm-up [25] (§IX-D): sparsity ramps exponentially from 25% kept
    to the target ratio over ``warmup_steps``.  NOTE: returns a *traced*
    ratio — usable only by compressors that consume a dynamic budget
    (wangni/threshold); top-k keeps static k and applies warm-up by masking.
    """
    if not warmup_steps:
        return jnp.asarray(base_ratio, f32)
    t = jnp.minimum(step.astype(f32) / warmup_steps, 1.0)
    return jnp.exp(jnp.log(0.25) * (1 - t) + jnp.log(base_ratio) * t)


def pre_compress(
    comm: CommConfig,
    g: jax.Array,
    state: dict[str, Any],
    idx: int,
    n_workers: int,
    knobs: dict[str, Any],
    alive: jax.Array | None = None,
) -> jax.Array:
    """Momentum correction + EF accumulation + local clipping (order per
    DGC [25]): returns the vector handed to the compressor.

    The on/off *flags* come from ``comm`` (structural — they decide which
    state buffers exist); the coefficients come from the traced ``knobs``
    tree, so cells differing only in momentum / clip / EF-decay values
    share one compiled program.  ``alive`` (churn participation bit):
    a masked-out shard neither sends nor accumulates — its momentum buffer
    freezes here and its EF residual freezes in :func:`post_compress`."""
    if comm.momentum_correction:
        u = knobs["momentum"] * state["u"][idx] + g
        state["u"][idx] = u if alive is None else jnp.where(alive > 0, u, state["u"][idx])
        g = u
    if comm.local_clip:
        g = local_clip(g, knobs["local_clip"], n_workers)
    if comm.error_feedback:
        g = state["ef"][idx] * knobs["ef_decay"] + g
    return g


def post_compress(
    comm: CommConfig,
    g_in: jax.Array,
    g_hat: jax.Array,
    state: dict[str, Any],
    idx: int,
    alive: jax.Array | None = None,
) -> None:
    """Error accumulation update e = a - C(a) (§IX-A, eq. block).  A
    masked-out shard (``alive == 0``) sent nothing, so its residual stays
    frozen until it rejoins.  This is the *freeze* half of the
    freeze→resync rejoin protocol — the *resync* half (dropping the stale
    residual and momentum row on the shard's rejoin step) lives with the
    rejoin detection in :func:`repro.core.aggregate.aggregate_buckets`,
    which zeroes ``state["ef"]``/``state["u"]`` before the round."""
    if comm.error_feedback:
        new = g_in - g_hat
        if alive is not None:
            new = jnp.where(alive > 0, new, state["ef"][idx])
        state["ef"][idx] = new
