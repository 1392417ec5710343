"""Optimizers (pure-pytree, f32 state, bf16-param-safe).

The update consumes the *aggregated* gradient produced by
``repro.core.aggregate`` — for majority-vote SignSGD the aggregate is the
vote itself, so plain SGD on it reproduces [173]'s update rule.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp


f32 = jnp.float32


@dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any, jax.Array], tuple[Any, Any]]
    name: str = "opt"
    #: hashable identity of coefficients NOT already encoded in ``name``
    #: (adamw betas, nesterov flag) — part of the step-bundle cache key
    fingerprint: tuple = ()


def sgd() -> Optimizer:
    def init(params):
        return ()

    def update(grads, state, params, lr):
        new = jax.tree.map(
            lambda p, g: (p.astype(f32) - lr * g.astype(f32)).astype(p.dtype), params, grads
        )
        return new, state

    return Optimizer(init, update, "sgd")


def momentum_sgd(m: float = 0.9, nesterov: bool = False) -> Optimizer:
    def init(params):
        return {"v": jax.tree.map(lambda p: jnp.zeros(p.shape, f32), params)}

    def update(grads, state, params, lr):
        v = jax.tree.map(lambda v, g: m * v + g.astype(f32), state["v"], grads)
        if nesterov:
            step = jax.tree.map(lambda g, vv: g.astype(f32) + m * vv, grads, v)
        else:
            step = v
        new = jax.tree.map(lambda p, s: (p.astype(f32) - lr * s).astype(p.dtype), params, step)
        return new, {"v": v}

    return Optimizer(init, update, f"momentum{m}", (nesterov,))


def adamw(b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8, wd: float = 0.0) -> Optimizer:
    def init(params):
        return {
            "m": jax.tree.map(lambda p: jnp.zeros(p.shape, f32), params),
            "v": jax.tree.map(lambda p: jnp.zeros(p.shape, f32), params),
            "t": jnp.zeros((), jnp.int32),
        }

    def update(grads, state, params, lr):
        t = state["t"] + 1
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(f32), state["m"], grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(f32)), state["v"], grads)
        bc1 = 1 - b1**t.astype(f32)
        bc2 = 1 - b2**t.astype(f32)

        def upd(p, m_, v_):
            step = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            if wd:
                step = step + wd * p.astype(f32)
            return (p.astype(f32) - lr * step).astype(p.dtype)

        new = jax.tree.map(upd, params, m, v)
        return new, {"m": m, "v": v, "t": t}

    return Optimizer(init, update, "adamw", (b1, b2, eps, wd))


def zero1(opt: Optimizer, data_axes: tuple[str, ...]) -> Optimizer:
    """ZeRO-1 optimizer-state sharding over the gradient (data) axes.

    Each data shard keeps a 1/n slice of every optimizer-state leaf, updates
    its parameter slice, and the new parameters are re-assembled with one
    all_gather (counted by the comms accounting, tag 'zero1_gather').
    Orthogonal to the paper's techniques; standard production memory lever
    (DeepSpeed ZeRO / optimizer state sharding).
    """
    import numpy as np

    from repro.core import comms

    def n_shards():
        n = 1
        for a in data_axes:
            n *= jax.lax.axis_size(a)
        return n

    def shard_index():
        i = jnp.zeros((), jnp.int32)
        for a in data_axes:
            i = i * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return i

    def _slice(leaf):
        n = n_shards()
        flat = leaf.reshape(-1)
        pad = (-flat.size) % n
        flat = jnp.pad(flat, (0, pad))
        return jax.lax.dynamic_slice_in_dim(
            flat.reshape(n, -1), shard_index(), 1, axis=0
        )[0]

    def init(params):
        sliced = jax.tree.map(_slice, params)
        inner = opt.init(sliced)
        return {"inner": inner}

    def update(grads, state, params, lr):
        g_sl = jax.tree.map(_slice, grads)
        p_sl = jax.tree.map(_slice, params)
        new_sl, inner = opt.update(g_sl, state["inner"], p_sl, lr)

        def regather(p, new_slice):
            n = n_shards()
            with comms.tag("zero1_gather"):
                full = comms.all_gather(new_slice, data_axes, axis=0, tiled=True)
            return full[: p.size].reshape(p.shape).astype(p.dtype)

        new_params = jax.tree.map(regather, params, new_sl)
        return new_params, {"inner": inner}

    return Optimizer(init, update, f"zero1_{opt.name}", opt.fingerprint)


def global_clip(grads: Any, max_norm) -> Any:
    """Global-norm gradient clipping (vanilla [223]; the *local* variant
    lives in repro.core.feedback.local_clip).  ``max_norm`` may be a traced
    scalar (the bundle-cache path passes the threshold as a CommKnobs
    value); only a *static* zero short-circuits."""
    if isinstance(max_norm, (int, float)) and not max_norm:
        return grads
    g2 = sum(jnp.sum(jnp.square(g.astype(f32))) for g in jax.tree.leaves(grads))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(jnp.sqrt(g2), 1e-30))
    return jax.tree.map(lambda g: (g.astype(f32) * scale).astype(g.dtype), grads)
